"""CPU tests of the readers of the program's stage spans (``bench/spans.py``):
their arithmetic, their silence where the program keeps no spans, and
that the names they read are the names a tiny run of the program
writes while the profiler records.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, idle_split, spans, trace  # noqa: E402
from test_bench import _events, _run, tiny_cell  # noqa: E402

TOTALS = {"repro.engine.pad": (0.006, 2),
          "repro.engine.dispatch": (0.002, 2),
          "repro.engine.collect": (0.004, 2),
          "repro.engine.prefetch": (0.5, 2),
          "repro.cache.admit": (0.2, 2),
          "repro.cache.fetch": (0.1, 2),
          "repro.cache.scatter": (0.04, 2),
          "repro.pipeline.prestage": (0.01, 2)}

READERS = ("flush_host_ms.offline", "flush_host_ms.server", "admit_ms",
           "fetch_ms", "scatter_ms")


# what each reader of the accepted benchmark gives on ``_run()`` (the
# recorded synthetic trace of ``test_bench``), and with a tiered store's
# counters in place of a trace
EXISTING = {"submit_us.offline": 0.04 / 2048 * 1e6,
            "submit_us.server": 0.04 / 2048 * 1e6,
            "step_mfu": 100 * 1000 * 1024 / 2e12,
            "step_mfu.server": 100 * 1000 * 1024 / 2e12,
            "tbe_roofline.offline": 50.0, "tbe_roofline.server": 50.0,
            "rw_collective_ms": 1e-6 / 2 * 1e3,
            "device_idle.offline": 65.0, "device_idle.server": 65.0,
            "hit_rate": None, "prefetch_ms": None}
EXISTING_CACHED = {"hit_rate": 86.0, "prefetch_ms": 50.0}


def _with_program_spans(events):
    """``events`` with the program's stage spans on the host plane: on
    the main thread's line and on a prefetch thread's, one of them
    reaching past the window's end."""
    E = trace.Event
    host = "/host:CPU"
    return events + [E(host, "python3", "repro.engine.pad", 4000, 400),
                     E(host, "python3", "repro.engine.dispatch", 4400, 100),
                     E(host, "python3 #2", "repro.cache.admit", 7500, 1000),
                     E(host, "python3 #2", "repro.cache.fetch", 8500, 3000),
                     E(host, "python3", "repro.engine.collect", 9000, 200)]


def test_existing_readers_do_not_move(monkeypatch):
    """The accepted per-layer metrics read what they read before, also
    when the trace and the program's totals hold the stage spans."""
    accepted = {m["name"] for m in json.loads(
        (ROOT / "BENCHMARK.json").read_text())["per_layer"]}
    assert set(EXISTING) <= accepted
    plain = trace.summarize(_events(), chips=2)
    spanned = trace.summarize(_with_program_spans(_events()), chips=2)
    assert spanned == plain
    monkeypatch.setattr(spans, "profiled", lambda: dict(TOTALS))
    for summary in (plain, spanned):
        run = _run(trace=summary)
        cached = _run(cache=(86, 100, 0.5, 10), chips=1, trace=None)
        for name, want in EXISTING.items():
            got = harness.reader(name)(run)
            assert got == (None if want is None else pytest.approx(want)), \
                name
        for name, want in EXISTING_CACHED.items():
            assert harness.reader(name)(cached) == pytest.approx(want), name


def test_span_reader_arithmetic(monkeypatch):
    monkeypatch.setattr(spans, "profiled", lambda: dict(TOTALS))
    run = _run()                          # two micro-batches, traced
    assert spans.flush_host_ms(run) == pytest.approx(
        (0.006 + 0.002 + 0.004) / 2 * 1e3)
    assert spans.admit_ms(run) == pytest.approx(100.0)
    assert spans.fetch_ms(run) == pytest.approx(50.0)
    assert spans.scatter_ms(run) == pytest.approx(20.0)
    # each metric's file binds its reader
    for name in READERS:
        assert harness.reader(name)(run) == pytest.approx(
            getattr(spans, name.split(".")[0])(run))
    # an untraced run reads nothing
    assert all(harness.reader(n)(_run(trace=None)) is None for n in READERS)


def test_span_readers_fall_silent_without_spans(monkeypatch):
    run = _run()
    # a process that saw no profiler session, or two: no window's totals
    import repro.obs

    for sessions in ([], [dict(TOTALS), dict(TOTALS)]):
        monkeypatch.setattr(repro.obs, "profiled_spans", lambda: sessions)
        assert spans.profiled() is None
        assert all(harness.reader(n)(run) is None for n in READERS)
    monkeypatch.setattr(repro.obs, "profiled_spans", lambda: [dict(TOTALS)])
    assert spans.profiled() == TOTALS
    # a program without the span call (the reader's import fails)
    monkeypatch.setattr(spans, "profiled", lambda: None)
    assert all(harness.reader(n)(run) is None for n in READERS)
    # a cell with no tiered store: the engine spans but no cache spans
    engine_only = {k: v for k, v in TOTALS.items()
                   if k.startswith("repro.engine.")}
    monkeypatch.setattr(spans, "profiled", lambda: engine_only)
    assert spans.flush_host_ms(run) is not None
    assert spans.admit_ms(run) is None and spans.scatter_ms(run) is None
    # a partial engine split is no reading
    partial = {k: v for k, v in engine_only.items()
               if k != "repro.engine.collect"}
    monkeypatch.setattr(spans, "profiled", lambda: partial)
    assert spans.flush_host_ms(run) is None


def window_spans(workload: str, log_dir: str):
    """A tiny window of the cell under the profiler: the program's span
    totals of the window alone (not the warm-up's), ``{name: (seconds,
    count)}``, and the window's micro-batch count."""
    import jax

    from bench import traffic
    from bench.models import dlrm
    from repro.obs import profiled_spans

    cell = tiny_cell(workload)
    cfg, mix = cell.config, cell.mix
    log = (traffic.log_counts(mix, cfg["rows_per_table"])
           if "cache_log_lookups" in mix and cfg["cache"] else None)
    seed = 2 ** 31 + 5
    system = dlrm.System(seed, cfg, jax.devices()[:cell.chips], log)
    pool = traffic.samples(seed, cfg, mix, mix["distinct_samples"])
    warm = mix["warmup_batches"] * cfg["batch_size"]
    harness.submit(system, pool, 0, warm)
    system.drain()
    seen = len(profiled_spans())
    jax.profiler.start_trace(log_dir)
    try:
        win = harness.offline(system, pool, mix, 0.2, warm,
                              harness.no_annotation)
    finally:
        jax.profiler.stop_trace()
    (window,) = profiled_spans()[seen:]
    system.close()
    return window, len(win.batches)


_WINDOW = """
import sys, json
sys.path[:0] = [{root!r}, {root!r} + "/src", {tests!r}]
from test_span_readers import window_spans
print(json.dumps(window_spans({workload!r}, {log_dir!r})))
"""


@pytest.mark.parametrize("workload,present", [
    ("hbm.uniform.offline", {"flush_host_ms"}),
    ("tiered.zipf.offline", {"flush_host_ms", "admit_ms", "fetch_ms",
                             "scatter_ms"}),
    ("rw4.uniform.offline", {"flush_host_ms"})])
def test_readers_read_what_the_program_writes(monkeypatch, tmp_path, workload,
                                             present):
    """The readers find exactly their cells' spans in a tiny window of
    the cell (on four CPU devices, in a process of its own, for the
    row-wise cell)."""
    if tiny_cell(workload).chips == 1:
        window, batches = window_spans(workload, str(tmp_path))
    else:
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   XLA_FLAGS="--xla_force_host_platform_device_count=4")
        code = _WINDOW.format(root=str(ROOT), tests=str(Path(__file__).parent),
                              workload=workload, log_dir=str(tmp_path))
        p = subprocess.run([sys.executable, "-c", code], env=env,
                           capture_output=True, text=True, timeout=600)
        assert p.returncode == 0, p.stderr[-3000:]
        window, batches = json.loads(p.stdout.strip().splitlines()[-1])
        window = {name: tuple(v) for name, v in window.items()}
    # once per micro-batch, and again for each split of one whose working
    # set overflowed the tiny pool
    for name in spans.ENGINE_HOST:
        assert window[name][1] >= batches, name
    if "admit_ms" in present:
        assert window["repro.cache.admit"][1] >= batches
    else:
        assert window["repro.engine.pad"][1] == batches
    monkeypatch.setattr(spans, "profiled", lambda: window)
    win = harness.Window("offline", 0.2, 0, 0, 0.0, [range(0)] * batches, {})
    run = _run(window=win)
    got = {n for n in ("flush_host_ms", "admit_ms", "fetch_ms", "scatter_ms")
           if getattr(spans, n)(run) is not None}
    assert got == present


def test_idle_split_names_gaps_by_the_program_span():
    """Span seconds are clipped to the window and summed over threads; a
    gap takes the latest-started program span open at its midpoint, on
    whichever thread, and keeps the benchmark's name without one."""
    got = idle_split.split(_with_program_spans(_events()))
    assert got["window_s"] == pytest.approx(10000e-9)
    assert got["span_s"] == pytest.approx({
        "repro.engine.pad": 400e-9, "repro.engine.dispatch": 100e-9,
        "repro.cache.admit": 1000e-9,
        "repro.cache.fetch": 2500e-9,        # clipped at the window's end
        "repro.engine.collect": 200e-9})
    assert set(got["span_n"].values()) == {1}
    # chip 0 idles over [1000, 4500) (submit; the drain and its pad
    # start at 4000, after the midpoint) and [8000, 11000) (drain; the
    # prefetch thread's fetch is the latest-started span open at 9500,
    # though collect on the main thread started later and closed before)
    assert got["idle"] == pytest.approx({
        "bench.submit": 3500e-9, "bench.drain/repro.cache.fetch": 3000e-9})
    assert got["idle_in_program_pct"] == pytest.approx(30.0)
    # the names without program spans are the breakdown's own
    plain = idle_split.split(_events())
    assert plain["idle"] == pytest.approx(
        trace.summarize(_events(), chips=2).idle_by_host)
    assert plain["span_s"] == {} and plain["idle_in_program_pct"] == 0.0


_EXECUTE = """
import json, sys, time
from pathlib import Path
sys.path[:0] = [{root!r}, {root!r} + "/src", {tests!r}]
import jax
from bench import harness, trace
from repro.obs import profiled_spans
from test_bench import tiny_cell

cell = tiny_cell({workload!r})
cell.per_layer = [m for m in harness.load_cell(Path({root!r}),
                                               {workload!r}).per_layer
                  if m["name"] in {readers!r}]
events, load = [], trace.load

def keep(log_dir):
    events.extend(load(log_dir))
    return events

# a CPU trace has no TPU plane to summarize: the device side is a stand-in
trace.load = keep
trace.summarize = lambda ev, chips: trace.Summary(1.0, [0.5], [{{}}], {{}})
result, _, info = harness.execute(cell, 2 ** 31 + 9, 0.5, Path({log_dir!r}),
                                  time.perf_counter(), jax.devices()[:1])
host = {{}}
for e in events:
    if e.name.startswith("repro.") and not trace.DEVICE_PLANE.match(e.plane):
        sec, n = host.get(e.name, (0.0, 0))
        host[e.name] = (sec + e.dur_ns * 1e-9, n + 1)
print(json.dumps({{"metrics": result["metrics"], "correct": result["correct"],
                  "batches": info["micro_batches"], "host": host,
                  "sessions": profiled_spans()}}))
"""


def test_a_traced_run_reads_the_program_spans(tmp_path):
    """``harness.execute`` with the profiler on, in a process of its own
    as ``bench/run.py`` runs it: each span metric of the tiered cell is
    its spans' seconds in the one profiler session per micro-batch, and
    the session counts the spans the trace holds."""
    code = _EXECUTE.format(root=str(ROOT), tests=str(Path(__file__).parent),
                           workload="tiered.zipf.offline",
                           readers=READERS, log_dir=str(tmp_path / "t"))
    p = subprocess.run([sys.executable, "-c", code],
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["correct"]
    (session,) = got["sessions"]
    assert {n: c for n, (_, c) in session.items()} == \
        {n: c for n, (_, c) in got["host"].items()}
    per_batch = {n: sec / got["batches"] * 1e3
                 for n, (sec, _) in session.items()}
    want = {"flush_host_ms.offline": sum(per_batch[n]
                                         for n in spans.ENGINE_HOST),
            "admit_ms": per_batch["repro.cache.admit"],
            "fetch_ms": per_batch["repro.cache.fetch"],
            "scatter_ms": per_batch["repro.cache.scatter"]}
    assert {n: m["value"] for n, m in got["metrics"].items()} == \
        pytest.approx(want, rel=1e-9)
    # the span call's own stamps lie inside the profiler's interval
    for name, (sec, n) in session.items():
        assert sec <= got["host"][name][0] + 1e-9 * n, name
