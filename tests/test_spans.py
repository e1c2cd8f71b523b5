"""The one span call (``repro.obs.span``): every serving stage timed once,
on the profiler's clock and on the in-memory timeline alike.

The profiler tests trace tiny CPU runs with ``jax.profiler`` and read the
host plane back, so they check what a device trace on the chip would
hold beside its ops: the ``repro.<layer>.<stage>`` spans of the main and
the prefetch threads."""
import dataclasses
import glob
import os
import sys
import threading

import jax
import numpy as np
import pytest

from repro.cache import CacheConfig
from repro.configs import dlrm as dlrm_cfg
from repro.models import dlrm as dlrm_mod
from repro.obs import Telemetry, Tracer, profiled_spans, span
from repro.pipeline import PipelineTrace
from repro.serving.engine import CTRRequest, make_dlrm_engine

B = 4           # micro-batch
N_PIPED = 3     # micro-batches through the pipeline


def _cfg(depth):
    return dataclasses.replace(
        dlrm_cfg.smoke(), kernel_mode="reference",
        cache=CacheConfig(rows=32, pipeline_depth=depth))


def _engine(depth, telemetry=None, seed=0):
    cfg = _cfg(depth)
    params = dlrm_mod.init_params(jax.random.key(seed), cfg)
    eng = make_dlrm_engine(params, cfg, batch_size=B, telemetry=telemetry)
    T, L, F = cfg.num_sparse_features, cfg.pooling, cfg.num_dense_features
    rng = np.random.default_rng(seed)

    def submit(n, first=0):
        for rid in range(first, first + n):
            eng.submit(CTRRequest(
                rid=rid, dense=rng.standard_normal(F).astype(np.float32),
                indices=rng.integers(0, cfg.rows_per_table, (T, L),
                                     dtype=np.int32),
                lengths=np.full(T, L, np.int32)))
    return eng, submit


def _delta(after, before):
    """Seconds and count per span name between two totals of one
    profiler session."""
    out = {}
    for name, (sec, n) in after.items():
        sec0, n0 = before.get(name, (0.0, 0))
        if n != n0:
            out[name] = (sec - sec0, n - n0)
    return out


def _host_events(log_dir):
    """``(thread line, name)`` of every ``repro.*`` host event; each host
    thread has a line of its own."""
    (path,) = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                      "*.xplane.pb"))
    data = jax.profiler.ProfileData.from_file(path)
    return [((plane.name, i), ev.name) for plane in data.planes
            if plane.name.startswith("/host:")
            for i, line in enumerate(plane.lines) for ev in line.events
            if ev.name.startswith("repro.")]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """One serial flush and one pipelined ``run_to_completion`` of
    ``N_PIPED`` micro-batches, under one profiler trace."""
    serial, submit_serial = _engine(1)
    piped, submit_piped = _engine(2, seed=1)
    submit_serial(B)
    submit_piped(B * N_PIPED)
    timers = {name: (eng.cache_stats(), (eng.cache_stats().prefetch_s,
                                         eng.cache_stats().scatter_s))
              for name, eng in (("serial", serial), ("piped", piped))}
    log_dir = str(tmp_path_factory.mktemp("profile"))
    seen = len(profiled_spans())
    jax.profiler.start_trace(log_dir)
    try:
        serial.flush()
        mid = profiled_spans()[-1]
        piped.run_to_completion()
    finally:
        jax.profiler.stop_trace()
    (session,) = profiled_spans()[seen:]
    return {"events": _host_events(log_dir),
            "profiled": session,
            "serial": (mid,) + timers["serial"],
            "piped": (_delta(session, mid),) + timers["piped"]}


def test_span_without_tracer_records_nothing_in_memory():
    tracer = Tracer()
    before = profiled_spans()
    with span("repro.test.alone") as s:
        pass
    assert 0.0 < s.t0 <= s.t1 and s.seconds == s.t1 - s.t0
    assert tracer.event_count == 0
    # no profiler ran, so nothing was totalled for a traced window
    assert profiled_spans() == before
    # with a tracer, the in-memory span carries the stamps the call took
    with span("repro.test.lane", tracer, "cache.admit", lane="cache",
              cat="cache", args={"tier": "host"}) as s2:
        pass
    (rec,) = tracer.spans()
    assert (rec.name, rec.lane, rec.cat, rec.args) == \
        ("cache.admit", "cache", "cache", {"tier": "host"})
    assert (rec.t0, rec.t1) == (s2.t0, s2.t1)
    # a body that raises still closes the span, and lands it
    with pytest.raises(RuntimeError):
        with span("repro.test.raises", tracer) as s3:
            raise RuntimeError("boom")
    assert s3.t1 >= s3.t0 > 0.0
    assert tracer.spans(name="repro.test.raises")


def test_every_stage_span_reaches_the_profiler(traced):
    events = traced["events"]
    names = {n for _, n in events}
    assert names >= {
        "repro.engine.pad", "repro.engine.prefetch",
        "repro.engine.dispatch", "repro.engine.collect",
        "repro.cache.admit", "repro.cache.fetch", "repro.cache.scatter",
        "repro.pipeline.prestage", "repro.pipeline.swap"}
    count = {n: sum(1 for _, m in events if m == n) for n in names}
    # once per micro-batch: one serial flush + the pipeline's batches
    for n in ("repro.engine.pad", "repro.engine.dispatch",
              "repro.engine.collect"):
        assert count[n] == 1 + N_PIPED, n
    for n in ("repro.pipeline.prestage", "repro.pipeline.swap"):
        assert count[n] == N_PIPED, n
    assert count["repro.cache.admit"] == 1 + N_PIPED
    # the prefetch worker's stages sit on a thread line of their own
    main = {line for line, n in events if n == "repro.engine.dispatch"}
    assert len(main) == 1
    worker = {line for line, n in events if n == "repro.pipeline.prestage"}
    assert worker and not worker & main
    for n in ("repro.cache.admit", "repro.cache.fetch",
              "repro.cache.scatter"):
        assert {line for line, m in events if m == n} & worker, n
    # the totals of the profiled spans count what the trace holds
    assert {n: c for n, (_, c) in traced["profiled"].items()} == count


@pytest.mark.parametrize("engine", ["serial", "piped"])
def test_cache_stage_timers_read_the_span_stamps(traced, engine):
    delta, stats, (pre0, sc0) = traced[engine]
    got = (stats.prefetch_s - pre0) + (stats.scatter_s - sc0)
    names = ["repro.cache.admit", "repro.cache.fetch", "repro.cache.scatter"]
    if engine == "piped":
        names.append("repro.pipeline.prestage")
    spans = sum(delta[n][0] for n in names)
    assert got == pytest.approx(spans, rel=1e-9, abs=1e-12)
    assert got > 0


def test_lanes_keep_their_in_memory_names():
    """With a Telemetry the lanes hold the names they always held; no
    profiler name lands in memory."""
    tel = Telemetry()
    serial, submit = _engine(1, telemetry=tel)
    submit(2 * B)
    serial.run_to_completion()
    by_lane = {}
    for s in tel.tracer.spans():
        by_lane.setdefault(s.lane, set()).add(s.name)
    assert by_lane == {
        "engine": {"dlrm.prefetch", "dlrm.forward"},
        "cache": {"cache.admit", "cache.fetch", "cache.scatter"},
        "request": {"request"}}
    assert len(tel.tracer.spans(name="dlrm.forward")) == 2

    tel2 = Telemetry()
    piped, submit2 = _engine(2, telemetry=tel2, seed=2)
    submit2(2 * B)
    piped.run_to_completion()
    by_lane = {}
    for s in tel2.tracer.spans():
        by_lane.setdefault(s.lane, set()).add(s.name)
    assert by_lane == {
        "pipeline": {f"pipeline.{st}" for st in
                     ("admit", "fetch", "scatter", "forward", "swap")},
        "request": {"request"}}
    # one lane span per StageSpan, with the same stamps
    lane = sorted((s.name, s.t0, s.t1) for s in tel2.tracer.spans(
        lane="pipeline"))
    kept = sorted((f"pipeline.{s.stage}", s.start, s.end)
                  for s in piped.trace.spans)
    assert lane == kept
    # the forward runs from its dispatch span's start to its collect's
    stats = piped.cache_stats()
    assert stats.forward_s == pytest.approx(
        piped.trace.total("forward"), rel=1e-12)


def test_overlap_is_summed_run_by_run():
    """Each run's overlap comes from its own spans; the total is the
    same as over every span at once, since runs do not overlap."""
    tr = PipelineTrace()
    tr.record("forward", 0, 0.0, 1.0)
    tr.record("fetch", 1, 0.5, 1.5)      # 0.5 s inside the forward
    first = tr.close_run()
    assert first == pytest.approx(0.5)
    tr.record("forward", 1, 2.0, 3.0)
    tr.record("admit", 2, 2.5, 3.5)      # 0.5 s inside
    tr.record("fetch", 2, 1.0, 2.25)     # 0.25 s inside
    assert tr.overlap_s() == pytest.approx(1.25)   # the open run counts
    assert tr.close_run() == pytest.approx(0.75)
    assert tr.overlap_s() == pytest.approx(1.25)
    assert tr.close_run() == 0.0         # an empty run adds nothing
    tr.clear()
    assert tr.overlap_s() == 0.0 and tr.close_run() == 0.0


def test_profiled_totals_survive_concurrent_threads(tmp_path):
    """Spans closing on many threads at once lose no count, and the
    threads' first spans open one session between them."""
    n_threads, per_thread = 16, 200
    seen = len(profiled_spans())
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    jax.profiler.start_trace(str(tmp_path))
    try:
        def work():
            for _ in range(per_thread):
                with span("repro.test.stress"):
                    pass
        threads = [threading.Thread(target=work) for _ in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        jax.profiler.stop_trace()
        sys.setswitchinterval(old)
    (session,) = profiled_spans()[seen:]
    assert session["repro.test.stress"][1] == n_threads * per_thread


def test_profiled_totals_are_kept_per_session(tmp_path):
    """Each profiler session keeps its own totals; a span counts only in
    the session it opened and closed in."""
    seen = len(profiled_spans())
    outer = span("repro.test.straddles")
    outer.__enter__()                    # opens before the first session
    jax.profiler.start_trace(str(tmp_path / "a"))
    try:
        outer.__exit__(None, None, None)
        for _ in range(3):
            with span("repro.test.first"):
                pass
    finally:
        jax.profiler.stop_trace()
    with span("repro.test.between"):     # the profiler is off
        pass
    jax.profiler.start_trace(str(tmp_path / "b"))
    try:
        with span("repro.test.second") as s:
            pass
    finally:
        jax.profiler.stop_trace()
    first, second = profiled_spans()[seen:]
    assert {n: c for n, (_, c) in first.items()} == {"repro.test.first": 3}
    assert second == {"repro.test.second": (s.seconds, 1)}
