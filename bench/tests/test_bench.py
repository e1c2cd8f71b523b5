"""CPU tests of the benchmark: its arithmetic, its generators, its refusal
to run without a chip, and whole runs at a tiny size, sound and with the
timed path broken.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, readers, stats, trace, traffic  # noqa: E402
from bench.models import dlrm, dlrm_reference as ref  # noqa: E402

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

# CONFIG's structure at a size the Pallas interpreter runs in seconds
TINY = {"num_dense_features": 4, "num_sparse_features": 4,
        "embedding_dim": 16, "rows_per_table": 256, "pooling": 4,
        "bottom_mlp": [32, 16], "top_mlp": [64, 32, 1],
        "kernel_mode": "interpret", "batch_size": 32}
TINY_MIX = {"uniform.offline": {"distinct_samples": 512, "warmup_batches": 2,
                                "batches_per_call": 2},
            "zipf.offline": {"distinct_samples": 1024, "warmup_batches": 4,
                             "batches_per_call": 2},
            "zipf.server": {"distinct_samples": 1024, "warmup_batches": 1,
                            "rate_qps": 20, "query_samples": [5, 40]}}


def tiny_cell(workload: str, **config) -> harness.Cell:
    cell = harness.load_cell(ROOT, workload)
    cell.config.update(TINY, **config)
    if cell.config["cache"]:
        cell.config["cache"]["rows"] = 64
    cell.mix.update(TINY_MIX[SPEC_CELLS[workload]["traffic"]])
    cell.per_layer = []
    return cell


SPEC_CELLS = {w["name"]: w for w in SPEC["workloads"]}


def tiny_run(cell, seconds=1.0, seed=2 ** 31 + 17):
    import jax

    n = cell.chips
    return harness.execute(cell, seed, seconds, None, time.perf_counter(),
                           jax.devices()[:n])


# ---------------------------------------------------------------------------
# BENCHMARK.json and the files it names
# ---------------------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def test_spec_names_and_files():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    names = [m["name"] for m in SPEC["end_to_end"] + SPEC["per_layer"]]
    names += [w["name"] for w in SPEC["workloads"]]
    names += [c["name"] for c in SPEC["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert set(json.loads((ROOT / c["file"]).read_text())["reduced"]) \
            == set(c["reduced"])
    for w in SPEC["workloads"]:
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json").is_file()
        assert (ROOT / "bench" / "checks" / f"{w['name']}.json").is_file()
    for m in SPEC["per_layer"]:
        assert callable(harness.reader(m["name"]))
    assert any(m["name"] == "setup_s" for m in SPEC["end_to_end"])


def test_every_cell_reports_metrics():
    for w in SPEC["workloads"]:
        cell = harness.load_cell(ROOT, w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        assert all(m["moves"] in e2e for m in cell.per_layer)


# ---------------------------------------------------------------------------
# Arithmetic
# ---------------------------------------------------------------------------

def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 99) == 99
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([3.0], 99) == 3.0
    # a missing answer ranks above every latency
    assert stats.percentile([1.0] * 98 + [math.inf] * 2, 99) == math.inf
    assert stats.percentile([1.0] * 99 + [math.inf], 99) == 1.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_union_and_gaps():
    iv = [(0, 2), (1, 3), (5, 6), (9, 12)]
    assert trace.union_ns(iv, 0, 10) == 3 + 1 + 1
    assert trace.gaps(iv, 0, 10) == [(3, 5), (6, 9)]
    assert trace.gaps([], 0, 4) == [(0, 4)]


TBE_OP = ("%gather_pool_tbe_flat_pallas.1 = f32[26,1024,128]{2,1,0:T(8,128)} "
          "custom-call(s32[26]{0:T(128)} %iota, s32[26,8,1,4096] %ids)")


def _events():
    E = trace.Event
    host = "/host:CPU"
    ev = [E(host, "python3", "bench.window", 1000, 10000),
          E(host, "python3", "bench.submit", 1000, 3000),
          E(host, "python3", "bench.drain", 4000, 7000)]
    for chip in range(2):
        dev = f"/device:TPU:{chip}"
        ev += [E(dev, "XLA Ops", TBE_OP, 4500, 2000),
               E(dev, "XLA Ops", "fusion.3", 6500, 500),
               E(dev, "XLA Ops", "all-reduce.2", 7000, 1000),
               E(dev, "XLA Modules", "jit_fwd", 4500, 3500),
               E(dev, "XLA Ops", "copy", 0, 500)]          # before the window
    return ev


def test_summarize_device_time():
    s = trace.summarize(_events(), chips=2)
    assert s.window_s == pytest.approx(10000e-9)
    assert s.busy_s == pytest.approx([3500e-9, 3500e-9])
    assert s.matching_s(dlrm.TBE_KERNEL) == pytest.approx([2000e-9] * 2)
    assert s.matching_s(trace.COLLECTIVE.pattern) == pytest.approx([1e-6] * 2)
    # chip 0 idles 3500 ns under submit and 3000 ns under drain
    assert s.idle_by_host == pytest.approx({"bench.submit": 3500e-9,
                                            "bench.drain": 3000e-9})
    b = s.breakdown()
    assert b["device_ops"][0] == [
        "gather_pool_tbe_flat_pallas.1 custom-call f32[26,1024,128]",
        pytest.approx(2000e-9)]
    assert len(b["idle_gaps"]) == 2


def test_summarize_refuses_a_trace_without_device_ops():
    ev = [e for e in _events() if not e.plane.startswith("/device")]
    with pytest.raises(RuntimeError):
        trace.summarize(ev, chips=1)


def _run(**kw):
    win = harness.Window("offline", 2.0, 2048, 2048, 0.04,
                         [range(0, 1024), range(1024, 2048)], {})
    base = dict(cell=harness.load_cell(ROOT, "hbm.uniform.offline"),
                window=win, chips=2, peaks={"flops": 1e12,
                                            "hbm_bytes_per_s": 1e9},
                cache=None, trace=trace.summarize(_events(), chips=2),
                tbe_bytes=2000.0, flops_per_sample=1000)
    base.update(kw)
    return harness.Run(**base)


def test_reader_arithmetic():
    run = _run()
    # 2000 B at 1 GB/s = 2 us over 4 us of kernel time on two chips
    assert readers.tbe_roofline(run) == pytest.approx(50.0)
    assert readers.device_idle(run) == pytest.approx(65.0)
    assert readers.submit_us(run) == pytest.approx(0.04 / 2048 * 1e6)
    assert readers.step_mfu(run) == pytest.approx(
        100 * 1000 * 1024 / 2e12)
    assert readers.rw_collective_ms(run) == pytest.approx(1e-6 / 2 * 1e3)
    assert readers.hit_rate(run) is None
    run = _run(cache=(86, 100, 0.5, 10), chips=1, trace=None)
    assert readers.hit_rate(run) == pytest.approx(86.0)
    assert readers.prefetch_ms(run) == pytest.approx(50.0)
    assert readers.tbe_roofline(run) is None
    assert readers.rw_collective_ms(run) is None


def test_query_tail_reader():
    lat = np.arange(1, 101) / 1e3          # 1..100 ms
    win = harness.Window("server", 1.0, 100, 100, 0.0, [], {},
                         latencies_s=lat)
    assert readers.query_p99_ms(_run(window=win)) == pytest.approx(99.0)
    # an unanswered query ranks above every answered one
    win.latencies_s = np.concatenate([lat[:98], [np.inf, np.inf]])
    assert readers.query_p99_ms(_run(window=win)) == math.inf
    # offline windows have no queries
    assert readers.query_p99_ms(_run()) is None
    assert harness.reader("query_p99_ms.server") is readers.query_p99_ms


def test_operation_counts_from_shapes():
    cfg = json.loads((ROOT / "bench/configs/dlrm-criteo-hbm.json").read_text())
    # MLPs 4,730,368 + 351 pairs x 128 x 2 + 26 x 32 x 128 x 2
    assert ref.flops_per_sample(cfg) == 4_730_368 + 89_856 + 212_992
    # 32,231 distinct rows per table (uniform over 1M), 852k slots
    b = ref.tbe_least_bytes(26 * 32_231, 26 * 1024 * 32, 1024, 26, 128)
    assert b == 26 * 32_231 * 512 + 26 * 1024 * 32 * 8 + 26 * 1024 * 512
    assert b / 819e9 == pytest.approx(0.55e-3, rel=0.05)


# ---------------------------------------------------------------------------
# Generators
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mix", ["uniform.offline", "zipf.offline"])
def test_samples_follow_the_seed(mix):
    cfg = dict(json.loads(
        (ROOT / "bench/configs/dlrm-criteo-hbm.json").read_text()), **TINY)
    m = json.loads((ROOT / "bench/traffic" / f"{mix}.json").read_text())
    big = 2 ** 31 + 5
    a, b, c = (traffic.samples(s, cfg, m, 3000) for s in (big, big, big + 1))
    assert np.array_equal(a.ids, b.ids) and np.array_equal(a.dense, b.dense)
    assert not np.array_equal(a.ids, c.ids)
    assert a.ids.shape == (3000, 4, 4) and a.ids.dtype == np.int32
    assert a.ids.min() >= 0 and a.ids.max() < cfg["rows_per_table"]
    assert np.all(a.lengths == cfg["pooling"])


def test_zipf_ranks_match_the_truncated_zeta():
    rows, a = 1000, 1.05
    threshold, alias = traffic.zipf_alias(a, rows)
    pmf = np.arange(1, rows + 1) ** -a
    pmf /= pmf.sum()
    # the table's exact distribution: column mass 1/rows split by threshold
    got = threshold / rows
    np.add.at(got, alias, (1 - threshold) / rows)
    assert np.allclose(got, pmf, rtol=1e-5, atol=0)
    cfg = dict(TINY, rows_per_table=rows, num_sparse_features=1, pooling=8)
    ids = traffic.samples(3, cfg, {"ids": {"dist": "zipf", "a": a}},
                          20000).ids.ravel()
    assert np.mean(ids < 10) == pytest.approx(pmf[:10].sum(), abs=0.01)
    counts = traffic.log_counts({"ids": {"dist": "zipf", "a": a},
                                 "cache_log_lookups": 10 ** 9}, rows)
    assert counts.sum() == pytest.approx(10 ** 9, rel=1e-6)
    assert np.all(np.diff(counts) <= 0)


def test_query_schedule_is_the_mixs():
    mix = {"rate_qps": 50, "query_samples": [100, 700], "schedule_seed": 3}
    d1, s1 = traffic.query_schedule(mix, 20.0)
    d2, s2 = traffic.query_schedule(dict(mix), 20.0)
    d3, s3 = traffic.query_schedule(dict(mix, schedule_seed=4), 20.0)
    assert np.array_equal(d1, d2) and np.array_equal(s1, s2)
    assert len(d1) == 1000 and not np.array_equal(s1, s3)
    assert np.array_equal(np.sort(s1), np.sort(s3))
    assert np.allclose(np.sort(np.diff(d1)), np.sort(np.diff(d3)), rtol=0.1)
    assert d1[0] == 0 and d1[-1] < 20.0 and np.all(np.diff(d1) > 0)
    assert s1.min() >= 100 and s1.max() <= 700
    assert s1.mean() == pytest.approx(400, abs=1)


def test_rows_are_the_same_wherever_drawn():
    import jax.numpy as jnp

    w = ref.seed_words(2 ** 33 + 9)
    full = np.asarray(ref.rows_from_seed(w, jnp.arange(64, dtype=jnp.uint32),
                                         16))
    some = np.asarray(ref.rows_from_seed(w, jnp.asarray([5, 63], jnp.uint32),
                                         16))
    assert np.array_equal(full[[5, 63]], some)
    assert abs(full.var() - 1 / 16) < 0.02
    other = np.asarray(ref.rows_from_seed(ref.seed_words(9),
                                          jnp.arange(64, dtype=jnp.uint32), 16))
    assert not np.array_equal(full, other)


# ---------------------------------------------------------------------------
# The entry point refuses what it cannot measure
# ---------------------------------------------------------------------------

def _run_py(cwd: Path, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hbm.uniform.offline",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_cpu():
    p = _run_py(ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_run_refuses_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_py(tmp_path)
    assert p.returncode != 0 and p.stdout == ""


# ---------------------------------------------------------------------------
# Whole runs at a tiny size
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("workload", ["hbm.uniform.offline",
                                      "tiered.zipf.offline",
                                      "hbm.zipf.server"])
def test_tiny_run_is_correct(workload):
    result, compared, info = tiny_run(tiny_cell(workload))
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] > 0
    want = {m["name"] for m in harness.load_cell(ROOT, workload).end_to_end}
    assert set(result["metrics"]) == want
    assert all(m["value"] > 0 for m in result["metrics"].values())
    assert list(result)[-1] == "compared"


class _HalfLeftOut:
    """Wraps a ``System`` whose timed path answers half of what it drains."""

    def __init__(self, system):
        self._system = system

    def __getattr__(self, name):
        return getattr(self._system, name)

    def drain(self):
        out = self._system.drain()
        keep = sorted(out)[: len(out) // 2]
        return {r: out[r] for r in keep}


def _altered_scorer(real):
    """The engine's scoring program with the first pCTR of every
    micro-batch moved by 1e-3 on the device, where it is produced."""
    import jax

    def make(cfg, ctx=None):
        fwd = real(cfg, ctx)
        return jax.jit(lambda p, dense, batch:
                       fwd(p, dense, batch).at[0].add(1e-3))

    return make


@pytest.mark.parametrize("workload,fault", [
    ("hbm.uniform.offline", "answer altered"),
    ("hbm.uniform.offline", "half left out"),
    ("tiered.zipf.offline", "answer altered"),
    ("tiered.zipf.offline", "half left out"),
    ("hbm.zipf.server", "answer altered")])
def test_broken_answers_are_not_correct(monkeypatch, workload, fault):
    if fault == "answer altered":
        from repro.serving import engine

        monkeypatch.setattr(engine, "make_scorer",
                            _altered_scorer(engine.make_scorer))
    else:
        real = dlrm.System
        monkeypatch.setattr(dlrm, "System", lambda *a: _HalfLeftOut(real(*a)))
    result, compared, _ = tiny_run(tiny_cell(workload))
    assert not result["correct"]
    if fault == "answer altered":
        assert compared["max_abs_dpctr"]["value"] > 5e-4
    else:
        assert result["failed"] == result["attempted"] // 2


_RW = """
import sys, json, time
sys.path[:0] = [{root!r}, {root!r} + "/src"]
sys.path.insert(0, {tests!r})
import jax
from test_bench import tiny_cell, tiny_run
import repro.core.comm as comm
if {broken!r}:
    comm.all_reduce = lambda x, *a, **k: x      # the exchange left out
cell = tiny_cell("rw4.uniform.offline")
result, compared, _ = tiny_run(cell)
print(json.dumps({{"correct": result["correct"],
                  "count": result["device"]["count"], **compared}}))
"""


@pytest.mark.parametrize("broken", [False, True])
def test_rw_exchange_is_checked(broken):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = _RW.format(root=str(ROOT), tests=str(Path(__file__).parent),
                      broken=broken)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got["count"] == 4
    assert got["correct"] is (not broken), got


@pytest.mark.parametrize("workload", sorted(SPEC_CELLS))
def test_control_fails_the_limit(workload):
    """The reference at three bf16 passes in the program's place reads
    above the cell's limit, at the cell's widths (fewer rows and fewer
    samples than a run compares)."""
    from bench import control

    cell = harness.load_cell(ROOT, workload)
    cell.config["rows_per_table"] = 4096
    limit = cell.check["max_abs_dpctr"]
    old = harness.CHECK_SAMPLES
    harness.CHECK_SAMPLES = 1024
    try:
        gaps = [control.control_gap(cell, s) for s in (1, 2)]
    finally:
        harness.CHECK_SAMPLES = old
    assert min(gaps) > limit, gaps
