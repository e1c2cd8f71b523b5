"""Pipelined serving subsystem (repro/pipeline/ + engine integration):
single-device unit tests here; the multi-rank remote-cold-tier checks
run tests/_pipeline_checks.py in a subprocess with a FORCED 4-device
CPU backend (XLA_FLAGS must be set before jax import)."""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import CacheConfig, CacheStats, CachedEmbeddingBag
from repro.cache.manager import CacheCapacityError
from repro.configs import dlrm as dlrm_cfg
from repro.core.embedding_bag import (
    EmbeddingBagConfig,
    init_tables,
    pooled_lookup_local,
)
from repro.core.jagged import JaggedBatch
from repro.core.perf_model import (
    H100_DGX,
    TPU_V5E,
    EmbeddingWorkload,
    overlapped_embedding_bag_time,
    overlapped_phase_times,
    pipelined_speedup_vs_distributed,
    tiered_embedding_bag_time,
    tiered_phase_times,
    tiered_speedup_vs_distributed,
)
from repro.models import dlrm as dlrm_mod
from repro.pipeline import STAGES, DoubleBufferedSlotPool, PipelineTrace
from repro.serving.engine import (
    CTRRequest,
    DLRMEngine,
    PipelinedDLRMEngine,
    make_dlrm_engine,
)


# ---------------------------------------------------------------------------
# Multi-rank integration (subprocess, forced 4-device CPU)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(900)
def test_pipeline_multirank_suite():
    script = os.path.join(os.path.dirname(__file__), "_pipeline_checks.py")
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run(
        [sys.executable, script], env=env, capture_output=True, text=True,
        timeout=880)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "pipeline multi-rank checks failed"


# ---------------------------------------------------------------------------
# DoubleBufferedSlotPool: epoch swap protocol
# ---------------------------------------------------------------------------

def _bag_cfg(T=2, R=64, D=8, cache_rows=16, **kw):
    return EmbeddingBagConfig(num_tables=T, rows_per_table=R, dim=D,
                              kernel_mode="reference",
                              cache=CacheConfig(rows=cache_rows), **kw)


def _with_depth(cfg, depth):
    return dataclasses.replace(
        cfg, cache=dataclasses.replace(cfg.cache, pipeline_depth=depth))


def test_double_buffer_epoch_swap_protocol():
    cfg = _bag_cfg()
    tables = init_tables(jax.random.key(0), cfg)
    pool = DoubleBufferedSlotPool(tables, cfg, depth=2)
    with pytest.raises(ValueError, match="depth"):
        DoubleBufferedSlotPool(tables, cfg, depth=1)
    live0, shadow0 = pool.live, pool.shadow
    assert live0 is not shadow0
    assert shadow0.cold is live0.cold          # one shared cold tier
    assert shadow0.stats is pool.stats is live0.stats

    idx = np.arange(8, dtype=np.int32).reshape(1, 2, 4).repeat(2, axis=0)
    plan = pool.prepare_next(idx, None)
    assert plan.epoch == shadow0.mgr.epoch + 1 == 1
    rows = pool.fetch_next(plan)
    assert rows.shape == (plan.fetch_rows.size, 8)
    pool.commit_next(plan, rows)
    # payload landed in the SHADOW pool, live pool untouched (zeros)
    assert np.asarray(shadow0.pool).any()
    assert not np.asarray(live0.pool).any()
    pool.swap()
    assert pool.live is shadow0 and pool.shadow is live0
    assert shadow0.mgr.epoch == 1              # the swap published epoch 1
    # committing the SAME plan again is stale (its swap already happened)
    # AND the refusal rolls the plan's residency back in its OWNING
    # buffer — slots must never claim rows without a guaranteed payload
    with pytest.raises(RuntimeError, match="stale"):
        pool.commit_next(plan, rows)
    assert (shadow0.mgr.slot_of_id[0, :8] < 0).all()
    # the serialized facade serves from the (new) live buffer
    remapped = pool.prefetch_arrays(idx, None)
    assert remapped.shape == idx.shape
    np.testing.assert_array_equal(
        np.asarray(pool.pool), np.asarray(shadow0.pool))


def test_double_buffer_stale_slot_invalidation_on_fetch_failure():
    """A failed background fetch must roll back the shadow buffer's
    committed residency — no slot may claim a row that never arrived —
    and the next prefetch of those rows must re-fetch them correctly."""
    cfg = _bag_cfg()
    tables = init_tables(jax.random.key(1), cfg)
    pool = DoubleBufferedSlotPool(tables, cfg, depth=2)
    shadow = pool.shadow
    idx = np.arange(6, dtype=np.int32).reshape(1, 2, 3).repeat(2, axis=0)
    plan = pool.prepare_next(idx, None)
    assert (shadow.mgr.slot_of_id[0, :6] >= 0).all()   # residency committed

    real_fetch = shadow.cold.fetch
    shadow.cold.fetch = lambda *a, **kw: (_ for _ in ()).throw(
        RuntimeError("injected cold-tier failure"))
    try:
        with pytest.raises(RuntimeError, match="injected"):
            pool.fetch_next(plan)
    finally:
        shadow.cold.fetch = real_fetch
    # stale slots invalidated: nothing claims the uncopied rows
    assert (shadow.mgr.slot_of_id[0, :6] < 0).all()
    assert (shadow.mgr.id_of_slot < 0).all()
    # the retry path is clean: plan again, fetch for real, commit, swap
    plan2 = pool.prepare_next(idx, None)
    pool.commit_next(plan2, pool.fetch_next(plan2))
    pool.swap()
    got = pool.live.device_lookup(pool.pool,
                                  np.asarray(plan2.remapped), None, None)
    want = np.asarray(tables)[:, :6].reshape(2, 2, 3, 8).sum(axis=2)
    np.testing.assert_array_equal(np.asarray(got).transpose(1, 0, 2), want)


def test_double_buffer_capacity_error_is_atomic():
    cfg = _bag_cfg(cache_rows=4)
    tables = init_tables(jax.random.key(2), cfg)
    pool = DoubleBufferedSlotPool(tables, cfg, depth=2)
    idx = np.arange(8, dtype=np.int32).reshape(1, 1, 8).repeat(2, axis=0)
    with pytest.raises(CacheCapacityError):
        pool.prepare_next(idx, None)
    assert (pool.shadow.mgr.id_of_slot < 0).all()  # nothing half-admitted


def test_double_buffer_staged_fetch_bitwise_equals_serialized_bag():
    """Depth 2 with each buffer's fetch gathered into its pool staging:
    every micro-batch's output is bitwise the serialized bag's and the
    uncached oracle's, across evictions and staging size changes."""
    cfg = _bag_cfg(T=3, R=64, D=8, cache_rows=16)
    tables = init_tables(jax.random.key(9), cfg)
    ring = DoubleBufferedSlotPool(tables, cfg, depth=2)
    serial = CachedEmbeddingBag(tables, cfg)
    rng = np.random.default_rng(10)
    for _ in range(8):
        idx = rng.integers(0, 64, (3, 4, 3)).astype(np.int32)
        lens = rng.integers(1, 4, (3, 4)).astype(np.int32)
        plan = ring.prepare_next(idx, lens)
        rows = ring.fetch_next(plan)
        if rows is not None:            # gathered into the owner's staging
            assert rows.base is ring._owner_of(plan).hot._staging
        ring.commit_next(plan, rows)
        ring.swap()
        got = ring.live.device_lookup(ring.pool, jnp.asarray(plan.remapped),
                                      jnp.asarray(lens), None)
        batch = JaggedBatch(jnp.asarray(idx), jnp.asarray(lens))
        want = serial.lookup(batch)
        oracle = pooled_lookup_local(tables, batch, cfg)
        for ref in (want, oracle):
            np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                          np.asarray(ref).view(np.uint32))
    assert ring.stats.evictions > 0 and ring.stats.staging_allocs > 1
    assert serial.stats.staging_allocs > 1


# ---------------------------------------------------------------------------
# PipelinedDLRMEngine: bitwise equality, stage timers, fallback
# ---------------------------------------------------------------------------

def _zipf_requests(cfg, n, rng, churn=0):
    T, L, F = (cfg.num_sparse_features, cfg.pooling,
               cfg.num_dense_features)
    R = cfg.rows_per_table
    reqs = []
    for rid in range(n):
        idx = np.minimum(rng.zipf(1.2, size=(T, L)) - 1, R - 1)
        if churn:
            shifted = (idx + (rid // 2) * churn) % R
            idx = np.where(rng.random((T, L)) < 0.4, shifted, idx)
        reqs.append(CTRRequest(
            rid=rid, dense=rng.standard_normal(F).astype(np.float32),
            indices=idx.astype(np.int32),
            lengths=rng.integers(1, L + 1, T).astype(np.int32)))
    return reqs


def test_pipelined_engine_bitwise_equals_serialized():
    """Depth-2 over the host cold tier, LRU churn across >= 3 flushes:
    scores bitwise-equal to the depth-1 engine; both engines record the
    same stage spans, only the pipeline measures overlap."""
    base = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference",
                               cache=CacheConfig(rows=12, policy="lru"))
    params = dlrm_mod.init_params(jax.random.key(3), base)
    serial = make_dlrm_engine(params, base, batch_size=4)
    piped = make_dlrm_engine(params, _with_depth(base, 2), batch_size=4)
    rng = np.random.default_rng(4)
    reqs = _zipf_requests(base, 24, rng, churn=32)     # 6 flushes
    for r in reqs:
        serial.submit(r)
        piped.submit(r)
    want = serial.run_to_completion()
    got = piped.run_to_completion()
    assert sorted(got) == sorted(want) == list(range(24))
    assert all(got[rid] == want[rid] for rid in want)
    s, ss = piped.cache_stats(), serial.cache_stats()
    assert s.evictions > 0                             # churn happened
    # satellite: the serialized engine reports the SAME spans
    for st in (s, ss):
        assert st.prefetch_s > 0 and st.forward_s > 0
        assert st.scatter_s >= 0
    assert ss.overlap_s == 0.0 and ss.overlap_fraction == 0.0
    assert s.overlap_s >= 0.0
    for stage in STAGES:
        assert piped.trace.by_stage(stage)
    assert piped.trace.total("forward") == pytest.approx(s.forward_s)


def test_pipeline_overflow_falls_back_to_serialized_flush():
    """Head-of-line regression (satellite 2): a micro-batch overflowing
    the shadow buffer must take the serialized CacheCapacityError split
    path — every request scored, none stranded, no deadlock."""
    base = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference")
    L = base.pooling
    params = dlrm_mod.init_params(jax.random.key(5), base)
    cfg = dataclasses.replace(
        base, cache=CacheConfig(rows=L, pipeline_depth=2))
    piped = make_dlrm_engine(params, cfg, batch_size=2)
    serial = make_dlrm_engine(params, _with_depth(cfg, 1), batch_size=2)
    T, F = base.num_sparse_features, base.num_dense_features
    rng = np.random.default_rng(6)
    # disjoint full-length working sets: every 2-request union overflows
    reqs = [CTRRequest(
        rid=rid, dense=rng.standard_normal(F).astype(np.float32),
        indices=(np.arange(T * L, dtype=np.int32).reshape(T, L)
                 + rid * L) % base.rows_per_table,
        lengths=np.full(T, L, np.int32)) for rid in range(5)]
    for r in reqs:
        piped.submit(r)
        serial.submit(r)
    got = piped.run_to_completion()
    want = serial.run_to_completion()
    assert sorted(got) == sorted(want) == [0, 1, 2, 3, 4]
    assert all(got[rid] == want[rid] for rid in want)
    assert not piped.queue


def test_pipeline_error_requeues_requests():
    """A mid-run cold-tier failure must not lose requests: the raising
    run_to_completion delivered no scores, so every submitted request
    goes back on the queue and a retry scores them all."""
    base = dataclasses.replace(
        dlrm_cfg.smoke(), kernel_mode="reference",
        cache=CacheConfig(rows=16, pipeline_depth=2))
    params = dlrm_mod.init_params(jax.random.key(7), base)
    piped = make_dlrm_engine(params, base, batch_size=4)
    serial = make_dlrm_engine(params, _with_depth(base, 1), batch_size=4)
    rng = np.random.default_rng(8)
    reqs = _zipf_requests(base, 12, rng)
    for r in reqs:
        piped.submit(r)
        serial.submit(r)
    cold = piped.cache.buffers[0].cold            # shared by both buffers
    real_fetch, calls = cold.fetch, {"n": 0}

    def flaky(t, r, out=None):
        calls["n"] += 1
        if calls["n"] == 2:
            raise RuntimeError("transient cold-tier failure")
        return real_fetch(t, r, out=out)

    cold.fetch = flaky
    try:
        with pytest.raises(RuntimeError, match="transient"):
            piped.run_to_completion()
    finally:
        cold.fetch = real_fetch
    assert len(piped.queue) == 12                 # nothing lost
    got = piped.run_to_completion()               # clean retry
    want = serial.run_to_completion()
    assert sorted(got) == sorted(want) == list(range(12))
    assert all(got[rid] == want[rid] for rid in want)


def test_engine_selection_and_guards():
    base = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference",
                               cache=CacheConfig(rows=16))
    params = dlrm_mod.init_params(jax.random.key(0), base)
    assert type(make_dlrm_engine(params, base, batch_size=2)) is DLRMEngine
    piped = make_dlrm_engine(params, _with_depth(base, 2), batch_size=2)
    assert isinstance(piped, PipelinedDLRMEngine)
    assert isinstance(piped.cache, DoubleBufferedSlotPool)
    assert piped.cache.depth == 2
    # a pipeline without a cache has no prefetch stage to overlap
    with pytest.raises(ValueError, match="cache_rows"):
        PipelinedDLRMEngine(
            params,
            dataclasses.replace(base,
                                cache=CacheConfig(rows=0, pipeline_depth=2)),
            batch_size=2)
    with pytest.raises(ValueError, match="pipeline_depth"):
        PipelinedDLRMEngine(params, base, batch_size=2)
    with pytest.raises(ValueError, match="pipeline_depth"):
        CacheConfig(pipeline_depth=0)


# ---------------------------------------------------------------------------
# Observability: CacheStats stage timers + PipelineTrace
# ---------------------------------------------------------------------------

def test_cache_stats_stage_timers():
    s = CacheStats()
    s.add_time("prefetch", 0.2)
    s.add_time("forward", 0.5)
    s.add_time("scatter", 0.1)
    s.add_time("overlap", 0.15)
    assert s.prefetch_s == pytest.approx(0.2)
    assert s.overlap_fraction == pytest.approx(0.75)
    d = s.as_dict()
    for k in ("prefetch_s", "scatter_s", "forward_s", "overlap_s",
              "overlap_fraction"):
        assert k in d
    with pytest.raises(ValueError, match="stage"):
        s.add_time("gather", 1.0)
    s.reset()
    assert s.prefetch_s == s.overlap_s == 0.0
    assert s.overlap_fraction == 0.0


def test_pipeline_trace_overlap_measures_intersections():
    tr = PipelineTrace()
    tr.record("forward", 0, 0.0, 1.0)
    tr.record("fetch", 1, 0.5, 1.5)      # 0.5 s inside the forward
    tr.record("admit", 1, 0.9, 1.1)      # 0.1 s inside
    tr.record("scatter", 1, 0.0, 2.0)    # scatter never counts as overlap
    assert tr.overlap_s() == pytest.approx(0.6)
    assert tr.overlap_fraction() == pytest.approx(0.6 / 1.2)
    with pytest.raises(ValueError, match="stage"):
        tr.record("nope", 0, 0.0, 1.0)
    tr.clear()
    assert tr.overlap_s() == 0.0 and tr.overlap_fraction() == 0.0


# ---------------------------------------------------------------------------
# Perf model: overlapped_phase_times reductions
# ---------------------------------------------------------------------------

def test_overlapped_phase_times_reductions():
    w = EmbeddingWorkload(num_tables=26, batch_per_device=1024, pooling=32,
                          dim=128)
    for hw in (H100_DGX, TPU_V5E):
        for hosts in (1, 8, 128):
            tiered = tiered_phase_times(w, hw, hit_rate=0.9, hosts=hosts)
            d1 = overlapped_phase_times(w, hw, hit_rate=0.9, hosts=hosts,
                                        depth=1)
            # depth 1 degenerates to the serialized tiered model exactly
            assert d1.pop("overlap") == 0.0
            assert d1 == tiered
            d2 = overlapped_phase_times(w, hw, hit_rate=0.9, hosts=hosts,
                                        depth=2)
            fetch = d2["prefetch_h2d"] + d2["fetch_remote"]
            # steady state: sum(...) == max(fetch, forward), never worse
            assert sum(d2.values()) == pytest.approx(
                max(fetch, d2["gather"]))
            assert sum(d2.values()) <= sum(tiered.values())
    # a perfect hit rate has nothing to hide: depth-2 == depth-1
    assert overlapped_embedding_bag_time(
        w, H100_DGX, hit_rate=1.0, hosts=8, depth=2) == \
        tiered_embedding_bag_time(w, H100_DGX, hit_rate=1.0, hosts=8)


def test_pipelined_recovery_beats_serialized_tiered():
    w = EmbeddingWorkload(num_tables=26, batch_per_device=1024, pooling=32,
                          dim=128)
    table_bytes = 10e12
    tiered = tiered_speedup_vs_distributed(
        table_bytes, w, H100_DGX, hit_rate=0.9, hosts=128)
    piped = pipelined_speedup_vs_distributed(
        table_bytes, w, H100_DGX, hit_rate=0.9, hosts=128)
    assert piped >= tiered > 1.0
    # with misses to hide, the pipeline strictly improves the recovery
    assert pipelined_speedup_vs_distributed(
        table_bytes, w, H100_DGX, hit_rate=0.5, hosts=128) > \
        tiered_speedup_vs_distributed(
            table_bytes, w, H100_DGX, hit_rate=0.5, hosts=128)


# ---------------------------------------------------------------------------
# Example smoke (the DLRMConfig-driven pipelined serving cell)
# ---------------------------------------------------------------------------

def test_serve_batched_pipelined_cell_runs():
    """examples/serve_batched.py's DLRM cell routes purely through
    DLRMConfig fields and asserts pipelined == serialized scores."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "examples", "serve_batched.py")
    spec = importlib.util.spec_from_file_location("serve_batched_ex", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.serve_dlrm_pipelined()
