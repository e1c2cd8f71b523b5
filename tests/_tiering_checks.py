"""Multi-rank tiered-store checks, run as a SUBPROCESS on a FORCED
4-device CPU backend by tests/test_tiering.py (XLA_FLAGS must be set
before jax import; the rest of the suite keeps the real single device).

Covers: the ``comm.fetch_rows`` primitive (bulk psum_scatter vs the
one-sided Pallas RDMA kernel in interpret mode vs a numpy oracle), the
remote cold tier end-to-end (CachedEmbeddingBag bitwise vs the uncached
oracle under both transports, single fused TBE launch), tier
promotion/demotion churn under zipf traffic, per-tier stats accounting,
fetch_rows instrumentation, and the DLRMEngine serving against a
cluster-wide cold tier.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.analysis import audit
from repro.cache import CacheConfig, RemoteStore
from repro.cache import cached_bag
from repro.core import comm
from repro.core.embedding_bag import (
    EmbeddingBagConfig, init_tables, make_cache, pooled_lookup_local,
)
from repro.core.jagged import JaggedBatch, random_jagged_batch
from jax import shard_map

failures = []


def check(name, fn):
    try:
        fn()
        print(f"PASS {name}")
    except Exception as e:  # noqa: BLE001
        failures.append(name)
        import traceback
        traceback.print_exc()
        print(f"FAIL {name}: {e}")


E = 4  # forced device count


def _fetch_via(backend, shards, addr, owner, axis="hosts"):
    """Run comm.fetch_rows over the 4-device mesh; requests replicated
    (a one-sided fetch runs its kernel in the Pallas interpreter)."""
    mesh = Mesh(np.asarray(jax.devices()), (axis,))

    def inner(shard, a, o):
        return comm.fetch_rows(shard[0], a, o, axis, backend=backend,
                               interpret=backend == "onesided")

    return np.asarray(jax.jit(shard_map(
        inner, mesh=mesh, in_specs=(P(axis), P(), P()), out_specs=P(),
        check_vma=False))(shards, addr, owner))


def fetch_rows_onesided_vs_lax():
    """The Pallas per-row RDMA kernel (interpret) == bulk psum_scatter ==
    a plain numpy gather, for rows scattered across every owner."""
    rng = np.random.default_rng(0)
    rows_local, D, M = 8, 16, 10
    shards = rng.standard_normal((E, rows_local, D)).astype(np.float32)
    owner = rng.integers(0, E, M).astype(np.int32)
    local = rng.integers(0, rows_local, M).astype(np.int32)
    want = shards[owner, local]                      # numpy oracle
    got_bulk = _fetch_via("bulk", shards, local, owner)
    np.testing.assert_array_equal(got_bulk, want)
    got_os = _fetch_via("onesided", shards, local, owner)
    np.testing.assert_array_equal(got_os, want)


def fetch_rows_instrumented():
    """fetch_rows traces ONE CollectiveEvent with the stacked payload
    bytes — benchmarks account the traffic without HLO parsing."""
    rows_local, D, M = 8, 4, 6
    shards = np.zeros((E, rows_local, D), np.float32)
    owner = np.zeros(M, np.int32)
    local = np.zeros(M, np.int32)
    mesh = Mesh(np.asarray(jax.devices()), ("hosts",))
    with comm.instrument() as events:
        jax.jit(shard_map(
            lambda s, a, o: comm.fetch_rows(s[0], a, o, "hosts"),
            mesh=mesh, in_specs=(P("hosts"), P(), P()), out_specs=P(),
            check_vma=False)).lower(shards, local, owner)
    ev = [e for e in events if e.op == "fetch_rows"]
    assert len(ev) == 1, events
    assert ev[0].bytes_in == E * M * D * 4   # the stacked (E, M, D) payload
    assert ev[0].axis_size == E


def fetch_rows_runtime_timestamps():
    """RemoteStore.fetch emits a runtime-timestamped fetch_rows event via
    the process-wide obs sink (jit-trace events carry t0 == t1 == 0.0 and
    never land on a trace timeline; only the runtime path does)."""
    from repro.cache import CacheConfig
    from repro.core.embedding_bag import EmbeddingBagConfig, init_tables, \
        make_cache
    from repro.obs import Tracer

    cfg = EmbeddingBagConfig(
        num_tables=1, rows_per_table=64, dim=8, kernel_mode="reference",
        cache=CacheConfig(rows=32, cold_tier="remote"))
    tables = init_tables(jax.random.key(5), cfg)
    cache = make_cache(tables, cfg)
    tracer = Tracer()
    tracer.install_comm_sink()
    try:
        b = JaggedBatch(jnp.asarray(np.arange(16).reshape(1, 4, 4),
                                    dtype=jnp.int32),
                        jnp.full((1, 4), 4, jnp.int32))
        cache.lookup(b)
    finally:
        tracer.remove_comm_sink()
    spans = tracer.spans(lane="comm", name="fetch_rows")
    assert spans, "no fetch_rows event reached the sink"
    # jit-trace-time events stamp t0 == t1; the runtime path must
    # contribute at least one span with real duration
    timed = [s for s in spans if s.t1 > s.t0]
    assert timed, "no runtime-timestamped fetch_rows span"
    assert all(s.args["axis_size"] == E and s.args["bytes"] > 0
               for s in timed)


def _exactness(backend, *, batches, cache_rows, cfg_kw, batch_kw):
    cfg = EmbeddingBagConfig(
        cache=CacheConfig(rows=cache_rows, cold_tier="remote",
                          remote_backend=backend), **cfg_kw)
    tables = init_tables(jax.random.key(0), cfg)
    cache = make_cache(tables, cfg)
    assert isinstance(cache.cold, RemoteStore)
    rng = np.random.default_rng(1)
    for _ in range(batches):
        b = random_jagged_batch(rng, cfg.num_tables, **batch_kw,
                                num_rows=cfg.rows_per_table,
                                fixed_pooling=False, zipf_a=1.2)
        got = cache.lookup(b)
        want = pooled_lookup_local(tables, b, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    return cache


def remote_lookup_bitwise_bulk():
    """Remote-tier lookup == uncached oracle, BITWISE, and the hot path
    stays one fused TBE pallas_call (jaxpr-asserted)."""
    cache = _exactness(
        "bulk", batches=4, cache_rows=128,
        cfg_kw=dict(num_tables=2, rows_per_table=512, dim=16,
                    kernel_mode="interpret"),
        batch_kw=dict(batch_size=8, pooling=5))
    s = cache.stats
    assert s.hits > 0                      # zipf traffic repeats hot rows
    assert s.misses_remote > 0 and s.bytes_remote > 0
    assert s.misses_host > 0 and s.bytes_h2d > 0
    assert s.misses_host + s.misses_remote == s.misses
    assert s.bytes_h2d == s.fetch_host * cache.row_bytes
    assert s.bytes_remote == s.fetch_remote * cache.row_bytes
    # structural single-launch guarantee under the remote tier layout
    pool = jax.ShapeDtypeStruct(cache.pool.shape, cache.pool.dtype)
    idx = jax.ShapeDtypeStruct((2, 8, 5), jnp.int32)
    w = jax.ShapeDtypeStruct((2, 8, 5), jnp.float32)
    audit(lambda p, i, ww: cache.device_lookup(p, i, None, ww),
          (pool, idx, w),
          cached_bag.KERNEL_CONTRACTS["device_lookup"]).raise_if_failed()


def remote_lookup_bitwise_onesided():
    """Same bitwise contract with the one-sided RDMA fetch transport
    (small shapes: every (dst, row) pair is one interpreted DMA)."""
    cache = _exactness(
        "onesided", batches=2, cache_rows=32,
        cfg_kw=dict(num_tables=2, rows_per_table=64, dim=8,
                    kernel_mode="interpret"),
        batch_kw=dict(batch_size=4, pooling=3))
    assert cache.stats.misses_remote > 0
    # the kernel mode reaches the store from the config, never a global
    assert cache.cold.backend == "onesided"


def tier_churn_promotion_demotion():
    """A pool smaller than the cross-batch footprint must churn — rows
    demoted (evicted) back to the remote tier and re-promoted on re-use —
    without ever changing the pooled output."""
    cfg = EmbeddingBagConfig(num_tables=2, rows_per_table=256, dim=8,
                             kernel_mode="reference",
                             cache=CacheConfig(rows=16, policy="lru",
                                               cold_tier="remote"))
    tables = init_tables(jax.random.key(2), cfg)
    cache = make_cache(tables, cfg)
    rng = np.random.default_rng(3)

    def feed(idx):
        idx = np.asarray(idx, np.int32)
        b = JaggedBatch(jnp.asarray(idx),
                        jnp.full(idx.shape[:2], idx.shape[2], jnp.int32))
        got = cache.lookup(b)
        want = pooled_lookup_local(tables, b, cfg)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    feed(np.full((2, 1, 4), 7))             # promote probe row 7 (host 0)
    assert cache.mgr.slot_of_id[0, 7] >= 0
    for i in range(6):
        # shifting window drags the working set across all 4 hosts' rows;
        # the 16-slot pool must evict — LRU demotes the untouched probe
        lo = 32 + i * 32
        feed(rng.integers(lo, lo + 32, (2, 4, 4)))
    assert cache.mgr.slot_of_id[0, 7] < 0   # probe demoted to the cold tier
    feed(np.full((2, 1, 4), 7))             # touch again -> re-promoted
    s = cache.stats
    assert s.evictions > 0                  # demotion happened
    # the re-promoted payload in the pool is still the source row, bitwise
    t0_slot = cache.mgr.slot_of_id[0, 7]
    assert t0_slot >= 0
    np.testing.assert_array_equal(cache.hot.fetch([0], [t0_slot])[0],
                                  np.asarray(tables)[0, 7])
    assert s.fetch_host > 0 and s.fetch_remote > 0
    # indirection invariant survives churn
    for t in range(2):
        res = cache.mgr.resident_ids(t)
        slots = cache.mgr.slot_of_id[t][res]
        assert np.array_equal(np.sort(cache.mgr.id_of_slot_t(t)[slots]),
                              res)


def engine_remote_cold_tier():
    """DLRMEngine scoring over a cluster-wide cold tier == the uncached
    direct forward."""
    from repro.configs import dlrm as dlrm_cfg
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import CTRRequest, DLRMEngine

    base = dlrm_cfg.smoke()
    cfg = dataclasses.replace(
        base, cache=CacheConfig(rows=64, cold_tier="remote"))
    params = dlrm_mod.init_params(jax.random.key(0), base)
    rng = np.random.default_rng(4)
    T, L, F = cfg.num_sparse_features, cfg.pooling, cfg.num_dense_features
    reqs = [CTRRequest(
        rid=i, dense=rng.standard_normal(F).astype(np.float32),
        indices=rng.integers(0, base.rows_per_table, (T, L)).astype(np.int32),
        lengths=rng.integers(0, L + 1, T).astype(np.int32))
        for i in range(6)]
    eng = DLRMEngine(params, cfg, batch_size=4)
    assert eng.params["tables"] is None    # HBM holds only the slot pool
    for r in reqs:
        eng.submit(r)
    out = eng.run_to_completion()
    # direct uncached forward, request by request
    for r in reqs:
        dense = jnp.asarray(r.dense[None])
        b = JaggedBatch(jnp.asarray(r.indices[:, None, :]),
                        jnp.asarray(r.lengths[:, None]))
        want = float(jax.nn.sigmoid(
            dlrm_mod.forward(params, dense, b, base))[0])
        assert abs(out[r.rid] - want) < 1e-6, (r.rid, out[r.rid], want)
    s = eng.cache_stats()
    assert s.misses_remote > 0 and s.bytes_remote > 0


def remote_store_freed_on_drop():
    """A dropped RemoteStore frees its device shards at once: no
    reference cycle waits for the garbage collector."""
    import gc
    import weakref

    gc.disable()
    try:
        store = RemoteStore(np.zeros((2, 8, 4), np.float32), hosts=E)
        store.fetch(np.zeros(3), np.arange(3))
        ref = weakref.ref(store)
        del store
        assert ref() is None
    finally:
        gc.enable()


def run_all():
    check("fetch_rows_onesided_vs_lax", fetch_rows_onesided_vs_lax)
    check("fetch_rows_instrumented", fetch_rows_instrumented)
    check("fetch_rows_runtime_timestamps", fetch_rows_runtime_timestamps)
    check("remote_lookup_bitwise_bulk", remote_lookup_bitwise_bulk)
    check("remote_lookup_bitwise_onesided", remote_lookup_bitwise_onesided)
    check("tier_churn_promotion_demotion", tier_churn_promotion_demotion)
    check("engine_remote_cold_tier", engine_remote_cold_tier)
    check("remote_store_freed_on_drop", remote_store_freed_on_drop)

    if failures:
        print("FAILURES:", failures)
        sys.exit(1)
    print("ALL TIERING CHECKS PASS")


if __name__ == "__main__":
    run_all()
