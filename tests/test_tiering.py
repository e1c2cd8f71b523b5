"""Tiered embedding store (repro/cache/tiers.py + planner integration):
single-device tier-interface tests here; the multi-rank remote-tier
checks run tests/_tiering_checks.py in a subprocess with a FORCED
4-device CPU backend (XLA_FLAGS must be set before jax import)."""
import dataclasses
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import (
    CacheConfig,
    HostStore,
    RemoteStore,
    SlotPool,
    SlotPoolManager,
    TableStore,
    make_cold_store,
)
from repro.core.embedding_bag import (
    EmbeddingBagConfig,
    init_tables,
    make_cache,
    pooled_lookup_local,
)
from repro.core.jagged import JaggedBatch, random_jagged_batch
from repro.core.perf_model import (
    H100_DGX,
    TPU_V5E,
    EmbeddingWorkload,
    cached_phase_times,
    tiered_embedding_bag_time,
    tiered_phase_times,
    tiered_speedup_vs_distributed,
)
from repro.core.sharding_plan import TableSpec, plan


# ---------------------------------------------------------------------------
# Multi-rank integration (subprocess, forced 4-device CPU)
# ---------------------------------------------------------------------------

@pytest.mark.timeout(900)
def test_tiering_multirank_suite():
    script = os.path.join(os.path.dirname(__file__), "_tiering_checks.py")
    env = dict(os.environ)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = os.path.join(root, "src")
    proc = subprocess.run(
        [sys.executable, script], env=env, capture_output=True, text=True,
        timeout=880)
    sys.stdout.write(proc.stdout[-4000:])
    sys.stderr.write(proc.stderr[-4000:])
    assert proc.returncode == 0, "tiering multi-rank checks failed"


# ---------------------------------------------------------------------------
# TableStore interface (single device)
# ---------------------------------------------------------------------------

def test_host_store_fetch_matches_numpy():
    rng = np.random.default_rng(0)
    tables = rng.standard_normal((3, 32, 8)).astype(np.float32)
    store = HostStore(tables)
    assert isinstance(store, TableStore)
    assert (store.tier, store.hosts, store.home) == ("host", 1, 0)
    assert store.rows_per_host == 32
    t = np.array([0, 2, 1, 2])
    r = np.array([5, 31, 0, 7])
    np.testing.assert_array_equal(store.fetch(t, r), tables[t, r])


def test_slot_pool_scatter_fetch_roundtrip():
    pool = SlotPool(num_tables=2, slots=8, dim=4, dtype=np.float32)
    assert pool.tier == "hbm" and pool.slots == 8
    rows = np.arange(12, dtype=np.float32).reshape(3, 4)
    # flat addresses slot_offsets[t] + slot for (t, slot) (0,1), (1,0), (1,7)
    pool.scatter(np.array([0 * 8 + 1, 1 * 8 + 0, 1 * 8 + 7]), rows)
    np.testing.assert_array_equal(
        pool.fetch([0, 1, 1], [1, 0, 7]), rows)
    assert pool.array.shape == (2 * 8, 4)      # flat, never reallocated
    assert pool.nbytes == 2 * 8 * 4 * 4
    assert pool.live_nbytes == pool.nbytes     # exact: no padding to discount


def test_make_cold_store_dispatch_and_errors():
    tables = np.zeros((1, 8, 4), np.float32)
    cc = CacheConfig(rows=4)
    assert isinstance(make_cold_store(tables, cc), HostStore)
    with pytest.raises(ValueError, match="cold_tier"):
        make_cold_store(tables, dataclasses.replace(cc, cold_tier="disk"))
    with pytest.raises(ValueError, match="backend"):
        RemoteStore(tables, hosts=2, backend="tcp")
    # the single-process simulation needs >= 2 devices to back remote hosts
    if len(jax.devices()) == 1:
        with pytest.raises(ValueError, match="devices"):
            make_cold_store(tables,
                            dataclasses.replace(cc, cold_tier="remote",
                                                remote_hosts=2))
    # (full RemoteStore behaviour is covered by _tiering_checks.py)


def test_remote_store_rejects_uneven_rows():
    with pytest.raises(ValueError, match="divide"):
        RemoteStore(np.zeros((1, 7, 4), np.float32), hosts=2)


# ---------------------------------------------------------------------------
# Staged gather: missed rows straight into the pool's reused host buffer
# ---------------------------------------------------------------------------

def _bits(a):
    return np.asarray(a).view(np.uint32)


def test_staged_gather_is_bitwise_two_index():
    rng = np.random.default_rng(5)
    tables = rng.standard_normal((4, 50, 8)).astype(np.float32)
    store = HostStore(tables)
    t = rng.integers(0, 4, 37)                 # mixed tables, repeats
    r = rng.integers(0, 50, 37)
    out = np.full((37, 8), np.nan, np.float32)
    got = store.fetch(t, r, out=out)
    assert got is out
    np.testing.assert_array_equal(_bits(got), _bits(tables[t, r]))
    np.testing.assert_array_equal(_bits(store.fetch(t, r)),
                                  _bits(tables[t, r]))
    # the flat view follows the tables when they are set again
    other = -tables
    store.tables = other
    np.testing.assert_array_equal(_bits(store.fetch(t, r, out=out)),
                                  _bits(other[t, r]))


def test_staged_gather_refuses_out_of_range_ids():
    """``mode="clip"`` must not hide a bad id: row R of table t would be
    row 0 of table t + 1 in the flat view."""
    tables = np.arange(3 * 4 * 2, dtype=np.float32).reshape(3, 4, 2)
    store = HostStore(tables)
    out = np.full((2, 2), -1, np.float32)
    for t, r in ((0, 4), (1, 4), (0, -1), (3, 0), (-1, 0)):
        with pytest.raises(IndexError):
            store.fetch(np.array([0, t]), np.array([1, r]), out=out)
        assert (out == -1).all()               # nothing was read


def test_staged_pool_contents_across_pow2_miss_counts():
    """Prefetches whose miss counts cross powers of two (staging sizes
    4, 4, 8, 128, 1): every resident slot holds its table row bitwise,
    the buffer is reallocated only at a size change, and the padding
    is P - M rows per scatter."""
    cfg = _cfg(T=2, R=256, cache_rows=128)
    tables = init_tables(jax.random.key(6), cfg)
    host = np.asarray(tables)
    bag = make_cache(tables, cfg)
    fresh = [0, 0]
    for m in (3, 4, 5, 100, 1):
        n = (m - m // 2, m // 2)               # split over both tables
        idx = np.zeros((2, 1, n[0]), np.int32)
        for t in (0, 1):
            idx[t, 0, :n[t]] = fresh[t] + np.arange(n[t])
            fresh[t] += n[t]
        bag.prefetch_arrays(idx, np.array([[n[0]], [n[1]]], np.int32))
        for t in (0, 1):
            ids = np.arange(fresh[t])
            got = bag.hot.fetch(np.full_like(ids, t),
                                bag.mgr.slot_of_id[t, ids])
            np.testing.assert_array_equal(_bits(got), _bits(host[t, ids]))
    s = bag.stats
    assert s.misses == s.fetch_host == 113 and s.evictions == 0
    assert s.staging_allocs == 4               # P: 4, 4, 8, 128, 1
    assert s.pad_rows == 1 + 0 + 3 + 28 + 0


def test_staged_reuse_fence_two_prefetches_without_forward():
    """Two gathers of one staging size into one pool, nothing reading
    the pool between them: the second gather must wait for the first
    scatter, which may still be reading the buffer (on CPU the device
    array aliases it and the scatter runs asynchronously)."""
    rng = np.random.default_rng(8)
    tables = rng.standard_normal((2, 16384, 128)).astype(np.float32)
    store = HostStore(tables)
    pool = SlotPool(num_tables=2, slots=32768, dim=128, dtype=np.float32)
    m = 16384
    for _ in range(8):
        t = rng.integers(0, 2, (2, m))
        r = rng.integers(0, 16384, (2, m))
        for k in range(2):
            rows = store.fetch(t[k], r[k], out=pool.staging(m))
            pool.scatter(np.arange(m) + k * m, rows)
        got = np.asarray(pool.array)
        for k in range(2):
            np.testing.assert_array_equal(_bits(got[k * m:(k + 1) * m]),
                                          _bits(tables[t[k], r[k]]))
    assert pool.stats.staging_allocs == 1 and pool.stats.pad_rows == 0


def test_staging_allocs_counts_only_size_changes():
    pool = SlotPool(num_tables=1, slots=4, dim=2, dtype=np.float32)
    for m, allocs in ((5, 1), (8, 1), (6, 1), (9, 2), (16, 2), (3, 3),
                      (4, 3), (1, 4), (1, 4), (2, 5)):
        view = pool.staging(m)
        assert view.shape == (m, 2) and view.dtype == np.float32
        assert pool.stats.staging_allocs == allocs, m
    # rows from anywhere else are padded as before, with no allocation
    pool.scatter(np.array([0, 1, 2]), np.ones((3, 2), np.float32))
    assert pool.stats.staging_allocs == 5 and pool.stats.pad_rows == 1
    np.testing.assert_array_equal(pool.fetch([0, 0, 0], [0, 1, 2]),
                                  np.ones((3, 2), np.float32))


# ---------------------------------------------------------------------------
# Warmup from logged frequencies
# ---------------------------------------------------------------------------

def _cfg(T=2, R=256, D=8, cache_rows=16, **kw):
    return EmbeddingBagConfig(num_tables=T, rows_per_table=R, dim=D,
                              kernel_mode="reference",
                              cache=CacheConfig(rows=cache_rows), **kw)


def _with_warmup(cfg, freqs):
    return dataclasses.replace(
        cfg, cache=dataclasses.replace(cfg.cache, warmup_freqs=freqs))


def test_warmup_freqs_skip_cold_start_miss_burst():
    cfg = _cfg()
    tables = init_tables(jax.random.key(0), cfg)
    freqs = np.zeros((2, 256))
    freqs[:, :16] = np.arange(16, 0, -1)     # logged: rows 0..15 hot
    warm = make_cache(tables, _with_warmup(cfg, freqs))
    cold = make_cache(tables, cfg)
    assert warm.mgr.resident_rows == 32      # top-S of both tables admitted
    assert warm.stats.bytes_h2d == 32 * warm.row_bytes   # warmup traffic...
    assert warm.stats.lookups == 0           # ...but no lookups yet
    rng = np.random.default_rng(1)
    idx = jnp.asarray(rng.integers(0, 16, (2, 8, 4)), jnp.int32)
    b = JaggedBatch(idx, jnp.full((2, 8), 4, jnp.int32))
    got = warm.lookup(b)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(pooled_lookup_local(tables, b, cfg)))
    assert warm.stats.misses == 0            # the burst is gone
    cold.prefetch(b)
    assert cold.stats.misses > 0             # ...the unseeded bag pays it


def test_warmup_freqs_broadcast_and_validation():
    cfg = _cfg(T=3, R=64, cache_rows=8)
    tables = init_tables(jax.random.key(1), cfg)
    # (R,) broadcasts to every table
    freqs = np.zeros(64)
    freqs[:4] = [4, 3, 2, 1]
    bag = make_cache(tables, _with_warmup(cfg, freqs))
    for t in range(3):
        assert set(bag.mgr.resident_ids(t)) == {0, 1, 2, 3}
    m = SlotPoolManager(3, 64, 8)
    with pytest.raises(ValueError, match="warmup freqs"):
        m.seed_frequencies(np.zeros((2, 64)))
    with pytest.raises(ValueError, match="non-negative"):
        m.seed_frequencies(np.full((3, 64), -1))
    # an all-zero seed admits nothing
    m.seed_frequencies(np.zeros((3, 64)))
    assert m.warmup_admit().fetch_rows.size == 0


def test_warmup_seeds_lfu_ranking():
    """Seeded counters must drive the FIRST eviction decision: the row
    with the lowest logged frequency is the victim."""
    cfg = _cfg(T=1, R=32, cache_rows=2)
    tables = init_tables(jax.random.key(2), cfg)
    freqs = np.zeros((1, 32))
    freqs[0, 0], freqs[0, 1] = 100, 2        # both pre-admitted
    bag = make_cache(tables, _with_warmup(cfg, freqs))
    assert set(bag.mgr.resident_ids(0)) == {0, 1}
    idx = jnp.full((1, 1, 1), 9, jnp.int32)  # force one eviction
    bag.prefetch(JaggedBatch(idx, jnp.ones((1, 1), jnp.int32)))
    assert set(bag.mgr.resident_ids(0)) == {0, 9}   # victim was row 1


# ---------------------------------------------------------------------------
# Per-tier stats accounting (single-host tier: everything is host traffic)
# ---------------------------------------------------------------------------

def test_stats_tier_split_host_only():
    cfg = _cfg()
    tables = init_tables(jax.random.key(3), cfg)
    cache = make_cache(tables, cfg)
    rng = np.random.default_rng(2)
    for _ in range(3):
        cache.prefetch(random_jagged_batch(rng, 2, 8, 4, 256, zipf_a=1.2))
    s = cache.stats
    assert s.misses_remote == 0 and s.bytes_remote == 0
    assert s.misses_host == s.misses
    assert s.fetch_remote == 0
    assert s.bytes_h2d == s.fetch_host * cache.row_bytes
    assert s.remote_miss_fraction == 0.0
    d = s.as_dict()
    for k in ("misses_host", "misses_remote", "bytes_remote",
              "fetch_host", "fetch_remote", "remote_miss_fraction"):
        assert k in d


# ---------------------------------------------------------------------------
# Remote-miss-aware perf model
# ---------------------------------------------------------------------------

def test_tiered_phase_times_reduce_to_cached_at_one_host():
    w = EmbeddingWorkload(num_tables=26, batch_per_device=1024, pooling=32,
                          dim=128)
    for hw in (H100_DGX, TPU_V5E):
        t1 = tiered_phase_times(w, hw, hit_rate=0.9, hosts=1)
        assert t1["fetch_remote"] == 0.0
        legacy = cached_phase_times(w, hw, hit_rate=0.9)
        assert "fetch_remote" not in legacy
        for k, v in legacy.items():
            assert t1[k] == v


def test_tiered_remote_penalty_grows_with_hosts_and_misses():
    w = EmbeddingWorkload(num_tables=26, batch_per_device=1024, pooling=32,
                          dim=128)
    t_by_hosts = [tiered_embedding_bag_time(w, H100_DGX, hit_rate=0.9,
                                            hosts=h) for h in (1, 2, 8, 32)]
    assert all(a < b for a, b in zip(t_by_hosts, t_by_hosts[1:]))
    # a perfect hit rate never pays the network, any number of hosts
    assert tiered_embedding_bag_time(w, H100_DGX, hit_rate=1.0, hosts=32) \
        == tiered_embedding_bag_time(w, H100_DGX, hit_rate=1.0, hosts=1)


def test_tiered_onesided_wins_at_small_miss_payload():
    """Few missed rows = small messages: the one-sided transport's low
    alpha wins, the bulk transport's beta wins at big payloads — the
    paper's Fig. 1 crossover on the row-fetch path."""
    small = EmbeddingWorkload(num_tables=1, batch_per_device=4, pooling=4,
                              dim=32)
    big = EmbeddingWorkload(num_tables=64, batch_per_device=4096,
                            pooling=64, dim=256)
    t_small = {o: tiered_embedding_bag_time(
        small, H100_DGX, hit_rate=0.99, hosts=8, onesided=o)
        for o in (False, True)}
    t_big = {o: tiered_embedding_bag_time(
        big, H100_DGX, hit_rate=0.5, hosts=8, onesided=o)
        for o in (False, True)}
    assert t_small[True] < t_small[False]
    assert t_big[False] < t_big[True]


def test_tiered_recovery_projection():
    """A 90%-hit tiered store recovers most of the Fig. 9 slowdown even
    with the cold tier spread over the same number of hosts."""
    w = EmbeddingWorkload(num_tables=26, batch_per_device=1024, pooling=32,
                          dim=128)
    table_bytes = 10e12
    rec = tiered_speedup_vs_distributed(
        table_bytes, w, H100_DGX, hit_rate=0.9, hosts=128)
    assert rec > 1.0                          # beats distributing the table


# ---------------------------------------------------------------------------
# Planner: the fourth "cached" strategy
# ---------------------------------------------------------------------------

def _paper_tables(n=8, rows=50_000_000):
    return [TableSpec(f"t{i}", rows=rows, dim=128, pooling=32)
            for i in range(n)]


def test_planner_emits_cached_when_priced_cheaper():
    """Tables too big to TW-pack, under zipf traffic: the slot pool beats
    the RW pipeline and the planner must say so."""
    p = plan(_paper_tables(), num_shards=8, batch_per_shard=1024,
             hbm_budget_bytes=8e9, hw=H100_DGX, zipf_a=1.2)
    strategies = {pl.strategy for pl in p.placements}
    assert "cached" in strategies
    cached = [pl for pl in p.placements if pl.strategy == "cached"]
    for pl in cached:
        assert pl.cache_rows > 0
        assert 0.0 < pl.est_hit_rate <= 1.0
        assert pl.shard >= 0
        # it was priced cheaper than both alternatives it displaced
        from repro.core.sharding_plan import _rw_time
        assert pl.est_time_s < _rw_time(pl.table, 1024, 8, H100_DGX)
    # pool bytes (not the full table) are what's charged to the shard
    assert all(b <= 8e9 for b in p.per_shard_bytes)
    assert p.cache_rows_of(cached[0].table.name) == cached[0].cache_rows


def test_planner_cached_respects_budget_and_falls_back():
    """With NO leftover HBM budget the cached strategy can't fit and the
    planner falls back to RW exactly as before."""
    p = plan(_paper_tables(), num_shards=8, batch_per_shard=1024,
             hbm_budget_bytes=1, hw=H100_DGX, zipf_a=1.2)
    assert all(pl.strategy == "row" for pl in p.placements)


def test_planner_no_zipf_is_backward_compatible():
    tables = [TableSpec("small", rows=1000, dim=64, pooling=8),
              TableSpec("big", rows=10_000_000, dim=128, pooling=32)]
    a = plan(tables, num_shards=4, batch_per_shard=256,
             hbm_budget_bytes=1e9)
    assert {pl.strategy for pl in a.placements} <= {"table", "row"}


# ---------------------------------------------------------------------------
# Example smoke (the refactored-API consumer)
# ---------------------------------------------------------------------------

def test_dlrm_inference_example_main_runs():
    """examples/dlrm_inference.py routes through DLRMConfig tier fields;
    its main() must run end-to-end on the default (single-device) CPU."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    path = os.path.join(root, "examples", "dlrm_inference.py")
    spec = importlib.util.spec_from_file_location("dlrm_inference_ex", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.main()
