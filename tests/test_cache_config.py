"""The unified ``CacheConfig`` API: golden old-vs-new equivalence.

An old-style config built from the DEPRECATED flat fields
(``cache_rows`` / ``cache_policy`` / ... on ``EmbeddingBagConfig`` and
``DLRMConfig``) must (a) emit a ``DeprecationWarning`` per alias used,
(b) normalize to a config EQUAL to the new-style ``cache=CacheConfig``
spelling, and (c) build an engine whose scores are BITWISE identical
and whose ``cache_stats()`` counters match the new-style engine's.
Also pins the shared slot-geometry helpers (``slots_per_table`` /
``slot_offsets``), the exact flat-pool byte accounting
(``live_nbytes == slot_pool_bytes``), and the ``CacheStats.as_dict``
schema contract.
"""
import dataclasses
import warnings

import jax
import numpy as np
import pytest

from repro.cache import CacheConfig, CacheStats
from repro.configs import dlrm as dlrm_cfg
from repro.core.cache_config import ALIAS_FIELDS
from repro.core.embedding_bag import (
    EmbeddingBagConfig, init_tables, make_cache,
)
from repro.core.perf_model import padded_slot_pool_bytes, slot_pool_bytes
from repro.models import dlrm as dlrm_mod
from repro.serving.engine import CTRRequest, DLRMEngine

# ---------------------------------------------------------------------------
# Deprecated-alias shims: every alias warns, forwards, then reads None
# ---------------------------------------------------------------------------

_EB_ALIASES = [
    ("cache_rows", 8),
    ("cache_policy", "lru"),
    ("cache_rows_per_table", (8, 8)),
    ("cold_tier", "host"),
    ("remote_hosts", 2),
    ("remote_backend", "bulk"),
    ("warmup_freqs", np.ones((2, 16))),
]

_DLRM_ALIASES = [
    ("cache_rows", 8),
    ("cache_policy", "lru"),
    ("cold_tier", "host"),
    ("remote_hosts", 2),
    ("remote_backend", "bulk"),
    ("pipeline_depth", 2),
    ("warmup_freqs", np.ones(16)),
]


@pytest.mark.parametrize("alias,value", _EB_ALIASES,
                         ids=[a for a, _ in _EB_ALIASES])
def test_embedding_config_alias_warns_and_forwards(alias, value):
    with pytest.warns(DeprecationWarning, match=alias):
        cfg = EmbeddingBagConfig(num_tables=2, rows_per_table=16, dim=4,
                                 **{alias: value})
    # the alias forwarded into cfg.cache and reset to its None sentinel
    assert getattr(cfg, alias) is None
    got = getattr(cfg.cache, ALIAS_FIELDS[alias])
    if alias == "warmup_freqs":
        assert got is value
    elif alias == "cache_rows_per_table":
        assert got == tuple(value)
    else:
        assert got == value


@pytest.mark.parametrize("alias,value", _DLRM_ALIASES,
                         ids=[a for a, _ in _DLRM_ALIASES])
def test_dlrm_config_alias_warns_and_forwards(alias, value):
    with pytest.warns(DeprecationWarning, match=alias):
        cfg = dataclasses.replace(dlrm_cfg.smoke(), **{alias: value})
    assert getattr(cfg, alias) is None
    got = getattr(cfg.cache, ALIAS_FIELDS[alias])
    if alias == "warmup_freqs":
        assert got is value
    else:
        assert got == value


def test_new_style_config_is_warning_free():
    """The replacement spelling must never trip -W error::DeprecationWarning
    (the CI tier-1 filter): construction, replace(cache=...), and nested
    cache replaces all stay silent."""
    with warnings.catch_warnings():
        warnings.simplefilter("error", DeprecationWarning)
        cfg = EmbeddingBagConfig(
            num_tables=2, rows_per_table=16, dim=4,
            cache=CacheConfig(rows=8, policy="lru", cold_tier="remote",
                              remote_backend="onesided"))
        cfg = dataclasses.replace(cfg, cache=CacheConfig(rows=4))
        cfg = dataclasses.replace(
            cfg, cache=dataclasses.replace(cfg.cache, pipeline_depth=2))
        d = dataclasses.replace(dlrm_cfg.smoke(),
                                cache=CacheConfig(rows=16))
        d = dataclasses.replace(d, kernel_mode="reference")
    assert cfg.cache.rows == 4 and cfg.cache.pipeline_depth == 2
    assert d.cache.rows == 16


def test_old_and_new_configs_normalize_equal():
    """Golden: the flat-field spelling and the CacheConfig spelling land
    on EQUAL configs (dataclass equality over every field)."""
    with pytest.warns(DeprecationWarning):
        old = EmbeddingBagConfig(
            num_tables=2, rows_per_table=32, dim=4,
            kernel_mode="reference",
            cache_rows=8,        # lint: allow[deprecated-cache-field] -- golden test OF the deprecation shim
            cache_policy="lru",  # lint: allow[deprecated-cache-field] -- golden test OF the deprecation shim
            cold_tier="remote",  # lint: allow[deprecated-cache-field] -- golden test OF the deprecation shim
            remote_backend="bulk")  # lint: allow[deprecated-cache-field] -- golden test OF the deprecation shim
    new = EmbeddingBagConfig(num_tables=2, rows_per_table=32, dim=4,
                             kernel_mode="reference",
                             cache=CacheConfig(rows=8, policy="lru",
                                               cold_tier="remote",
                                               remote_backend="bulk"))
    assert old == new
    with pytest.warns(DeprecationWarning):
        old_d = dataclasses.replace(
            dlrm_cfg.smoke(),
            cache_rows=24,       # lint: allow[deprecated-cache-field] -- golden test OF the deprecation shim
            cache_policy="lru",  # lint: allow[deprecated-cache-field] -- golden test OF the deprecation shim
            pipeline_depth=2)
    new_d = dataclasses.replace(
        dlrm_cfg.smoke(),
        cache=CacheConfig(rows=24, policy="lru", pipeline_depth=2))
    assert old_d == new_d


# ---------------------------------------------------------------------------
# Golden engine equivalence: old-style vs new-style serve identically
# ---------------------------------------------------------------------------

def _requests(cfg, n, rng):
    T, L, F = (cfg.num_sparse_features, cfg.pooling,
               cfg.num_dense_features)
    ranks = rng.zipf(1.2, size=(n, T, L))
    return [CTRRequest(
        rid=rid, dense=rng.standard_normal(F).astype(np.float32),
        indices=np.minimum(ranks[rid] - 1,
                           cfg.rows_per_table - 1).astype(np.int32),
        lengths=rng.integers(1, L + 1, T).astype(np.int32))
        for rid in range(n)]


def test_golden_old_style_engine_matches_new_style():
    base = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference")
    with pytest.warns(DeprecationWarning):
        old = dataclasses.replace(
            base,
            cache_rows=24,        # lint: allow[deprecated-cache-field] -- golden test OF the deprecation shim
            cache_policy="lru")   # lint: allow[deprecated-cache-field] -- golden test OF the deprecation shim
    new = dataclasses.replace(base,
                              cache=CacheConfig(rows=24, policy="lru"))
    assert old == new
    params = dlrm_mod.init_params(jax.random.key(0), base)
    eng_old = DLRMEngine(params, old, batch_size=4)
    eng_new = DLRMEngine(params, new, batch_size=4)
    reqs = _requests(base, 12, np.random.default_rng(3))
    for r in reqs:
        eng_old.submit(r)
        eng_new.submit(r)
    got_old = eng_old.run_to_completion()
    got_new = eng_new.run_to_completion()
    assert sorted(got_old) == sorted(got_new) == list(range(12))
    for rid in got_new:                     # BITWISE, not approximately
        assert got_old[rid] == got_new[rid], rid
    d_old = eng_old.cache_stats().as_dict()
    d_new = eng_new.cache_stats().as_dict()
    timers = {"prefetch_s", "scatter_s", "forward_s", "overlap_s",
              "overlap_fraction"}
    for k in d_new:
        if k not in timers:
            assert d_old[k] == d_new[k], k
    assert d_new["hits"] > 0 and d_new["misses"] > 0


# ---------------------------------------------------------------------------
# Shared slot geometry + exact flat-pool byte accounting
# ---------------------------------------------------------------------------

def test_slot_geometry_helpers():
    cc = CacheConfig(rows_per_table=(4, 2, 3))
    assert cc.enabled
    assert cc.slots_per_table(3, 100).tolist() == [4, 2, 3]
    assert cc.slot_offsets(3, 100).tolist() == [0, 4, 6, 9]
    # the uniform scalar clamps to the table size
    assert CacheConfig(rows=8).slots_per_table(2, 4).tolist() == [4, 4]
    assert not CacheConfig().enabled
    with pytest.raises(ValueError, match="one entry per table"):
        cc.slots_per_table(2, 100)
    with pytest.raises(ValueError, match="cache rows"):
        CacheConfig(rows_per_table=(4, 0)).slots_per_table(2, 100)
    with pytest.raises(ValueError, match="pipeline_depth"):
        CacheConfig(pipeline_depth=0)
    with pytest.raises(ValueError, match="cache rows"):
        CacheConfig(rows=-1)
    # array-likes normalize to a hashable tuple (jit static args)
    cc2 = CacheConfig(rows_per_table=np.array([4, 2, 3]))
    assert cc2.rows_per_table == (4, 2, 3)
    assert hash(cc2) == hash(CacheConfig(rows_per_table=(4, 2, 3)))


def test_flat_pool_bytes_exact():
    """The tentpole's byte contract: the device pool allocates EXACTLY
    sum(S_t) * D * itemsize — priced by slot_pool_bytes, measured by
    live_nbytes — strictly below the padded T x max(S_t) rectangle."""
    cfg = EmbeddingBagConfig(num_tables=3, rows_per_table=64, dim=4,
                             kernel_mode="reference",
                             cache=CacheConfig(rows_per_table=(16, 4, 8)))
    tables = init_tables(jax.random.key(0), cfg)
    bag = make_cache(tables, cfg)
    slots = bag.mgr.slots_per_table
    assert bag.pool.shape == (16 + 4 + 8, 4)
    assert bag.hot.live_nbytes == bag.hot.nbytes \
        == slot_pool_bytes(slots, 4) == (16 + 4 + 8) * 4 * 4
    assert padded_slot_pool_bytes(slots, 4) == 3 * 16 * 4 * 4
    assert slot_pool_bytes(slots, 4) < padded_slot_pool_bytes(slots, 4)
    with pytest.raises(ValueError, match=">= 0"):
        slot_pool_bytes((4, -1), 4)
    assert slot_pool_bytes((), 4) == padded_slot_pool_bytes((), 4) == 0


# ---------------------------------------------------------------------------
# CacheStats serialization schema
# ---------------------------------------------------------------------------

def test_cache_stats_schema():
    d = CacheStats().as_dict()
    assert next(iter(d)) == "schema_version"
    assert d["schema_version"] == CacheStats.SCHEMA_VERSION == 4
    assert set(d) == {
        "schema_version", "hits", "misses", "misses_host", "misses_remote",
        "evictions", "bytes_h2d", "bytes_remote", "fetch_host",
        "fetch_remote", "batches", "staging_allocs", "pad_rows",
        "lookups", "hit_rate",
        "remote_miss_fraction", "hits_t", "misses_t", "evictions_t",
        "lookups_t", "hit_rate_t",
        "prefetch_s", "scatter_s", "forward_s", "overlap_s",
        "overlap_fraction",
    }
    # v3: the derived lookups keys are ALWAYS present (lookups_t None
    # before any per-table update, like the other *_t splits)
    assert d["lookups"] == 0 and d["lookups_t"] is None
    s = CacheStats()
    s.update(hits=3, misses=1, evictions=0, bytes_h2d=16,
             hits_t=[2, 1], misses_t=[1, 0], evictions_t=[0, 0])
    d = s.as_dict()
    assert d["hits_t"] == [2, 1] and isinstance(d["hits_t"], list)
    assert d["hit_rate_t"] == [round(2 / 3, 4), 1.0]
    assert d["lookups"] == 4 and d["lookups_t"] == [3, 1]
