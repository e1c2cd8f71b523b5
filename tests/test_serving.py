"""DLRM serving engine: scores, request validation, the tiered store."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest


# ---------------------------------------------------------------------------
# DLRM CTR scoring engine (fused-TBE consumer)
# ---------------------------------------------------------------------------

def test_dlrm_engine_scores_match_direct_forward():
    import dataclasses

    from repro.configs import dlrm as dlrm_cfg
    from repro.core.jagged import JaggedBatch
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import CTRRequest, DLRMEngine

    cfg = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="interpret")
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    T, L, F = cfg.num_sparse_features, cfg.pooling, cfg.num_dense_features

    rng = np.random.default_rng(0)
    reqs = [CTRRequest(
        rid=rid,
        dense=rng.standard_normal(F).astype(np.float32),
        indices=rng.integers(0, cfg.rows_per_table, (T, L)).astype(np.int32),
        lengths=rng.integers(1, L + 1, (T,)).astype(np.int32),
    ) for rid in range(5)]

    eng = DLRMEngine(params, cfg, batch_size=3)   # 5 reqs -> 2 flushes
    for r in reqs:
        eng.submit(r)
    scores = eng.run_to_completion()
    assert sorted(scores) == [0, 1, 2, 3, 4]
    assert all(0.0 < s < 1.0 for s in scores.values())

    # each score equals an unbatched direct forward of that request
    for r in reqs[:2]:
        batch = JaggedBatch(
            indices=jnp.asarray(r.indices[:, None, :]),
            lengths=jnp.asarray(r.lengths[:, None]))
        direct = jax.nn.sigmoid(dlrm_mod.forward(
            params, jnp.asarray(r.dense[None]), batch, cfg))
        np.testing.assert_allclose(scores[r.rid], float(direct[0]),
                                   atol=1e-5, rtol=1e-5)


def test_dlrm_engine_rejects_bad_shapes():
    import dataclasses

    from repro.configs import dlrm as dlrm_cfg
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import CTRRequest, DLRMEngine

    cfg = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference")
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    eng = DLRMEngine(params, cfg, batch_size=2)
    with pytest.raises(ValueError):
        eng.submit(CTRRequest(
            rid=0,
            dense=np.zeros(cfg.num_dense_features, np.float32),
            indices=np.zeros((1, 1), np.int32),
            lengths=np.zeros((1,), np.int32)))


def test_dlrm_engine_rejects_bad_dtypes():
    """Float indices/lengths (or int dense) must fail loudly at submit,
    not get silently truncated into the jitted forward."""
    import dataclasses

    from repro.configs import dlrm as dlrm_cfg
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import CTRRequest, DLRMEngine

    cfg = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference")
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    eng = DLRMEngine(params, cfg, batch_size=2)
    T, L, F = cfg.num_sparse_features, cfg.pooling, cfg.num_dense_features
    good = dict(dense=np.zeros(F, np.float32),
                indices=np.zeros((T, L), np.int32),
                lengths=np.ones(T, np.int32))
    with pytest.raises(TypeError, match="indices"):
        eng.submit(CTRRequest(rid=0, **{
            **good, "indices": np.zeros((T, L), np.float32)}))
    with pytest.raises(TypeError, match="lengths"):
        eng.submit(CTRRequest(rid=1, **{
            **good, "lengths": np.ones(T, np.float64)}))
    with pytest.raises(TypeError, match="dense"):
        eng.submit(CTRRequest(rid=2, **{
            **good, "dense": np.zeros(F, np.int32)}))
    assert not eng.queue                      # nothing slipped through
    eng.submit(CTRRequest(rid=3, **good))     # the good one is accepted
    assert len(eng.queue) == 1


def test_dlrm_engine_cached_matches_uncached():
    """cfg.cache.rows > 0: flush prefetches into the HBM slot pool and
    scores over it — pCTRs must equal the uncached engine's exactly."""
    import dataclasses

    from repro.cache import CacheConfig
    from repro.configs import dlrm as dlrm_cfg
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import CTRRequest, DLRMEngine

    base = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="interpret")
    params = dlrm_mod.init_params(jax.random.key(1), base)
    T, L, F = base.num_sparse_features, base.pooling, base.num_dense_features

    rng = np.random.default_rng(7)
    ranks = rng.zipf(1.2, size=(6, T, L))     # zipf traffic, like serving
    reqs = [CTRRequest(
        rid=rid,
        dense=rng.standard_normal(F).astype(np.float32),
        indices=np.minimum(ranks[rid] - 1,
                           base.rows_per_table - 1).astype(np.int32),
        lengths=rng.integers(1, L + 1, (T,)).astype(np.int32),
    ) for rid in range(6)]

    plain = DLRMEngine(params, base, batch_size=4)
    cached_cfg = dataclasses.replace(base, cache=CacheConfig(rows=48))
    cached = DLRMEngine(params, cached_cfg, batch_size=4)
    assert cached.cache is not None and plain.cache is None
    for r in reqs:
        plain.submit(r)
        cached.submit(r)
    want = plain.run_to_completion()
    got = cached.run_to_completion()
    assert sorted(got) == sorted(want)
    for rid in want:
        np.testing.assert_allclose(got[rid], want[rid], atol=1e-6,
                                   rtol=1e-6)
    stats = cached.cache_stats()
    assert stats.batches == 2                  # 6 reqs / batch_size 4
    assert stats.misses > 0
    assert stats.hits > 0                      # zipf repeats across flushes


def test_dlrm_engine_rejects_out_of_range_values():
    """Out-of-range indices/lengths fail at submit — the uncached gather
    would clamp them into a silently wrong score, the cached path would
    refuse the whole micro-batch at prefetch."""
    import dataclasses

    from repro.configs import dlrm as dlrm_cfg
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import CTRRequest, DLRMEngine

    cfg = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference")
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    eng = DLRMEngine(params, cfg, batch_size=2)
    T, L, F = cfg.num_sparse_features, cfg.pooling, cfg.num_dense_features
    good = dict(dense=np.zeros(F, np.float32),
                indices=np.zeros((T, L), np.int32),
                lengths=np.ones(T, np.int32))
    with pytest.raises(ValueError, match="indices"):
        eng.submit(CTRRequest(rid=0, **{
            **good,
            "indices": np.full((T, L), cfg.rows_per_table, np.int32)}))
    with pytest.raises(ValueError, match="lengths"):
        eng.submit(CTRRequest(rid=1, **{
            **good, "lengths": np.full(T, L + 1, np.int32)}))
    assert not eng.queue
    # sentinel padding BEYOND lengths is arbitrary — must stay accepted
    padded = np.full((T, L), -1, np.int32)
    padded[:, 0] = 3
    eng.submit(CTRRequest(rid=2, **{**good, "indices": padded}))
    assert len(eng.queue) == 1


def test_dlrm_engine_cached_splits_oversized_working_set():
    """A micro-batch whose UNION working set overflows the slot pool must
    split instead of stalling the queue head or dropping requests — and a
    pool too small for even one request is rejected at construction."""
    import dataclasses

    from repro.cache import CacheConfig
    from repro.configs import dlrm as dlrm_cfg
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import CTRRequest, DLRMEngine

    base = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference")
    params = dlrm_mod.init_params(jax.random.key(2), base)
    T, L, F = base.num_sparse_features, base.pooling, base.num_dense_features

    with pytest.raises(ValueError, match="cache_rows"):
        DLRMEngine(params,
                   dataclasses.replace(base, cache=CacheConfig(rows=L - 1)),
                   batch_size=2)

    # pool holds exactly one request's working set (L ids/table): a
    # 2-request flush with disjoint ids must split 2 -> 1, score both
    # across flushes, and match the uncached engine exactly
    cfg = dataclasses.replace(base, cache=CacheConfig(rows=L))
    eng = DLRMEngine(params, cfg, batch_size=2)
    plain = DLRMEngine(params, base, batch_size=2)
    rng = np.random.default_rng(9)
    reqs = [CTRRequest(
        rid=rid,
        dense=rng.standard_normal(F).astype(np.float32),
        indices=(np.arange(T * L, dtype=np.int32).reshape(T, L)
                 + rid * L) % base.rows_per_table,
        lengths=np.full(T, L, np.int32)) for rid in range(2)]
    for r in reqs:
        eng.submit(r)
        plain.submit(r)
    first = eng.flush()
    assert len(first) == 1                # split: scored the head only
    assert len(eng.queue) == 1            # nothing silently dropped
    got = {**first, **eng.run_to_completion()}
    want = plain.run_to_completion()
    assert sorted(got) == sorted(want) == [0, 1]
    for rid in want:
        np.testing.assert_allclose(got[rid], want[rid], atol=1e-6,
                                   rtol=1e-6)


def test_dlrm_engine_cache_rejects_parallel_ctx():
    import dataclasses

    from repro.cache import CacheConfig
    from repro.configs import dlrm as dlrm_cfg
    from repro.models import dlrm as dlrm_mod
    from repro.serving.engine import DLRMEngine

    cfg = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="reference",
                              cache=CacheConfig(rows=16))
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    with pytest.raises(NotImplementedError, match="cache"):
        DLRMEngine(params, cfg, batch_size=2, ctx=object())
