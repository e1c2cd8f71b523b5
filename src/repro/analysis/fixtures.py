"""Shape-only audit fixtures for every attached kernel contract.

One tiny ``ShapeDtypeStruct`` tracing setup per contract in the repo's
``KERNEL_CONTRACTS`` registries — the CLI (``python -m repro.analysis
--contracts``) audits all of them in a few seconds with zero FLOPs and
zero allocation.  This module imports the hot modules (jax included),
so the CLI loads it LAZILY: the lint/protocol layers stay importable on
any tree state.

Each fixture mirrors the canonical call site it guards (the shapes are
the repo's own smoke shapes), so a regression that adds a launch, a
collective, a callback, or drops the scatter donation fails here the
same way it would fail in serving.
"""
from __future__ import annotations

from typing import List

from repro.analysis.contracts import AuditReport, audit


def _sds(shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype)


def audit_tbe_fused() -> AuditReport:
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    T, R, D, B, L = 4, 64, 16, 8, 4
    return audit(
        lambda t, i, w: kops.embedding_bag_batched(
            t, i, None, w, mode="interpret", fused=True),
        (_sds((T, R, D), jnp.float32), _sds((T, B, L), jnp.int32),
         _sds((T, B, L), jnp.float32)),
        kops.KERNEL_CONTRACTS["tbe_fused"])


def audit_tbe_flat() -> AuditReport:
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    T, D, B, L, N = 4, 16, 8, 4, 4 * 32
    return audit(
        lambda p, o, i, w: kops.embedding_bag_batched_flat(
            p, o, i, None, w, mode="interpret"),
        (_sds((N, D), jnp.float32), _sds((T,), jnp.int32),
         _sds((T, B, L), jnp.int32), _sds((T, B, L), jnp.float32)),
        kops.KERNEL_CONTRACTS["tbe_flat"])


def audit_tbe_multihot() -> AuditReport:
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    pooling, D, B = (3, 1, 12, 2), 16, 8
    N, S = 4 * 32, sum(pooling)
    return audit(
        lambda p, o, i, ln: kops.embedding_bag_packed(
            p, o, i, ln, pooling, mode="interpret"),
        (_sds((N, D), jnp.float32), _sds((len(pooling),), jnp.int32),
         _sds((B, S), jnp.int32), _sds((B, len(pooling)), jnp.int32)),
        kops.KERNEL_CONTRACTS["tbe_multihot"])


def audit_rw_partial_fused() -> AuditReport:
    import jax.numpy as jnp

    from repro.kernels import ops as kops

    T, R_shard, D, B, L = 4, 8, 16, 8, 4
    return audit(
        lambda t, i: kops.embedding_bag_rw_partial_batched(
            t, 0, i, mode="interpret", fused=True),
        (_sds((T, R_shard, D), jnp.float32), _sds((T, B, L), jnp.int32)),
        kops.KERNEL_CONTRACTS["rw_partial_fused"])


def audit_cached_device_lookup() -> AuditReport:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.cache import CacheConfig, CachedEmbeddingBag
    from repro.cache import cached_bag
    from repro.core.embedding_bag import EmbeddingBagConfig

    T, D, S = 4, 16, 32
    cfg = EmbeddingBagConfig(num_tables=T, rows_per_table=128, dim=D,
                             kernel_mode="interpret",
                             cache=CacheConfig(rows=S))
    bag = CachedEmbeddingBag(np.zeros((T, 128, D), np.float32), cfg)
    return audit(
        lambda p, i, w: bag.device_lookup(p, i, None, w),
        (jax.ShapeDtypeStruct(bag.pool.shape, bag.pool.dtype),
         _sds((T, 8, 4), jnp.int32), _sds((T, 8, 4), jnp.float32)),
        cached_bag.KERNEL_CONTRACTS["device_lookup"])


def audit_pooled_lookup_local() -> AuditReport:
    import jax.numpy as jnp

    from repro.core import embedding_bag as eb
    from repro.core.jagged import JaggedBatch

    T, R, D, B, L = 4, 64, 16, 8, 4
    cfg = eb.EmbeddingBagConfig(num_tables=T, rows_per_table=R, dim=D,
                                kernel_mode="interpret")
    return audit(
        lambda t, i, ln: eb.pooled_lookup_local(
            t, JaggedBatch(indices=i, lengths=ln), cfg),
        (_sds((T, R, D), jnp.float32), _sds((T, B, L), jnp.int32),
         _sds((T, B), jnp.int32)),
        eb.KERNEL_CONTRACTS["pooled_lookup_local"])


def audit_scatter_donation() -> AuditReport:
    import jax.numpy as jnp

    from repro.cache import tiers

    S, D, M = 64, 16, 8
    return audit(
        tiers._scatter_rows,
        (_sds((S, D), jnp.float32), _sds((M,), jnp.int32),
         _sds((M, D), jnp.float32)),
        tiers.KERNEL_CONTRACTS["scatter_rows"])


def audit_tiered_forward() -> AuditReport:
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.cache import CacheConfig
    from repro.configs import dlrm as dlrm_cfg
    from repro.core.jagged import JaggedBatch
    from repro.models import dlrm as dlrm_mod
    from repro.serving import engine

    cache_rows, batch = 32, 8
    cfg = dataclasses.replace(dlrm_cfg.smoke(), kernel_mode="interpret",
                              cache=CacheConfig(rows=cache_rows))
    T, D = cfg.num_sparse_features, cfg.embedding_dim
    params_t = jax.eval_shape(
        lambda: dlrm_mod.init_params(jax.random.key(0), cfg))
    params_t = {**params_t,
                "tables": jax.ShapeDtypeStruct((T * cache_rows, D),
                                               jnp.float32)}
    dense_t = _sds((batch, cfg.num_dense_features), jnp.float32)
    batch_t = JaggedBatch(_sds((T, batch, cfg.pooling), jnp.int32),
                          _sds((T, batch), jnp.int32))
    return audit(
        engine.make_scorer(cfg), (params_t, dense_t, batch_t),
        engine.KERNEL_CONTRACTS["tiered_forward"])


ALL_FIXTURES = (
    audit_tbe_fused,
    audit_tbe_flat,
    audit_tbe_multihot,
    audit_rw_partial_fused,
    audit_cached_device_lookup,
    audit_pooled_lookup_local,
    audit_scatter_donation,
    audit_tiered_forward,
)


def run_all() -> List[AuditReport]:
    """Audit every attached contract against its fixture."""
    return [fixture() for fixture in ALL_FIXTURES]
