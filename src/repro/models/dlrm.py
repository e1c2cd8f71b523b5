"""DLRM — the paper's canonical model (Fig. 2): bottom MLP, embedding
pooling (the distributed Embedding Bag under test), dot-product feature
interaction, top MLP.  ``interaction="dcn"`` swaps the dot products for
DCN-v2's low-rank cross network (DLRM-DCNv2, MLPerf's DLRMv2).

Inference path = §4/§5 of the paper; the training path (BCE on CTR labels)
exists so the framework's optimizer/checkpoint substrates are exercised on
the paper's own model too. The embedding pooling runs through
core/embedding_bag with the configured sharding (RW/CW/TW/DP) and backend,
so every paper experiment (phase timing, NCCL-vs-NVSHMEM analogue,
distribution projection) drives this exact model.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.dlrm import DLRMConfig
from repro.core import embedding_bag as eb
from repro.core.jagged import JaggedBatch, PackedBatch
from repro.core.parallel import ParallelContext


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def _mlp_init(rng, dims, dtype):
    ks = jax.random.split(rng, len(dims) - 1)
    return [{"w": (jax.random.truncated_normal(k, -2, 2,
                                               (i, o)) * i ** -0.5
                   ).astype(dtype),
             "b": jnp.zeros((o,), dtype)}
            for k, i, o in zip(ks, dims[:-1], dims[1:])]


def _mlp_apply(layers_, x, *, final_act=False):
    for i, l in enumerate(layers_):
        x = x @ l["w"] + l["b"]
        if i < len(layers_) - 1 or final_act:
            x = jax.nn.relu(x)
    return x


def _cross_init(rng, dim: int, rank: int, layers: int, dtype):
    ks = jax.random.split(rng, 2 * layers)
    return [{"v": (jax.random.truncated_normal(ks[2 * i], -2, 2, (dim, rank))
                   * dim ** -0.5).astype(dtype),
             "w": (jax.random.truncated_normal(ks[2 * i + 1], -2, 2,
                                               (rank, dim))
                   * rank ** -0.5).astype(dtype),
             "b": jnp.zeros((dim,), dtype)}
            for i in range(layers)]


def init_params(rng, cfg: DLRMConfig, dtype=jnp.float32) -> Dict[str, Any]:
    ks = jax.random.split(rng, 3)
    ecfg = cfg.embedding_config()
    params = {
        "tables": eb.init_tables(ks[0], ecfg),   # (T, R, D) or (sum R_t, D)
        "bottom": _mlp_init(
            ks[1], (cfg.num_dense_features,) + cfg.bottom_mlp, dtype),
        "top": _mlp_init(ks[2], (cfg.interaction_dim,) + cfg.top_mlp, dtype),
    }
    if cfg.interaction == "dcn":
        params["cross"] = _cross_init(jax.random.fold_in(rng, 3),
                                      cfg.interaction_dim, cfg.dcn_rank,
                                      cfg.dcn_layers, dtype)
    return params


# ---------------------------------------------------------------------------
# Feature interaction (dot product, DLRM §2 of Naumov et al.)
# ---------------------------------------------------------------------------

def dot_interaction(dense_vec: jax.Array, pooled: jax.Array) -> jax.Array:
    """dense (B, D), pooled (B, T, D) -> (B, D + (T+1)T/2) features."""
    B, T, D = pooled.shape
    feats = jnp.concatenate([dense_vec[:, None, :], pooled], axis=1)  # (B,T+1,D)
    gram = jnp.einsum("bnd,bmd->bnm", feats, feats)                   # (B,N,N)
    n = T + 1
    iu, ju = jnp.triu_indices(n, k=1)
    pairs = gram[:, iu, ju]                                           # (B, n(n-1)/2)
    return jnp.concatenate([dense_vec, pairs], axis=1)


# ---------------------------------------------------------------------------
# Feature interaction (DCN-v2 low-rank cross network, Wang et al. 2020)
# ---------------------------------------------------------------------------

def dcn_cross(x0: jax.Array, layers) -> jax.Array:
    """x0 (B, N) -> (B, N): ``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l``
    for each layer ``{"v": (N, r), "w": (r, N), "b": (N,)}``
    (arXiv:2008.13535, TorchRec ``LowRankCrossNet``)."""
    with jax.named_scope("repro.dlrm.cross"):
        x = x0
        for layer in layers:
            x = x0 * ((x @ layer["v"]) @ layer["w"] + layer["b"]) + x
        return x


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def forward(params, dense: jax.Array, batch: Union[JaggedBatch, PackedBatch],
            cfg: DLRMConfig,
            ctx: Optional[ParallelContext] = None) -> jax.Array:
    """dense (B, num_dense), batch: sparse lookups -> CTR logit (B,).

    ``batch`` is a ``JaggedBatch``, or for a per-table config
    (``cfg.per_table``) a ``PackedBatch`` of each table's ``L_t`` slots.
    With a ``ctx``, the embedding pooling runs the paper's distributed
    pipeline inside shard_map (tables sharded per cfg.sharding over the tp
    axis, batch replicated over tp / sharded over dp).
    """
    ecfg = cfg.embedding_config()
    if ctx is None:
        pooled = eb.pooled_lookup_local(params["tables"], batch, ecfg)
    else:
        tspec = eb.table_pspec(ecfg, ctx.tp_axis)   # refuses uneven tables
        B = batch.batch_size
        dp = ctx.dp_for(B)

        def inner(tables, b):
            return eb.pooled_lookup_sharded(tables, b, ecfg,
                                            model_axis=ctx.tp_axis)

        bspec = JaggedBatch(
            indices=P(None, dp, None), lengths=P(None, dp),
            weights=None if batch.weights is None else P(None, dp, None))
        pooled = shard_map(
            inner, mesh=ctx.mesh,
            in_specs=(tspec, bspec),
            out_specs=P(dp, None, None),
            check_vma=False,
        )(params["tables"], batch)

    bot = _mlp_apply(params["bottom"], dense, final_act=True)   # (B, D)
    pooled = pooled.astype(bot.dtype)
    if cfg.interaction == "dcn":
        x0 = jnp.concatenate([bot, pooled.reshape(bot.shape[0], -1)], axis=1)
        feats = dcn_cross(x0, params["cross"])                  # (B, (T+1)D)
    else:
        feats = dot_interaction(bot, pooled)
    logit = _mlp_apply(params["top"], feats)                    # (B, 1)
    return logit[:, 0]


def bce_loss(params, dense, batch: JaggedBatch, labels, cfg: DLRMConfig,
             ctx=None) -> jax.Array:
    logit = forward(params, dense, batch, cfg, ctx)
    z = jax.nn.log_sigmoid(logit)
    zn = jax.nn.log_sigmoid(-logit)
    return -jnp.mean(labels * z + (1.0 - labels) * zn)
