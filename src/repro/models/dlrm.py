"""DLRM — the paper's canonical model (Fig. 2): bottom MLP, embedding
pooling (the distributed Embedding Bag under test), dot-product feature
interaction, top MLP.

Inference path = §4/§5 of the paper; the training path (BCE on CTR labels)
exists so the framework's optimizer/checkpoint substrates are exercised on
the paper's own model too. The embedding pooling runs through
core/embedding_bag with the configured sharding (RW/CW/TW/DP) and backend,
so every paper experiment (phase timing, NCCL-vs-NVSHMEM analogue,
distribution projection) drives this exact model.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.dlrm import DLRMConfig
from repro.core import embedding_bag as eb
from repro.core.jagged import JaggedBatch
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


def init_params(rng, cfg: DLRMConfig, dtype=jnp.float32) -> Dict[str, Any]:
    ks = jax.random.split(rng, 3)
    ecfg = cfg.embedding_config()
    return {
        "tables": eb.init_tables(ks[0], ecfg),          # (T, R, D)
        "bottom": _mlp_init(
            ks[1], (cfg.num_dense_features,) + cfg.bottom_mlp, dtype),
        "top": _mlp_init(ks[2], (cfg.interaction_dim,) + cfg.top_mlp, dtype),
    }


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
# Forward
# ---------------------------------------------------------------------------

def forward(params, dense: jax.Array, batch: JaggedBatch, cfg: DLRMConfig,
            ctx: Optional[ParallelContext] = None) -> jax.Array:
    """dense (B, num_dense), batch: sparse lookups -> CTR logit (B,).

    With a ``ctx``, the embedding pooling runs the paper's distributed
    pipeline inside shard_map (tables sharded per cfg.sharding over the tp
    axis, batch replicated over tp / sharded over dp).
    """
    ecfg = cfg.embedding_config()
    if ctx is None:
        pooled = eb.pooled_lookup_local(params["tables"], batch, ecfg)
    else:
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
            in_specs=(eb.table_pspec(ecfg, ctx.tp_axis), bspec),
            out_specs=P(dp, None, None),
            check_vma=False,
        )(params["tables"], batch)

    bot = _mlp_apply(params["bottom"], dense, final_act=True)   # (B, D)
    feats = dot_interaction(bot, pooled.astype(bot.dtype))
    logit = _mlp_apply(params["top"], feats)                    # (B, 1)
    return logit[:, 0]


def bce_loss(params, dense, batch: JaggedBatch, labels, cfg: DLRMConfig,
             ctx=None) -> jax.Array:
    logit = forward(params, dense, batch, cfg, ctx)
    z = jax.nn.log_sigmoid(logit)
    zn = jax.nn.log_sigmoid(-logit)
    return -jnp.mean(labels * z + (1.0 - labels) * zn)
