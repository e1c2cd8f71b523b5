"""PartitionSpecs for every pytree the launchers move: params, optimizer
state, KV caches, and input batches — plus ShapeDtypeStruct builders
(``input_specs``) for the dry-run.

Sharding policy:
  * embed table (1, Vp, d)      -> P(None, tp, None)    — the paper's RW
  * lm head (d, Vp)             -> P(dp?, tp)           — vocab-parallel
  * column-parallel weights (wq/wk/wv/gate/up/in_proj/ck/w_r...) —
    last dim tp, second-to-last dp (FSDP/ZeRO-3) when divisible
  * row-parallel weights (wo/down/out_proj/cv/w_o) — dim -2 tp, last dp
  * MoE experts (n, E, d, f)    -> P(None, tp, dp?, None) — EP on tp
  * norms/scalars               -> replicated
  * optimizer moments           -> parameter spec (int8 blocks append
    trailing Nones — optim/quant.py keeps blocks on the last axis)
  * activations (B, S, d)       -> P(dp, tp, None) between blocks when tp
    divides S, else P(dp, None, None)
  * KV caches                   -> batch over dp, KV seq over tp
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig, TrainConfig
from repro.core.parallel import ParallelContext
from repro.models import decode as dec
from repro.models import lm
from repro.optim.quant import QuantizedTensor
from repro.train.step import init_train_state


_ROW_PARALLEL = {"wo", "down", "out_proj", "cv", "w_o", "w_uq", "dt_proj",
                 "w_B", "fc2"}
_REPLICATED = {"router", "mu", "mu_c", "w0", "u", "ln_w", "ln_b", "conv_w",
               "conv_b", "A_log", "D", "dt_bias", "q_norm", "kv_norm",
               "enc_pos", "w_A", "scale"}


def _path_names(path) -> Tuple[str, ...]:
    return tuple(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def param_spec_tree(params, cfg: ModelConfig, ctx: ParallelContext):
    """PartitionSpec pytree matching ``params`` (see module docstring)."""
    tp = ctx.tp_axis

    # Head-aligned TP gating: sharding a flattened (H*hd) projection dim
    # when H (or KH) does not divide tp splits WITHIN heads; GSPMD then
    # shards the hd contraction inside attention and inserts an all-reduce
    # per score matmul — observed as a 4 MiB all-reduce x 6144 trips on
    # whisper prefill_32k (48 GiB/device). Sub-head-parallel projections
    # are replicated instead (cheap: only small-H models are affected).
    # Only small-d models take the replication route: for them attention
    # params/compute are cheap and the q-SEQUENCE dim carries the
    # parallelism (SP carry + vmapped q-blocks in chunked attention). For
    # big models (yi: 56 heads, d=7168) sub-head sharding measured fine —
    # GSPMD re-shards to head boundaries once per layer.
    small_d = cfg.d_model <= 2048
    q_heads_ok = cfg.num_heads % ctx.tp_size == 0 or not small_d
    kv_heads_ok = cfg.num_kv_heads % ctx.tp_size == 0 or not small_d

    def rule(path, leaf):
        names = _path_names(path)
        name = names[-1]
        shape = leaf.shape
        nd = leaf.ndim
        in_moe = "moe" in names
        if name == "embed":
            return P(None, ctx.tp_for(shape[1]), None)
        if name == "head":
            return P(ctx.dp_for(shape[0]), ctx.tp_for(shape[1]))
        if name in _REPLICATED or nd <= 1 or "projector" in names and name == "fc1":
            return P(*([None] * nd))
        if in_moe and name in ("gate", "up", "down") and nd == 4:
            # (n, E, d, f): EP over tp; FSDP over the larger inner dim
            return P(None, ctx.tp_for(shape[1]), ctx.dp_for(shape[2]), None)
        if name in ("w", "b"):                       # norms inside stacks
            return P(*([None] * nd))
        if name == "wq" and not q_heads_ok:
            return P(*([None] * (nd - 2)), ctx.dp_for(shape[-2]), None)
        if name in ("wk", "wv") and not kv_heads_ok:
            return P(*([None] * (nd - 2)), ctx.dp_for(shape[-2]), None)
        if name == "wo" and not q_heads_ok:
            return P(*([None] * (nd - 2)), None, ctx.dp_for(shape[-1]))
        if name in _ROW_PARALLEL and nd >= 2:
            spec = [None] * nd
            spec[-2] = ctx.tp_for(shape[-2])
            spec[-1] = ctx.dp_for(shape[-1]) if shape[-1] >= 1024 else None
            return P(*spec)
        if nd >= 2:                                  # column-parallel default
            spec = [None] * nd
            spec[-1] = ctx.tp_for(shape[-1])
            if shape[-2] >= 1024:
                spec[-2] = ctx.dp_for(shape[-2])
            return P(*spec)
        return P(*([None] * nd))

    return jax.tree_util.tree_map_with_path(rule, params)


def opt_spec_tree(param_specs, opt_template):
    """Moments inherit param specs; quantized leaves append trailing Nones."""
    def moment_spec(spec, leaf):
        if isinstance(leaf, QuantizedTensor):
            base = list(spec) + [None] * 10
            qdim = leaf.q.ndim
            return QuantizedTensor(
                q=P(*base[: qdim - 2], None, None),
                scale=P(*base[: qdim - 2], None),
                shape=leaf.shape,
                mode=leaf.mode,     # aux data must match the state tree
            )
        return spec

    def build(tmpl_moments):
        return jax.tree.map(moment_spec, param_specs, tmpl_moments,
                            is_leaf=lambda x: isinstance(x, QuantizedTensor))

    return {
        "m": build(opt_template["m"]),
        "v": build(opt_template["v"]),
        "step": P(),
    }


def state_spec_tree(cfg: ModelConfig, tc: TrainConfig, ctx: ParallelContext):
    """(template ShapeDtypeStructs, spec tree) for the full train state."""
    template = jax.eval_shape(
        lambda: init_train_state(jax.random.key(tc.seed), cfg, tc,
                                 tp_size=ctx.tp_size))
    pspecs = param_spec_tree(template["params"], cfg, ctx)
    specs = {"params": pspecs,
             "opt": opt_spec_tree(pspecs, template["opt"])}
    return template, specs


def cache_spec_tree(cache_template, cfg: ModelConfig, ctx: ParallelContext,
                    batch: int):
    """Specs for a decode cache built by models/decode.init_cache."""
    builder = dec.cache_specs(cfg, ctx)
    return builder(batch)


# ===========================================================================
# input_specs — ShapeDtypeStruct stand-ins for every dry-run cell
# ===========================================================================

def batch_structs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Training/prefill batch as ShapeDtypeStructs (no allocation)."""
    B, S = shape.global_batch, shape.seq_len
    sd = jax.ShapeDtypeStruct
    if shape.kind == "train":
        out = {"tokens": sd((B, S), jnp.int32),
               "labels": sd((B, S), jnp.int32)}
        if cfg.family == "audio":
            out["frames"] = sd((B, cfg.encoder_seq_len, cfg.d_model),
                               jnp.float32)
        if cfg.family == "vlm":
            out["patches"] = sd((B, cfg.vision_tokens, cfg.vision_dim),
                                jnp.float32)
        return out
    if shape.kind == "prefill":
        out = {"tokens": sd((B, S), jnp.int32)}
        if cfg.family == "audio":
            out["frames"] = sd((B, cfg.encoder_seq_len, cfg.d_model),
                               jnp.float32)
        if cfg.family == "vlm":
            out["patches"] = sd((B, cfg.vision_tokens, cfg.vision_dim),
                                jnp.float32)
        return out
    # decode: one new token against a seq_len KV cache
    return {"tokens": sd((B,), jnp.int32)}


def batch_specs(cfg: ModelConfig, shape: ShapeConfig, ctx: ParallelContext):
    B = shape.global_batch
    dp = ctx.dp_for(B)
    if shape.kind == "train":
        out = {"tokens": P(dp, None), "labels": P(dp, None)}
        if cfg.family == "audio":
            out["frames"] = P(dp, None, None)
        if cfg.family == "vlm":
            out["patches"] = P(dp, None, None)
        return out
    if shape.kind == "prefill":
        out = {"tokens": P(dp, None)}
        if cfg.family == "audio":
            out["frames"] = P(dp, None, None)
        if cfg.family == "vlm":
            out["patches"] = P(dp, None, None)
        return out
    return {"tokens": P(dp)}
