"""Unified LM zoo: one init/forward/decode covering all 10 assigned archs.

Families (cfg.family):
  dense   — llama-style GQA stacks (starcoder2, yi, granite, nemotron)
  moe     — GQA/MLA + expert-parallel MoE FFN (moonshot, deepseek-v3)
  hybrid  — parallel attention+mamba heads (hymba)
  ssm     — rwkv6 (attention-free)
  audio   — whisper enc-dec (frame-embedding frontend stub)
  vlm     — internvl2 (patch-embedding frontend stub + llama backbone)

Design rules:
  * per-layer params are STACKED (leading num_layers axis) and consumed by
    ``jax.lax.scan`` — the compiled HLO contains one layer body regardless
    of depth, which keeps the 512-device dry-run compile tractable.
  * the token embedding is the paper's row-wise-sharded embedding bag
    (core/embedding_bag inside shard_map) whenever a ParallelContext is
    given — the single-hot (L=1) degenerate case of the DLRM pipeline.
  * decode uses a sequence-sharded KV cache with a flash-decode combine
    over the tp axis (GQA and MLA both return (o, m, l) partials).
  * everything else is GSPMD: params carry PartitionSpecs (see
    ``param_specs``), activations get sharding constraints between blocks.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.embedding_bag import EmbeddingBagConfig, pooled_lookup_sharded
from repro.core.jagged import JaggedBatch
from repro.core.parallel import ParallelContext
from repro.models import layers, mla, moe as moe_mod, ssm


# ===========================================================================
# Vocab padding (row-wise sharding needs rows % tp == 0)
# ===========================================================================

def padded_vocab(cfg: ModelConfig, tp_size: int) -> int:
    V = cfg.vocab_size
    return -(-V // tp_size) * tp_size


def embedding_bag_config(cfg: ModelConfig, tp_size: int) -> EmbeddingBagConfig:
    return EmbeddingBagConfig(
        num_tables=1,
        rows_per_table=padded_vocab(cfg, tp_size),
        dim=cfg.d_model,
        sharding=cfg.vocab_sharding,
        rw_impl=cfg.vocab_rw_impl,
        dtype=cfg.dtype,
        kernel_mode="reference",     # pallas kernel switched in on real TPU
    )


# ===========================================================================
# Init
# ===========================================================================

def _stack(rng, n, shape, fan_in, dtype):
    return (jax.random.truncated_normal(rng, -2.0, 2.0, (n,) + shape)
            * fan_in ** -0.5).astype(dtype)


def _init_norm(n, d, cfg, dtype):
    p = {"w": jnp.ones((n, d), dtype)}
    if cfg.norm == "layernorm":
        p["b"] = jnp.zeros((n, d), dtype)
    return p


def _init_gqa(rng, n, cfg: ModelConfig, dtype):
    d, H, KH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ks = jax.random.split(rng, 4)
    return {
        "wq": _stack(ks[0], n, (d, H * hd), d, dtype),
        "wk": _stack(ks[1], n, (d, KH * hd), d, dtype),
        "wv": _stack(ks[2], n, (d, KH * hd), d, dtype),
        "wo": _stack(ks[3], n, (H * hd, d), H * hd, dtype),
    }


def _init_block_stack(rng, n, cfg: ModelConfig, dtype, *, with_moe=False,
                      with_cross=False, with_mamba=False, d_ff=None):
    """One scanned stack: norms + attention (or rwkv) + ffn/moe."""
    ks = jax.random.split(rng, 8)
    p: Dict[str, Any] = {
        "ln1": _init_norm(n, cfg.d_model, cfg, dtype),
        "ln2": _init_norm(n, cfg.d_model, cfg, dtype),
    }
    if cfg.attention == "mla":
        p["attn"] = mla.init_mla_params(ks[0], n, cfg, dtype)
    elif cfg.attention != "none":
        p["attn"] = _init_gqa(ks[0], n, cfg, dtype)
    if with_mamba:
        p["mamba"] = ssm.init_mamba_params(ks[1], n, cfg, dtype)
        p["ln_attn_out"] = _init_norm(n, cfg.d_model, cfg, dtype)
        p["ln_mamba_out"] = _init_norm(n, cfg.d_model, cfg, dtype)
    if with_cross:
        p["cross"] = _init_gqa(ks[2], n, cfg, dtype)
        p["ln_cross"] = _init_norm(n, cfg.d_model, cfg, dtype)
    if with_moe:
        p["moe"] = moe_mod.init_moe_params(ks[3], n, cfg, dtype)
    else:
        p["ffn"] = layers.init_ffn(ks[3], n, cfg.d_model, d_ff or cfg.d_ff,
                                   gated=cfg.gated_ffn, dtype=dtype)
    return p


def init_params(rng, cfg: ModelConfig, *, tp_size: int = 1,
                dtype=None) -> Dict[str, Any]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    Vp = padded_vocab(cfg, tp_size)
    d = cfg.d_model
    ks = jax.random.split(rng, 10)
    params: Dict[str, Any] = {
        # (T=1, Vp, d): the stacked-table layout of core/embedding_bag
        "embed": (jax.random.normal(ks[0], (1, Vp, d)) * d ** -0.5
                  ).astype(dtype),
        "final_norm": _init_norm(1, d, cfg, dtype),
    }
    if not cfg.tie_embeddings:
        params["head"] = _stack(ks[1], 1, (d, Vp), d, dtype)[0]

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["blocks"] = _init_block_stack(ks[2], cfg.num_layers, cfg, dtype)
    elif fam == "moe":
        nk = cfg.first_k_dense
        if nk:
            params["dense_blocks"] = _init_block_stack(ks[2], nk, cfg, dtype)
        params["moe_blocks"] = _init_block_stack(
            ks[3], cfg.num_layers - nk, cfg, dtype, with_moe=True)
    elif fam == "hybrid":
        params["blocks"] = _init_block_stack(ks[2], cfg.num_layers, cfg, dtype,
                                             with_mamba=True)
    elif fam == "ssm":
        params["blocks"] = {
            "ln1": _init_norm(cfg.num_layers, d, cfg, dtype),
            "ln2": _init_norm(cfg.num_layers, d, cfg, dtype),
            "rwkv": ssm.init_rwkv_params(ks[2], cfg.num_layers, cfg, dtype),
        }
    elif fam == "audio":
        params["enc_blocks"] = _init_block_stack(
            ks[2], cfg.encoder_layers, cfg, dtype)
        params["enc_pos"] = (jax.random.normal(
            ks[4], (cfg.encoder_seq_len, d)) * 0.01).astype(dtype)
        params["enc_norm"] = _init_norm(1, d, cfg, dtype)
        params["blocks"] = _init_block_stack(
            ks[3], cfg.num_layers, cfg, dtype, with_cross=True)
    else:
        raise ValueError(f"unknown family {fam!r}")

    if fam == "vlm":
        params["projector"] = {
            "ln_w": jnp.ones((cfg.vision_dim,), dtype),
            "ln_b": jnp.zeros((cfg.vision_dim,), dtype),
            "fc1": _stack(ks[5], 1, (cfg.vision_dim, d), cfg.vision_dim, dtype)[0],
            "fc2": _stack(ks[6], 1, (d, d), d, dtype)[0],
        }
    if cfg.mtp_depth:
        params["mtp"] = {
            "proj": _stack(ks[7], 1, (2 * d, d), 2 * d, dtype)[0],
            "norm_h": _init_norm(1, d, cfg, dtype),
            "norm_e": _init_norm(1, d, cfg, dtype),
            "block": _init_block_stack(ks[8], 1, cfg, dtype),
        }
    return params


# ===========================================================================
# Norms / attention blocks
# ===========================================================================

def _norm(h, p, cfg: ModelConfig):
    if cfg.norm == "layernorm":
        return layers.layer_norm(h, p["w"], p["b"], cfg.norm_eps)
    return layers.rms_norm(h, p["w"], cfg.norm_eps)


def _gqa_qkv(p, h, positions, cfg: ModelConfig, *, rope=True):
    B, S, _ = h.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (h @ p["wq"]).reshape(B, S, H, hd)
    k = (h @ p["wk"]).reshape(B, S, KH, hd)
    v = (h @ p["wv"]).reshape(B, S, KH, hd)
    if rope:
        q = layers.apply_rope(q, positions, theta=cfg.rope_theta)
        k = layers.apply_rope(k, positions, theta=cfg.rope_theta)
    return q, k, v


def gqa_attention(p, h, positions, cfg: ModelConfig, *, causal=True,
                  window=None, rope=True):
    """Full-sequence GQA. Returns (out (B,S,d), (k, v) cache entries)."""
    B, S, _ = h.shape
    q, k, v = _gqa_qkv(p, h, positions, cfg, rope=rope)
    o = layers.attention(q, k, v, causal=causal, window=window,
                         chunk_threshold=cfg.attn_chunk_threshold)
    return o.reshape(B, S, -1) @ p["wo"], (k, v)


def cross_attention(p, h, kv_feats, cfg: ModelConfig):
    """Decoder->encoder cross attention (whisper). No rope, no mask."""
    B, S, _ = h.shape
    H, KH, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = (h @ p["wq"]).reshape(B, S, H, hd)
    k = (kv_feats @ p["wk"]).reshape(B, kv_feats.shape[1], KH, hd)
    v = (kv_feats @ p["wv"]).reshape(B, kv_feats.shape[1], KH, hd)
    o = layers.full_attention(q, k, v, causal=False)
    return o.reshape(B, S, -1) @ p["wo"]


# ===========================================================================
# Embedding (the paper's technique, first-class)
# ===========================================================================

def embed_tokens(params, tokens: jax.Array, cfg: ModelConfig,
                 ctx: Optional[ParallelContext]) -> jax.Array:
    """tokens (B, S) int32 -> (B, S, d) via the RW-sharded embedding bag."""
    B, S = tokens.shape
    table = params["embed"]                               # (1, Vp, d)
    if ctx is None or cfg.vocab_sharding == "replicated":
        return table[0][tokens]
    eb_cfg = embedding_bag_config(cfg, ctx.tp_size)
    flat = tokens.reshape(-1)
    N = flat.shape[0]
    dp = ctx.dp_for(N)

    def inner(table_shard, idx_flat):
        batch = JaggedBatch(
            indices=idx_flat.reshape(1, -1, 1),
            lengths=jnp.ones((1, idx_flat.shape[0]), jnp.int32),
        )
        out = pooled_lookup_sharded(table_shard, batch, eb_cfg,
                                    model_axis=ctx.tp_axis)   # (N, 1, d)
        return out[:, 0, :]

    out = shard_map(
        inner,
        mesh=ctx.mesh,
        in_specs=(P(None, ctx.tp_axis, None), P(dp)),
        out_specs=P(dp, None),
        check_vma=False,
    )(table, flat)
    return out.reshape(B, S, cfg.d_model)


def lm_logits(params, hidden: jax.Array, cfg: ModelConfig,
              ctx: Optional[ParallelContext]) -> jax.Array:
    """hidden (..., d) -> logits (..., Vp), vocab-sharded under GSPMD."""
    head = params["embed"][0].T if cfg.tie_embeddings else params["head"]
    logits = hidden @ head
    if ctx is not None:
        spec = (P(ctx.dp_for(hidden.shape[0]), None, ctx.tp_axis)
                if logits.ndim == 3 else P(None, ctx.tp_axis))
        logits = ctx.constrain(logits, spec)
    return logits


# ===========================================================================
# Full-sequence forward (train / prefill)
# ===========================================================================

def _moe_apply(p_moe, h, cfg: ModelConfig, ctx: Optional[ParallelContext]):
    """h (B,S,d) -> (out, aux). EP over tp axis when ctx given."""
    B, S, d = h.shape
    if ctx is None:
        out, aux = moe_mod.moe_ffn(p_moe, h.reshape(-1, d), cfg)
        return out.reshape(B, S, d), aux
    tp = ctx.tp_axis
    seq_shardable = S % ctx.tp_size == 0
    dp = ctx.dp_for(B)

    def inner(pm, hblk):
        b, s, _ = hblk.shape
        out, aux = moe_mod.moe_ffn_ep(pm, hblk.reshape(-1, d), cfg, tp)
        return out.reshape(b, s, d), aux

    espec = lambda a: P(tp, *([None] * (a.ndim - 1)))
    pspec = jax.tree.map(espec, p_moe)
    # router stays replicated (every rank routes its own tokens)
    pspec["router"] = P(None, None)
    if "shared" in p_moe:
        pspec["shared"] = jax.tree.map(lambda a: P(*([None] * a.ndim)),
                                       p_moe["shared"])
    hspec = P(dp, tp if seq_shardable else None, None)
    out, aux = shard_map(
        inner, mesh=ctx.mesh,
        in_specs=(pspec, hspec),
        out_specs=(hspec, P()),
        check_vma=False,
    )(p_moe, h)
    # named for the remat policy: saving the EP output keeps the backward
    # from REPLAYING the dispatch/combine all-to-alls and the expert
    # matmuls (§Perf hc3). Costs one seq-sharded (B, S, d) residual/layer.
    from jax.ad_checkpoint import checkpoint_name
    out = checkpoint_name(out, "moe_out")
    return out, aux


_SP_FAMILIES = ("dense", "vlm", "moe", "audio")


def _carry_constraint(h, cfg, ctx):
    """Between-block activation sharding (scan-carry spec).

    The carry is sharded over the tp axis along S (Megatron-SP): saved
    activations (the remat residuals) shrink by tp_size, at the cost of an
    all-gather before attention/FFN and a reduce-scatter after (GSPMD
    inserts them). Recurrent families scan
    over time/chunks inside the block, where a seq-sharded carry would
    force per-step resharding — they stay batch-sharded only.
    """
    if ctx is None:
        return h
    B, S, _ = h.shape
    if cfg.family in _SP_FAMILIES and S % ctx.tp_size == 0:
        return ctx.constrain(h, P(ctx.dp_for(B), ctx.tp_axis, None))
    return ctx.constrain(h, P(ctx.dp_for(B), None, None))


def _dense_block(pl, h, positions, cfg, ctx, *, window=None, causal=True,
                 cross_feats=None):
    x = _norm(h, pl["ln1"], cfg)
    if cfg.attention == "mla":
        attn_out, _ = mla.mla_attention(pl["attn"], x, positions, cfg,
                                        causal=causal)
    else:
        attn_out, _ = gqa_attention(pl["attn"], x, positions, cfg,
                                    causal=causal, window=window,
                                    rope=cfg.family != "audio")
    h = h + attn_out
    if cross_feats is not None:
        h = h + cross_attention(pl["cross"], _norm(h, pl["ln_cross"], cfg),
                                cross_feats, cfg)
    h = h + layers.apply_ffn(pl["ffn"], _norm(h, pl["ln2"], cfg),
                             cfg.activation)
    return _carry_constraint(h, cfg, ctx)


def _hybrid_block(pl, h, positions, cfg, ctx, *, window):
    """Hymba: attention and mamba heads in parallel, normed mean fusion."""
    x = _norm(h, pl["ln1"], cfg)
    attn_out, _ = gqa_attention(pl["attn"], x, positions, cfg,
                                causal=True, window=window)
    mamba_out, _ = ssm.mamba_forward(pl["mamba"], x, cfg)
    fused = 0.5 * (_norm(attn_out, pl["ln_attn_out"], cfg) +
                   _norm(mamba_out, pl["ln_mamba_out"], cfg))
    h = h + fused
    h = h + layers.apply_ffn(pl["ffn"], _norm(h, pl["ln2"], cfg),
                             cfg.activation)
    return _carry_constraint(h, cfg, ctx)


def _scan_stack(stack, h, body, *, remat=False, extra_xs=None):
    """Scan ``body(h, layer_params, extra) -> (h, aux)`` over stacked params."""
    def f(carry, xs):
        return body(carry, xs)
    if remat:
        # full remat except named saveables ("moe_out"): dense layers
        # recompute everything; MoE layers keep their EP output so the
        # backward never replays the a2a round trips or expert matmuls
        f = jax.checkpoint(
            f, prevent_cse=False,
            policy=jax.checkpoint_policies.save_only_these_names("moe_out"))
    xs = (stack, extra_xs) if extra_xs is not None else (stack, None)
    h, auxs = jax.lax.scan(lambda c, x: f(c, x), h, xs)
    return h, auxs


def _hymba_windows(cfg: ModelConfig) -> jax.Array:
    """Per-layer effective window (big number = global attention)."""
    w = jnp.full((cfg.num_layers,), cfg.window or 1 << 30, jnp.int32)
    for i in cfg.global_attn_layers:
        w = w.at[i].set(1 << 30)
    return w


def forward(params, tokens: jax.Array, cfg: ModelConfig,
            ctx: Optional[ParallelContext] = None, *,
            frames: Optional[jax.Array] = None,
            patches: Optional[jax.Array] = None,
            remat: bool = False) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """tokens (B, S[, +prefix]) -> hidden (B, S_total, d), aux metrics.

    audio: ``frames`` (B, enc_S, d) precomputed frame embeddings (stub).
    vlm:   ``patches`` (B, vision_tokens, vision_dim) patch embeddings
           (stub), projected and prepended; text positions follow.
    """
    B, S = tokens.shape
    aux: Dict[str, jax.Array] = {}
    h = embed_tokens(params, tokens, cfg, ctx)

    if cfg.family == "vlm":
        pj = params["projector"]
        x = patches.astype(h.dtype)
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + 1e-6) * pj["ln_w"] + pj["ln_b"]
        x = jax.nn.gelu(x @ pj["fc1"]) @ pj["fc2"]
        h = jnp.concatenate([x, h], axis=1)
        S = h.shape[1]

    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    if ctx is not None:
        sp = (P(ctx.dp_for(B), ctx.tp_axis, None)
              if S % ctx.tp_size == 0
              else P(ctx.dp_for(B), None, None))
        h = ctx.constrain(h, sp)

    fam = cfg.family
    if fam in ("dense", "vlm"):
        h, _ = _scan_stack(
            params["blocks"], h,
            lambda c, xs: (_dense_block(xs[0], c, positions, cfg, ctx,
                                        window=cfg.window), None),
            remat=remat)
    elif fam == "moe":
        if cfg.first_k_dense:
            h, _ = _scan_stack(
                params["dense_blocks"], h,
                lambda c, xs: (_dense_block(xs[0], c, positions, cfg, ctx),
                               None),
                remat=remat)

        def moe_body(c, xs):
            pl = xs[0]
            x = _norm(c, pl["ln1"], cfg)
            if cfg.attention == "mla":
                attn_out, _ = mla.mla_attention(pl["attn"], x, positions, cfg)
            else:
                attn_out, _ = gqa_attention(pl["attn"], x, positions, cfg)
            c = c + attn_out
            mo, a = _moe_apply(pl["moe"], _norm(c, pl["ln2"], cfg), cfg, ctx)
            return _carry_constraint(c + mo, cfg, ctx), a

        h, moe_aux = _scan_stack(params["moe_blocks"], h, moe_body,
                                 remat=remat)
        aux["moe_aux"] = jnp.mean(moe_aux["moe_aux"])
        aux["moe_dropped"] = jnp.sum(moe_aux["moe_dropped"])
    elif fam == "hybrid":
        wins = _hymba_windows(cfg)
        h, _ = _scan_stack(
            params["blocks"], h,
            lambda c, xs: (_hybrid_block(xs[0], c, positions, cfg, ctx,
                                         window=xs[1]), None),
            remat=remat, extra_xs=wins)
    elif fam == "ssm":
        def rwkv_body(c, xs):
            pl = xs[0]
            if cfg.rwkv_chunk:
                tm, _ = ssm.rwkv_time_mix_chunked(
                    pl["rwkv"], _norm(c, pl["ln1"], cfg), cfg,
                    chunk=cfg.rwkv_chunk)
            else:
                tm, _ = ssm.rwkv_time_mix(
                    pl["rwkv"], _norm(c, pl["ln1"], cfg), cfg)
            c = c + tm
            cm, _ = ssm.rwkv_channel_mix(pl["rwkv"],
                                         _norm(c, pl["ln2"], cfg), cfg)
            return _carry_constraint(c + cm, cfg, ctx), None
        h, _ = _scan_stack(params["blocks"], h, rwkv_body, remat=remat)
    elif fam == "audio":
        enc = frames.astype(h.dtype) + params["enc_pos"][None, : frames.shape[1]]
        enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1]),
                                   (B, enc.shape[1]))
        enc, _ = _scan_stack(
            params["enc_blocks"], enc,
            lambda c, xs: (_dense_block(xs[0], c, enc_pos, cfg, ctx,
                                        causal=False), None),
            remat=remat)
        enc = _norm(enc, jax.tree.map(lambda a: a[0], params["enc_norm"]), cfg)
        aux["encoder_out"] = enc
        h, _ = _scan_stack(
            params["blocks"], h,
            lambda c, xs: (_dense_block(xs[0], c, positions, cfg, ctx,
                                        cross_feats=enc), None),
            remat=remat)
    else:
        raise ValueError(fam)

    h = _norm(h, jax.tree.map(lambda a: a[0], params["final_norm"]), cfg)
    return h, aux
