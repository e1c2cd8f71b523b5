"""KV-cache decode: init_cache / prefill / decode_step for every family.

Cache layout: per-layer tensors STACKED on a leading num_layers axis and
scanned, like the forward pass. The KV sequence dim is sharded over the tp
axis and attention runs as a flash-decode: each shard computes a partial
(o, m, l) over its cache slice and the triple combines with a pmax/psum
over the axis (layers.combine_decode_partials) — this is what makes a
32k-context, 128-batch decode fit 16 GB/chip without replicating the cache.

Per-sample ``length`` (B,) supports continuous batching (slots at
different positions); cache writes are batched scatters.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.core.parallel import ParallelContext
from repro.models import layers, mla, ssm
from repro.models.lm import (
    _dense_block,
    _gqa_qkv,
    _hymba_windows,
    _moe_apply,
    _norm,
    cross_attention,
    embed_tokens,
    gqa_attention,
)


# ===========================================================================
# Cache init
# ===========================================================================

def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=None) -> Dict[str, Any]:
    dtype = dtype or jnp.dtype(cfg.dtype)
    B, S = batch, max_len
    d, KH, hd = cfg.d_model, cfg.num_kv_heads, cfg.head_dim
    cache: Dict[str, Any] = {"length": jnp.zeros((B,), jnp.int32)}
    fam = cfg.family

    def kv(n):
        return {"k": jnp.zeros((n, B, S, KH, hd), dtype),
                "v": jnp.zeros((n, B, S, KH, hd), dtype)}

    if fam in ("dense", "vlm"):
        cache["blocks"] = kv(cfg.num_layers)
    elif fam == "moe":
        if cfg.attention == "mla":
            m = cfg.mla
            def latent(n):
                return {"c_kv": jnp.zeros((n, B, S, m.kv_lora_rank), dtype),
                        "k_rope": jnp.zeros((n, B, S, m.qk_rope_head_dim),
                                            dtype)}
            if cfg.first_k_dense:
                cache["dense_blocks"] = latent(cfg.first_k_dense)
            cache["moe_blocks"] = latent(cfg.num_layers - cfg.first_k_dense)
        else:
            if cfg.first_k_dense:
                cache["dense_blocks"] = kv(cfg.first_k_dense)
            cache["moe_blocks"] = kv(cfg.num_layers - cfg.first_k_dense)
    elif fam == "hybrid":
        n = cfg.num_layers
        di = cfg.ssm_expand * d
        cache["blocks"] = kv(n)
        cache["blocks"]["ssm_h"] = jnp.zeros((n, B, di, cfg.ssm_state),
                                             jnp.float32)
        cache["blocks"]["conv"] = jnp.zeros((n, B, cfg.ssm_conv - 1, di),
                                            dtype)
    elif fam == "ssm":
        n = cfg.num_layers
        H = d // cfg.rwkv_head_size
        hs = cfg.rwkv_head_size
        cache["blocks"] = {
            "S": jnp.zeros((n, B, H, hs, hs), jnp.float32),
            "x_tm": jnp.zeros((n, B, d), dtype),
            "x_cm": jnp.zeros((n, B, d), dtype),
        }
    elif fam == "audio":
        cache["blocks"] = kv(cfg.num_layers)
        cache["enc"] = jnp.zeros((B, cfg.encoder_seq_len, d), dtype)
    return cache


def cache_specs(cfg: ModelConfig, ctx: ParallelContext) -> Dict[str, Any]:
    """PartitionSpecs mirroring init_cache: batch over dp, KV seq over tp."""
    tp = ctx.tp_axis

    def spec_of(path_leaf_shape):
        return None  # placeholder; tree built below

    def kv_spec(dpb):
        return {"k": P(None, dpb, tp, None, None),
                "v": P(None, dpb, tp, None, None)}

    def build(batch: int):
        dpb = ctx.dp_for(batch)
        specs: Dict[str, Any] = {"length": P(dpb)}
        fam = cfg.family
        if fam in ("dense", "vlm", "audio"):
            specs["blocks"] = kv_spec(dpb)
        elif fam == "moe":
            if cfg.attention == "mla":
                ls = {"c_kv": P(None, dpb, tp, None),
                      "k_rope": P(None, dpb, tp, None)}
                if cfg.first_k_dense:
                    specs["dense_blocks"] = dict(ls)
                specs["moe_blocks"] = dict(ls)
            else:
                if cfg.first_k_dense:
                    specs["dense_blocks"] = kv_spec(dpb)
                specs["moe_blocks"] = kv_spec(dpb)
        elif fam == "hybrid":
            specs["blocks"] = kv_spec(dpb)
            specs["blocks"]["ssm_h"] = P(None, dpb, tp, None)
            specs["blocks"]["conv"] = P(None, dpb, None, None)
        elif fam == "ssm":
            specs["blocks"] = {"S": P(None, dpb, None, None, None),
                               "x_tm": P(None, dpb, None),
                               "x_cm": P(None, dpb, None)}
        if fam == "audio":
            specs["enc"] = P(dpb, None, None)
        return specs

    return build


# ===========================================================================
# Sharded flash-decode attention
# ===========================================================================

def _decode_attn(q, k_cache, v_cache, length, cfg: ModelConfig,
                 ctx: Optional[ParallelContext], *, window=None):
    """q (B,1,H,hd), caches (B,S,KH,hd). Returns (B,1,H*hd)."""
    B = q.shape[0]
    if ctx is None:
        o, m, l = layers.decode_attention_partial(
            q, k_cache, v_cache, length[:, None], window=window)
        out = layers.combine_decode_partials(o, m, l)
        return out.reshape(B, 1, -1).astype(q.dtype)

    tp = ctx.tp_axis
    dpb = ctx.dp_for(B)
    Sc = k_cache.shape[1] // ctx.tp_size

    def inner(q_, k_, v_, len_):
        rank = jax.lax.axis_index(tp)
        o, m, l = layers.decode_attention_partial(
            q_, k_, v_, len_[:, None], window=window, kv_offset=rank * Sc)
        return layers.combine_decode_partials(o, m, l, tp)

    out = shard_map(
        inner, mesh=ctx.mesh,
        in_specs=(P(dpb, None, None, None), P(dpb, tp, None, None),
                  P(dpb, tp, None, None), P(dpb)),
        out_specs=P(dpb, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, length)
    return out.reshape(B, 1, -1).astype(q.dtype)


def _mla_decode_attn(pl, x, c_kv, k_rope, length, cfg: ModelConfig,
                     ctx: Optional[ParallelContext]):
    B = x.shape[0]
    if ctx is None:
        ctx_l, m, l = mla.mla_decode_partial(pl, x, cfg, c_kv, k_rope,
                                             length[:, None])
        combined = layers.combine_decode_partials(ctx_l, m, l)
        return mla.mla_decode_output(pl, combined, x.dtype)

    tp = ctx.tp_axis
    dpb = ctx.dp_for(B)
    Sc = c_kv.shape[1] // ctx.tp_size

    def inner(pl_, x_, ck_, kr_, len_):
        rank = jax.lax.axis_index(tp)
        ctx_l, m, l = mla.mla_decode_partial(pl_, x_, cfg, ck_, kr_,
                                             len_[:, None],
                                             kv_offset=rank * Sc)
        return layers.combine_decode_partials(ctx_l, m, l, tp)

    pl_spec = jax.tree.map(lambda a: P(*([None] * a.ndim)), pl)
    combined = shard_map(
        inner, mesh=ctx.mesh,
        in_specs=(pl_spec, P(dpb, None, None), P(dpb, tp, None),
                  P(dpb, tp, None), P(dpb)),
        out_specs=P(dpb, None, None),
        check_vma=False,
    )(pl, x, c_kv, k_rope, length)
    return mla.mla_decode_output(pl, combined, x.dtype)


def _write_kv(cache_k, cache_v, k_new, v_new, length):
    """Scatter one new (B,1,KH,hd) entry at per-sample positions."""
    B = k_new.shape[0]
    bi = jnp.arange(B)
    return (cache_k.at[bi, length].set(k_new[:, 0].astype(cache_k.dtype)),
            cache_v.at[bi, length].set(v_new[:, 0].astype(cache_v.dtype)))


# ===========================================================================
# Per-family single-token blocks
# ===========================================================================

def _gqa_decode_block(pl, h, lc, length, cfg, ctx, *, window=None,
                      cross_feats=None, rope=True):
    """h (B,1,d); lc = this layer's cache slice. Returns (h, new lc)."""
    x = _norm(h, pl["ln1"], cfg)
    positions = length[:, None]
    q, k_new, v_new = _gqa_qkv(pl["attn"], x, positions, cfg, rope=rope)
    ck, cv = _write_kv(lc["k"], lc["v"], k_new, v_new, length)
    attn = _decode_attn(q, ck, cv, length + 1, cfg, ctx, window=window)
    h = h + attn @ pl["attn"]["wo"]
    if cross_feats is not None:
        h = h + cross_attention(pl["cross"], _norm(h, pl["ln_cross"], cfg),
                                cross_feats, cfg)
    new_lc = dict(lc, k=ck, v=cv)
    return h, new_lc


def _ffn_or_moe(pl, h, cfg, ctx):
    if "moe" in pl:
        out, aux = _moe_apply(pl["moe"], _norm(h, pl["ln2"], cfg), cfg, ctx)
        return h + out, aux
    return h + layers.apply_ffn(pl["ffn"], _norm(h, pl["ln2"], cfg),
                                cfg.activation), {}


# ===========================================================================
# decode_step — one new token for the whole batch
# ===========================================================================

def decode_step(params, cache, tokens: jax.Array, cfg: ModelConfig,
                ctx: Optional[ParallelContext] = None
                ) -> Tuple[Dict[str, Any], jax.Array]:
    """tokens (B,) int32 -> (updated cache, hidden (B, d))."""
    B = tokens.shape[0]
    length = cache["length"]
    h = embed_tokens(params, tokens[:, None], cfg, ctx)      # (B,1,d)
    fam = cfg.family

    def scan_blocks(stack, blocks_cache, body):
        def f(carry, xs):
            pl, lc = xs
            return body(carry, pl, lc)
        return jax.lax.scan(f, h, (stack, blocks_cache))

    new_cache = dict(cache)
    if fam in ("dense", "vlm"):
        def body(c, pl, lc):
            c, lc = _gqa_decode_block(pl, c, lc, length, cfg, ctx,
                                      window=cfg.window)
            c, _ = _ffn_or_moe(pl, c, cfg, ctx)
            return c, lc
        h, bc = scan_blocks(params["blocks"], cache["blocks"], body)
        new_cache["blocks"] = bc
    elif fam == "moe":
        if cfg.attention == "mla":
            def mla_body(c, pl, lc):
                x = _norm(c, pl["ln1"], cfg)
                ckv_new, krope_new = mla.latent_kv(pl["attn"], x, cfg,
                                                   length[:, None])
                bi = jnp.arange(B)
                ck = lc["c_kv"].at[bi, length].set(
                    ckv_new[:, 0].astype(lc["c_kv"].dtype))
                kr = lc["k_rope"].at[bi, length].set(
                    krope_new[:, 0].astype(lc["k_rope"].dtype))
                c = c + _mla_decode_attn(pl["attn"], x, ck, kr, length + 1,
                                         cfg, ctx)
                c, _ = _ffn_or_moe(pl, c, cfg, ctx)
                return c, dict(lc, c_kv=ck, k_rope=kr)
            body = mla_body
        else:
            def body(c, pl, lc):
                c, lc = _gqa_decode_block(pl, c, lc, length, cfg, ctx)
                c, _ = _ffn_or_moe(pl, c, cfg, ctx)
                return c, lc
        if cfg.first_k_dense:
            h, dc = scan_blocks(params["dense_blocks"],
                                cache["dense_blocks"], body)
            new_cache["dense_blocks"] = dc
        h, mc = scan_blocks(params["moe_blocks"], cache["moe_blocks"], body)
        new_cache["moe_blocks"] = mc
    elif fam == "hybrid":
        wins = _hymba_windows(cfg)
        def body(c, xs):
            (pl, w), lc = xs[0], xs[1]
            x = _norm(c, pl["ln1"], cfg)
            positions = length[:, None]
            q, k_new, v_new = _gqa_qkv(pl["attn"], x, positions, cfg)
            ck, cv = _write_kv(lc["k"], lc["v"], k_new, v_new, length)
            attn = _decode_attn(q, ck, cv, length + 1, cfg, ctx, window=w)
            attn = attn @ pl["attn"]["wo"]
            m_out, hssm, conv = ssm.mamba_decode_step(
                pl["mamba"], x, cfg, lc["ssm_h"], lc["conv"])
            fused = 0.5 * (_norm(attn, pl["ln_attn_out"], cfg) +
                           _norm(m_out, pl["ln_mamba_out"], cfg))
            c = c + fused
            c = c + layers.apply_ffn(pl["ffn"], _norm(c, pl["ln2"], cfg),
                                     cfg.activation)
            return c, dict(lc, k=ck, v=cv, ssm_h=hssm, conv=conv)
        h, bc = jax.lax.scan(
            lambda c, xs: body(c, xs),
            h, (((params["blocks"], wins)), cache["blocks"]))
        new_cache["blocks"] = bc
    elif fam == "ssm":
        def body(c, xs):
            pl, lc = xs
            tm, (S_new, x_tm) = ssm.rwkv_time_mix(
                pl["rwkv"], _norm(c, pl["ln1"], cfg), cfg,
                state=lc["S"], x_last=lc["x_tm"])
            c = c + tm
            cm, x_cm = ssm.rwkv_channel_mix(
                pl["rwkv"], _norm(c, pl["ln2"], cfg), cfg, x_last=lc["x_cm"])
            c = c + cm
            return c, dict(lc, S=S_new, x_tm=x_tm, x_cm=x_cm)
        h, bc = jax.lax.scan(lambda c, xs: body(c, xs), h,
                             (params["blocks"], cache["blocks"]))
        new_cache["blocks"] = bc
    elif fam == "audio":
        enc = cache["enc"]
        def body(c, pl, lc):
            c, lc = _gqa_decode_block(pl, c, lc, length, cfg, ctx,
                                      cross_feats=enc, rope=False)
            c, _ = _ffn_or_moe(pl, c, cfg, ctx)
            return c, lc
        h, bc = scan_blocks(params["blocks"], cache["blocks"], body)
        new_cache["blocks"] = bc
    else:
        raise ValueError(fam)

    h = _norm(h, jax.tree.map(lambda a: a[0], params["final_norm"]), cfg)
    new_cache["length"] = length + 1
    return new_cache, h[:, 0]


# ===========================================================================
# prefill — run the full prompt, returning a filled cache
# ===========================================================================

def prefill(params, tokens: jax.Array, cfg: ModelConfig,
            ctx: Optional[ParallelContext] = None, *,
            max_len: Optional[int] = None,
            frames: Optional[jax.Array] = None,
            patches: Optional[jax.Array] = None,
            ) -> Tuple[Dict[str, Any], jax.Array]:
    """tokens (B, S) -> (cache at length S, hidden (B, S, d)).

    Mirrors lm.forward but collects per-layer cache entries as scan ys.
    """
    B, S = tokens.shape
    max_len = max_len or S
    pad = max_len - S
    h = embed_tokens(params, tokens, cfg, ctx)
    if cfg.family == "vlm" and patches is not None:
        from repro.models.lm import forward  # single source of truth
        raise NotImplementedError(
            "vlm prefill goes through serve.prefill_vlm (prefix handling)")
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    cache = init_cache(cfg, B, max_len, dtype=h.dtype)
    fam = cfg.family

    def pad_seq(x):                       # (B,S,...) -> (B,max_len,...)
        return jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))

    if fam in ("dense", "vlm", "audio"):
        cross = None
        if fam == "audio":
            enc = frames.astype(h.dtype) + params["enc_pos"][None,
                                                             : frames.shape[1]]
            enc_pos = jnp.broadcast_to(jnp.arange(enc.shape[1]),
                                       (B, enc.shape[1]))
            def ebody(c, xs):
                return _dense_block(xs, c, enc_pos, cfg, ctx,
                                    causal=False), None
            enc, _ = jax.lax.scan(lambda c, xs: ebody(c, xs),
                                  enc, params["enc_blocks"])
            enc = _norm(enc, jax.tree.map(lambda a: a[0],
                                          params["enc_norm"]), cfg)
            cache["enc"] = enc
            cross = enc

        def body(c, pl):
            x = _norm(c, pl["ln1"], cfg)
            rope = fam != "audio"
            q, k, v = _gqa_qkv(pl["attn"], x, positions, cfg, rope=rope)
            o = layers.attention(q, k, v, causal=True, window=cfg.window,
                                 chunk_threshold=cfg.attn_chunk_threshold)
            c = c + o.reshape(B, S, -1) @ pl["attn"]["wo"]
            if cross is not None:
                c = c + cross_attention(pl["cross"],
                                        _norm(c, pl["ln_cross"], cfg),
                                        cross, cfg)
            c = c + layers.apply_ffn(pl["ffn"], _norm(c, pl["ln2"], cfg),
                                     cfg.activation)
            return c, {"k": pad_seq(k), "v": pad_seq(v)}
        h, kv = jax.lax.scan(body, h, params["blocks"])
        cache["blocks"].update(kv)
    elif fam == "moe":
        def body(c, pl):
            x = _norm(c, pl["ln1"], cfg)
            if cfg.attention == "mla":
                o, (c_kv, k_rope) = mla.mla_attention(pl["attn"], x,
                                                      positions, cfg)
                entry = {"c_kv": pad_seq(c_kv), "k_rope": pad_seq(k_rope)}
            else:
                q, k, v = _gqa_qkv(pl["attn"], x, positions, cfg)
                o = layers.attention(q, k, v, causal=True,
                                     chunk_threshold=cfg.attn_chunk_threshold)
                o = o.reshape(B, S, -1) @ pl["attn"]["wo"]
                entry = {"k": pad_seq(k), "v": pad_seq(v)}
            c = c + o
            c, _ = _ffn_or_moe(pl, c, cfg, ctx)
            return c, entry
        if cfg.first_k_dense:
            h, kv = jax.lax.scan(body, h, params["dense_blocks"])
            cache["dense_blocks"].update(kv)
        h, kv = jax.lax.scan(body, h, params["moe_blocks"])
        cache["moe_blocks"].update(kv)
    elif fam == "hybrid":
        wins = _hymba_windows(cfg)
        def body(c, xs):
            pl, w = xs
            x = _norm(c, pl["ln1"], cfg)
            q, k, v = _gqa_qkv(pl["attn"], x, positions, cfg)
            o = layers.attention(q, k, v, causal=True, window=w,
                                 chunk_threshold=cfg.attn_chunk_threshold)
            attn = o.reshape(B, S, -1) @ pl["attn"]["wo"]
            m_out, h_final = ssm.mamba_forward(pl["mamba"], x, cfg)
            # conv state: last (K-1) post-in_proj inputs — recompute slice
            xs_in, _ = jnp.split(x @ pl["mamba"]["in_proj"], 2, axis=-1)
            K = cfg.ssm_conv
            conv_state = xs_in[:, -(K - 1):].swapaxes(1, 1)
            fused = 0.5 * (_norm(attn, pl["ln_attn_out"], cfg) +
                           _norm(m_out, pl["ln_mamba_out"], cfg))
            c = c + fused
            c = c + layers.apply_ffn(pl["ffn"], _norm(c, pl["ln2"], cfg),
                                     cfg.activation)
            return c, {"k": pad_seq(k), "v": pad_seq(v),
                       "ssm_h": h_final, "conv": conv_state}
        h, kv = jax.lax.scan(lambda c, xs: body(c, xs), h,
                             (params["blocks"], wins))
        cache["blocks"].update(kv)
    elif fam == "ssm":
        def body(c, pl):
            if cfg.rwkv_chunk:
                tm, (S_st, x_tm) = ssm.rwkv_time_mix_chunked(
                    pl["rwkv"], _norm(c, pl["ln1"], cfg), cfg,
                    chunk=cfg.rwkv_chunk)
            else:
                tm, (S_st, x_tm) = ssm.rwkv_time_mix(
                    pl["rwkv"], _norm(c, pl["ln1"], cfg), cfg)
            c = c + tm
            cm, x_cm = ssm.rwkv_channel_mix(pl["rwkv"],
                                            _norm(c, pl["ln2"], cfg), cfg)
            c = c + cm
            return c, {"S": S_st, "x_tm": x_tm, "x_cm": x_cm}
        h, st = jax.lax.scan(body, h, params["blocks"])
        cache["blocks"].update(st)
    else:
        raise ValueError(fam)

    h = _norm(h, jax.tree.map(lambda a: a[0], params["final_norm"]), cfg)
    cache["length"] = jnp.full((B,), S, jnp.int32)
    return cache, h
