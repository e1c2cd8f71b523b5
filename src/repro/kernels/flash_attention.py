"""Pallas TPU flash attention (forward) — the prefill hot-spot kernel.

Online-softmax blockwise attention with explicit VMEM tiling:

  grid = (B*H, nQ, nK) — kv blocks innermost ("arbitrary" semantics), so
  the (m, l, acc) running statistics live in VMEM scratch across the nK
  steps of one (b, h, qi) cell; Q/K/V blocks are DMA'd HBM->VMEM by the
  Pallas pipeline (double-buffered), the (q_block, kv_block) score tile
  hits the MXU, and the normalized output block is written once on the
  last kv step.

GQA: kv head index = q head // group — expressed in the K/V BlockSpec
index maps, so grouped heads reuse the same KV tiles.

Causal + sliding-window masking is applied with block-level shortcuts:
fully-masked kv blocks are skipped via pl.when (no MXU work), partially
masked blocks apply an elementwise mask. VMEM per grid cell:
q (qb, hd) + k,v (kb, hd) x2(double-buffer) + acc (qb, hd) f32 + tile
(qb, kb) f32 — with qb=kb=512, hd=128 that is ~2.8 MiB, well under v5e's
128 MiB VMEM.

Oracle: models/layers.chunked_attention (pure jnp, same math).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


DEFAULT_BLOCK = 512
_NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  scale: float, causal: bool, window: Optional[int],
                  q_block: int, kv_block: int, seq_len: int, n_kv: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * q_block
    k_start = ki * kv_block
    # block-level liveness: causal => skip blocks fully above the diagonal;
    # window => skip blocks fully left of the window
    live = True
    if causal:
        live = k_start <= q_start + q_block - 1
    if window is not None:
        live = jnp.logical_and(
            live, k_start + kv_block - 1 > q_start - window)

    @pl.when(live)
    def _step():
        q = q_ref[0, 0].astype(jnp.float32)                 # (qb, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (kb, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # (qb, kb)

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (q_block, kv_block), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (q_block, kv_block), 1)
        mask = kpos < seq_len                               # padded keys
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_block", "kv_block",
                              "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: Optional[int] = None,
                    q_block: int = DEFAULT_BLOCK,
                    kv_block: int = DEFAULT_BLOCK,
                    interpret: bool = False) -> jax.Array:
    """q (B, S, H, hd); k, v (B, S, KH, hd) -> (B, S, H, hd).

    Padded internally to block multiples; padded keys are masked out.
    """
    B, S, H, hd = q.shape
    KH = k.shape[2]
    G = H // KH
    scale = hd ** -0.5
    qb = min(q_block, max(S, 8))
    kb = min(kv_block, max(S, 8))
    Sp_q = -(-S // qb) * qb
    Sp_k = -(-S // kb) * kb
    # (B, heads, S, hd) layout for clean 2-D blocks
    qt = jnp.pad(q.transpose(0, 2, 1, 3), ((0, 0), (0, 0),
                                           (0, Sp_q - S), (0, 0)))
    kt = jnp.pad(k.transpose(0, 2, 1, 3), ((0, 0), (0, 0),
                                           (0, Sp_k - S), (0, 0)))
    vt = jnp.pad(v.transpose(0, 2, 1, 3), ((0, 0), (0, 0),
                                           (0, Sp_k - S), (0, 0)))
    nQ, nK = Sp_q // qb, Sp_k // kb

    grid = (B * H, nQ, nK)
    out = pl.pallas_call(
        functools.partial(
            _flash_kernel, scale=scale, causal=causal, window=window,
            q_block=qb, kv_block=kb, seq_len=S, n_kv=nK),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, qb, hd),
                         lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda bh, qi, ki: (bh // H, (bh % H) // G, ki, 0)),
            pl.BlockSpec((1, 1, kb, hd),
                         lambda bh, qi, ki: (bh // H, (bh % H) // G, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qb, hd),
                               lambda bh, qi, ki: (bh // H, bh % H, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp_q, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb,), jnp.float32),
            pltpu.VMEM((qb, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    return out[:, :, :S].transpose(0, 2, 1, 3)
