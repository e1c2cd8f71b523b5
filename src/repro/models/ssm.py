"""State-space sequence mixers: Mamba (S6, for Hymba) and RWKV-6 (Finch).

Both expose a full-sequence form (chunked scan — bounded memory, the
activation never materializes (B, S, d_inner, N)) and a single-step decode
form carrying O(1) state, which is what makes the ``long_500k`` cell
feasible for the ssm/hybrid families.

Mamba recurrence (per channel c, state n):
    h_t = exp(dt_t A)[c,n] h_{t-1} + dt_t B_t[n] x_t[c]
    y_t[c] = sum_n C_t[n] h_t[c,n] + D[c] x_t[c]
computed chunkwise with an associative scan inside each chunk.

RWKV-6 recurrence (per head, hs x hs state S):
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)
    S_t = diag(w_t) S_{t-1} + k_t^T v_t
with the Finch data-dependent decay w_t = exp(-exp(w0 + lora(x_t))).
"""
from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers


# ===========================================================================
# Mamba (S6) — used as the SSM heads of Hymba
# ===========================================================================

def init_mamba_params(rng, n: int, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    di = cfg.ssm_expand * d
    N = cfg.ssm_state
    dt_rank = max(1, math.ceil(d / 16))
    ks = jax.random.split(rng, 8)

    def stack(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2.0, 2.0, (n,) + shape)
                * fan_in ** -0.5).astype(dtype)

    return {
        "in_proj": stack(ks[0], (d, 2 * di), d),          # x and z (gate)
        "conv_w": stack(ks[1], (cfg.ssm_conv, di), cfg.ssm_conv),
        "conv_b": jnp.zeros((n, di), dtype),
        "x_proj": stack(ks[2], (di, dt_rank + 2 * N), di),
        "dt_proj": stack(ks[3], (dt_rank, di), dt_rank),
        "dt_bias": jnp.zeros((n, di), dtype),
        # S4D-real init: A = -(1..N) per channel
        "A_log": jnp.broadcast_to(
            jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (n, di, N)
        ).astype(dtype),
        "D": jnp.ones((n, di), dtype),
        "out_proj": stack(ks[4], (di, d), di),
    }


def _mamba_gates(p, x, cfg: ModelConfig):
    """Shared projections: x (B,S,d) -> (xs, z, dt, Bc, Cc)."""
    N = cfg.ssm_state
    di = cfg.ssm_expand * cfg.d_model
    dt_rank = p["dt_proj"].shape[0]
    xz = x @ p["in_proj"]                                 # (B,S,2di)
    xs, z = jnp.split(xz, 2, axis=-1)
    return xs, z


def _mamba_ssm_inputs(p, xs, cfg: ModelConfig):
    N = cfg.ssm_state
    dt_rank = p["dt_proj"].shape[0]
    proj = xs @ p["x_proj"]                               # (B,S,dt_rank+2N)
    dt = jax.nn.softplus(proj[..., :dt_rank] @ p["dt_proj"] + p["dt_bias"])
    Bc = proj[..., dt_rank: dt_rank + N]                  # (B,S,N)
    Cc = proj[..., dt_rank + N:]                          # (B,S,N)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))          # (di,N)
    return dt, Bc, Cc, A


def _causal_conv(xs, w, b, conv_state=None):
    """Depthwise causal conv1d. xs (B,S,di), w (K,di). Returns (y, new_state).

    ``conv_state`` (B,K-1,di) carries the last K-1 inputs for decode.
    """
    K = w.shape[0]
    if conv_state is None:
        pad = jnp.zeros_like(xs[:, : K - 1])
    else:
        pad = conv_state
    xp = jnp.concatenate([pad, xs], axis=1)               # (B,S+K-1,di)
    y = sum(xp[:, i: i + xs.shape[1]] * w[i] for i in range(K)) + b
    new_state = xp[:, -(K - 1):] if K > 1 else None
    return jax.nn.silu(y), new_state


def mamba_forward(p, x, cfg: ModelConfig, *, chunk: int = 128):
    """Full-sequence Mamba mixer: x (B,S,d) -> (y (B,S,d), final_state)."""
    B, S, d = x.shape
    di = cfg.ssm_expand * d
    N = cfg.ssm_state
    xs, z = _mamba_gates(p, x, cfg)
    xs, _ = _causal_conv(xs, p["conv_w"], p["conv_b"])
    dt, Bc, Cc, A = _mamba_ssm_inputs(p, xs, cfg)

    chunk = min(chunk, S)
    n_chunks = -(-S // chunk)
    Sp = n_chunks * chunk
    pad = lambda a: jnp.pad(a, ((0, 0), (0, Sp - S)) + ((0, 0),) * (a.ndim - 2))
    xs_p, dt_p, B_p, C_p = map(pad, (xs, dt, Bc, Cc))

    def chunk_body(h0, inp):
        xc, dtc, bc, cc = inp                             # (B,chunk,·)
        # per-step transition a_t (B,c,di,N) and input b_t
        a = jnp.exp(dtc[..., None].astype(jnp.float32) * A)          # (B,c,di,N)
        bx = (dtc * xc)[..., None].astype(jnp.float32) * \
            bc[:, :, None, :].astype(jnp.float32)                    # (B,c,di,N)

        def combine(l, r):
            al, bl = l
            ar, br = r
            return al * ar, bl * ar + br

        aa, bb = jax.lax.associative_scan(combine, (a, bx), axis=1)
        h = aa * h0[:, None] + bb                          # (B,c,di,N)
        y = jnp.einsum("bcdn,bcn->bcd", h, cc.astype(jnp.float32))
        return h[:, -1], y

    xs_c = xs_p.reshape(B, n_chunks, chunk, di).swapaxes(0, 1)
    dt_c = dt_p.reshape(B, n_chunks, chunk, di).swapaxes(0, 1)
    B_c = B_p.reshape(B, n_chunks, chunk, N).swapaxes(0, 1)
    C_c = C_p.reshape(B, n_chunks, chunk, N).swapaxes(0, 1)
    h_final, ys = jax.lax.scan(
        lambda h, i: chunk_body(h, i),
        jnp.zeros((B, di, N), jnp.float32), (xs_c, dt_c, B_c, C_c))
    y = ys.swapaxes(0, 1).reshape(B, Sp, di)[:, :S]
    y = (y + xs * p["D"]).astype(x.dtype) * jax.nn.silu(z)
    return y @ p["out_proj"], h_final


def mamba_decode_step(p, x, cfg: ModelConfig, h, conv_state):
    """One token: x (B,1,d); h (B,di,N); conv_state (B,K-1,di)."""
    xs, z = _mamba_gates(p, x, cfg)
    xs, conv_state = _causal_conv(xs, p["conv_w"], p["conv_b"], conv_state)
    dt, Bc, Cc, A = _mamba_ssm_inputs(p, xs, cfg)
    a = jnp.exp(dt[:, 0, :, None].astype(jnp.float32) * A)           # (B,di,N)
    bx = (dt * xs)[:, 0, :, None].astype(jnp.float32) * \
        Bc[:, 0, None, :].astype(jnp.float32)
    h = a * h + bx
    y = jnp.einsum("bdn,bn->bd", h, Cc[:, 0].astype(jnp.float32))[:, None]
    y = (y + xs * p["D"]).astype(x.dtype) * jax.nn.silu(z)
    return y @ p["out_proj"], h, conv_state


# ===========================================================================
# RWKV-6 (Finch)
# ===========================================================================

def init_rwkv_params(rng, n: int, cfg: ModelConfig, dtype=jnp.float32):
    d = cfg.d_model
    hs = cfg.rwkv_head_size
    H = d // hs
    lora = 64
    ks = jax.random.split(rng, 12)

    def stack(k, shape, fan_in):
        return (jax.random.truncated_normal(k, -2.0, 2.0, (n,) + shape)
                * fan_in ** -0.5).astype(dtype)

    return {
        # token-shift interpolation weights (static mu per stream)
        "mu": jnp.full((n, 5, d), 0.5, dtype),            # r,k,v,w,g
        "w_r": stack(ks[0], (d, d), d),
        "w_k": stack(ks[1], (d, d), d),
        "w_v": stack(ks[2], (d, d), d),
        "w_g": stack(ks[3], (d, d), d),
        "w_o": stack(ks[4], (d, d), d),
        # Finch data-dependent decay lora: w = exp(-exp(w0 + tanh(xA)B))
        "w0": jnp.full((n, d), -6.0, dtype),
        "w_A": stack(ks[5], (d, lora), d),
        "w_B": stack(ks[6], (lora, d), lora),
        "u": jnp.zeros((n, d), dtype),                    # bonus
        "ln_w": jnp.ones((n, d), dtype),                  # per-head groupnorm
        "ln_b": jnp.zeros((n, d), dtype),
        # channel mix
        "mu_c": jnp.full((n, 2, d), 0.5, dtype),
        "ck": stack(ks[7], (d, cfg.d_ff), d),
        "cv": stack(ks[8], (cfg.d_ff, d), cfg.d_ff),
        "cr": stack(ks[9], (d, d), d),
    }


def _rwkv_mix_inputs(p, x, x_prev, cfg: ModelConfig):
    """Token-shifted projections for one or more timesteps.

    x (B,S,d); x_prev (B,S,d) = x shifted right by one (decode: the carried
    last token). Returns r,k,v,g,w_decay each (B,S,H,hs)-shaped views.
    """
    d = cfg.d_model
    hs = cfg.rwkv_head_size
    H = d // hs
    mu = p["mu"]                                          # (5,d)
    mix = lambda i: x + (x_prev - x) * mu[i]
    r = mix(0) @ p["w_r"]
    k = mix(1) @ p["w_k"]
    v = mix(2) @ p["w_v"]
    xw = mix(3)
    g = jax.nn.silu(mix(4) @ p["w_g"])
    w = jnp.exp(-jnp.exp(
        (p["w0"] + jnp.tanh(xw @ p["w_A"]) @ p["w_B"]).astype(jnp.float32)))
    hview = lambda a: a.reshape(a.shape[0], a.shape[1], H, hs)
    return hview(r), hview(k), hview(v), g, hview(w.astype(x.dtype))


def rwkv_time_mix(p, x, cfg: ModelConfig, *, state=None, x_last=None):
    """Full-sequence time mix: x (B,S,d) -> (y, (final_state, last_x)).

    ``state`` (B,H,hs,hs) and ``x_last`` (B,d) carry decode state; None for
    a fresh sequence. Scans timesteps (the honest recurrent form; the
    chunked-parallel form is ``rwkv_chunk``).
    """
    B, S, d = x.shape
    hs = cfg.rwkv_head_size
    H = d // hs
    x_prev = jnp.concatenate(
        [jnp.zeros_like(x[:, :1]) if x_last is None else x_last[:, None],
         x[:, :-1]], axis=1)
    r, k, v, g, w = _rwkv_mix_inputs(p, x, x_prev, cfg)
    u = p["u"].reshape(H, hs).astype(jnp.float32)

    def step(S_state, inp):
        rt, kt, vt, wt = inp                              # (B,H,hs)
        rt, kt, vt, wt = (a.astype(jnp.float32) for a in (rt, kt, vt, wt))
        kv = kt[..., :, None] * vt[..., None, :]          # (B,H,hs,hs)
        y = jnp.einsum("bhk,bhkv->bhv", rt, S_state + u[..., None] * kv)
        S_state = wt[..., :, None] * S_state + kv
        return S_state, y

    S0 = (jnp.zeros((B, H, hs, hs), jnp.float32) if state is None
          else state)
    tmaj = lambda a: a.swapaxes(0, 1)                     # (S,B,H,hs)
    S_final, ys = jax.lax.scan(step, S0, (tmaj(r), tmaj(k), tmaj(v), tmaj(w)))
    y = ys.swapaxes(0, 1).reshape(B, S, d).astype(x.dtype)
    # per-head groupnorm then gate
    y = y.reshape(B, S, H, hs)
    mean = y.mean(-1, keepdims=True)
    var = y.var(-1, keepdims=True)
    y = ((y - mean) * jax.lax.rsqrt(var + 64e-5)).reshape(B, S, d)
    y = (y * p["ln_w"] + p["ln_b"]) * g
    return y @ p["w_o"], (S_final, x[:, -1])


def rwkv_time_mix_chunked(p, x, cfg: ModelConfig, *, chunk: int = 64,
                          state=None, x_last=None):
    """Chunk-parallel RWKV-6 time mix — numerically equal to the per-step
    scan, with state materialized only at chunk boundaries.

    Within a chunk of C tokens (per head, state S[k,v], decay w_t[k]):
        P_t[k] = prod_{i<=t} w_i[k]          (log-space cumsum, stable:
                                              all used ratios are <= 1)
        y_t = (r_t . P_{t-1} ⊙ S_0) + sum_{i<t} ((r_t⊙P_{t-1})·(k_i/P_i)) v_i
              + (r_t·k_t) u ⊙ v_t                     [diagonal bonus]
        S_C = P_C ⊙ S_0 + (K ⊙ P_C/P)^T V
    i.e. one (C, C) attention-like matrix per head per chunk instead of C
    sequential (hs, hs) state updates — HBM state traffic drops by ~C and
    the (C,C)@ (C,hs) matmuls hit the MXU. This is the §Perf hillclimb
    change for the rwkv6 train cell; equality with the scan form is tested
    in tests/test_models.py.
    """
    B, S, d = x.shape
    hs = cfg.rwkv_head_size
    H = d // hs
    C = min(chunk, S)
    n_chunks = -(-S // C)
    Sp = n_chunks * C
    x_prev = jnp.concatenate(
        [jnp.zeros_like(x[:, :1]) if x_last is None else x_last[:, None],
         x[:, :-1]], axis=1)
    r, k, v, g, w = _rwkv_mix_inputs(p, x, x_prev, cfg)
    u = p["u"].reshape(H, hs).astype(jnp.float32)

    def pad(a):
        return jnp.pad(a, ((0, 0), (0, Sp - S)) + ((0, 0),) * (a.ndim - 2))

    # (n_chunks, B, C, H, hs) f32
    def cview(a):
        return pad(a.astype(jnp.float32)).reshape(
            B, n_chunks, C, H, hs).swapaxes(0, 1)

    rc, kc, vc, wc = cview(r), cview(k), cview(v), cview(w)
    # padded slots: w=1 (log 0) keeps cumsums inert; k,v,r already 0-padded
    logw = jnp.where(
        (jnp.arange(Sp) < S).reshape(1, n_chunks, C, 1, 1).swapaxes(0, 1),
        jnp.log(jnp.maximum(wc, 1e-38)), 0.0)

    def chunk_body(S0, inp):
        rb, kb, vb, lw = inp                   # (B, C, H, hs)
        logP = jnp.cumsum(lw, axis=1)          # P_t (log), t = 1..C
        P = jnp.exp(logP)
        Pm1 = jnp.exp(logP - lw)               # P_{t-1}
        r_dec = rb * Pm1                       # r_t ⊙ P_{t-1}
        k_grow = kb * jnp.exp(-logP)           # k_i / P_i
        # A[t,i] = (r_t⊙P_{t-1})·(k_i/P_i), strictly causal (i < t)
        A = jnp.einsum("bthk,bihk->bhti", r_dec, k_grow)
        mask = jnp.tril(jnp.ones((C, C), bool), k=-1)
        A = jnp.where(mask[None, None], A, 0.0)
        y = jnp.einsum("bhti,bihv->bthv", A, vb)
        y = y + jnp.einsum("bthk,bhkv->bthv", r_dec, S0)
        diag = jnp.einsum("bthk,hk->bth", rb * kb, u)
        y = y + diag[..., None] * vb
        PC = P[:, -1]                          # (B, H, hs)
        S_new = PC[..., None] * S0 + jnp.einsum(
            "bthk,bthv->bhkv", kb * jnp.exp(logP[:, -1:] - logP), vb)
        return S_new, y

    S0 = (jnp.zeros((B, H, hs, hs), jnp.float32) if state is None else state)
    S_final, ys = jax.lax.scan(chunk_body, S0, (rc, kc, vc, logw))
    y = ys.swapaxes(0, 1).reshape(B, Sp, d)[:, :S].astype(x.dtype)
    y = y.reshape(B, S, H, hs)
    mean = y.mean(-1, keepdims=True)
    var = y.var(-1, keepdims=True)
    y = ((y - mean) * jax.lax.rsqrt(var + 64e-5)).reshape(B, S, d)
    y = (y * p["ln_w"] + p["ln_b"]) * g
    return y @ p["w_o"], (S_final, x[:, -1])


def rwkv_channel_mix(p, x, cfg: ModelConfig, *, x_last=None):
    """Channel mix (the rwkv FFN): squared-relu with token shift."""
    x_prev = jnp.concatenate(
        [jnp.zeros_like(x[:, :1]) if x_last is None else x_last[:, None],
         x[:, :-1]], axis=1)
    mu = p["mu_c"]
    xk = x + (x_prev - x) * mu[0]
    xr = x + (x_prev - x) * mu[1]
    k = jnp.square(jax.nn.relu(xk @ p["ck"]))
    return jax.nn.sigmoid(xr @ p["cr"]) * (k @ p["cv"]), x[:, -1]
