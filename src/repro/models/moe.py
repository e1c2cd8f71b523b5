"""Mixture-of-Experts FFN with expert-parallel all-to-all dispatch.

The EP path reuses the paper's permute machinery (core/routing.py): token
assignments are bucketed by owner rank (fixed capacity), all-to-all'd over
the ``model`` mesh axis, expert-computed, and returned through the inverse
permutation — structurally identical to the paper's RW embedding pipeline
(§4.2), with embedding rows replaced by expert FFNs. Two levels of
bucketing: rank-level (for the a2a) then local-expert level (for the
batched expert matmul).

``moe_ffn(params, x, cfg)``            — single-device oracle (scan over E).
``moe_ffn_ep(params, x, cfg, axis)``   — EP inside shard_map over ``axis``.
"""
from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from jax.lax import axis_size

from repro.configs.base import ModelConfig
from repro.core import routing
from repro.models import layers


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_moe_params(rng, n_layers: int, cfg: ModelConfig, dtype=jnp.float32):
    """Stacked (n_layers, ...) MoE block params."""
    d, f = cfg.d_model, cfg.moe_d_ff
    E = cfg.num_experts
    ks = jax.random.split(rng, 8)

    def stack(k, shape, scale):
        return (jax.random.truncated_normal(k, -2.0, 2.0,
                                            (n_layers,) + shape) * scale
                ).astype(dtype)

    p = {
        "router": stack(ks[0], (d, E), d ** -0.5).astype(jnp.float32),
        "gate": stack(ks[1], (E, d, f), d ** -0.5),
        "up": stack(ks[2], (E, d, f), d ** -0.5),
        "down": stack(ks[3], (E, f, d), f ** -0.5),
    }
    if cfg.num_shared_experts:
        fs = f * cfg.num_shared_experts
        p["shared"] = {
            "gate": stack(ks[4], (d, fs), d ** -0.5),
            "up": stack(ks[5], (d, fs), d ** -0.5),
            "down": stack(ks[6], (fs, d), fs ** -0.5),
        }
    return p


# ---------------------------------------------------------------------------
# Routing (shared by both paths)
# ---------------------------------------------------------------------------

def router_topk(x: jax.Array, router_w: jax.Array, k: int):
    """Returns (weights (N,k) f32, ids (N,k) i32, (top1_count, prob_sum, n)).

    The third element carries the per-shard load-balance sufficient
    statistics; ``aux_loss`` turns them into the GShard loss. Keeping them
    as SUMS lets the EP path psum them over the axis first, so the
    distributed aux loss equals the global single-device one exactly.
    """
    logits = x.astype(jnp.float32) @ router_w                    # (N, E)
    E = logits.shape[-1]
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(logits, k)
    w = jax.nn.softmax(topv, axis=-1)                            # renormalize
    onehot_top1 = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    stats = (onehot_top1.sum(0), probs.sum(0),
             jnp.asarray(x.shape[0], jnp.float32))
    return w, topi.astype(jnp.int32), stats


def aux_loss(stats) -> jax.Array:
    """GShard load-balance loss: E * mean_e(frac_tokens_e * mean_prob_e)."""
    top1_count, prob_sum, n = stats
    E = top1_count.shape[0]
    return E * jnp.mean((top1_count / n) * (prob_sum / n))


def _shared_out(p, x, act):
    if "shared" not in p:
        return 0.0
    s = p["shared"]
    return (layers.activation_fn(act)(x @ s["gate"]) * (x @ s["up"])) @ s["down"]


# ---------------------------------------------------------------------------
# Oracle: single-device dense scan over experts
# ---------------------------------------------------------------------------

def moe_ffn(p, x: jax.Array, cfg: ModelConfig):
    """x (N, d) -> (N, d). Reference path: loop experts, mask-combine."""
    N, d = x.shape
    k = cfg.experts_per_token
    w, topi, stats = router_topk(x, p["router"], k)
    aux = aux_loss(stats)

    def per_expert(carry, ep):
        gate_w, up_w, down_w, e = ep
        h = layers.activation_fn(cfg.activation)(x @ gate_w) * (x @ up_w)
        y = h @ down_w                                           # (N, d)
        sel = (topi == e).astype(jnp.float32) * w                # (N, k)
        return carry + y * sel.sum(-1, keepdims=True).astype(y.dtype), None

    E = cfg.num_experts
    out, _ = jax.lax.scan(
        per_expert, jnp.zeros_like(x),
        (p["gate"], p["up"], p["down"], jnp.arange(E)))
    out = out + _shared_out(p, x, cfg.activation)
    return out, {"moe_aux": aux, "moe_dropped": jnp.zeros((), jnp.int32)}


# ---------------------------------------------------------------------------
# Expert-parallel path (inside shard_map over ``axis``)
# ---------------------------------------------------------------------------

def moe_ffn_ep(p, x: jax.Array, cfg: ModelConfig, axis: str):
    """Expert-parallel MoE: x (N_local, d) sharded over ``axis``.

    Expert params arrive shard_map-sliced: (E/ranks, d, f). The dispatch is
    the paper's permute pipeline: bucket-by-owner -> all_to_all -> local
    compute -> inverse all_to_all -> weighted combine (segment-sum).
    """
    n_ranks = axis_size(axis)
    N, d = x.shape
    k = cfg.experts_per_token
    E = cfg.num_experts
    e_local = E // n_ranks
    assert p["gate"].shape[0] == e_local, (p["gate"].shape, e_local)

    w, topi, stats = router_topk(x, p["router"], k)              # (N,k)
    # global load-balance statistics (exactly equals the oracle's aux)
    stats = tuple(jax.lax.psum(s, axis) for s in stats)
    aux = aux_loss(stats)
    flat_e = topi.reshape(-1)                                    # (N*k,)
    flat_w = w.reshape(-1)
    tok_id = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)

    # --- level 1: bucket assignments by owner rank, a2a the hidden vectors
    cap1 = max(1, math.ceil(N * k / n_ranks * cfg.moe_capacity_factor))
    dest_rank = flat_e // e_local
    # empty slots are tagged with local-expert id ``e_local`` (one past the
    # end) so that at level 2 they fall into a discard bucket instead of
    # stealing expert-0 capacity.
    (b_tok, b_el), slot1, drop1 = routing.fixed_capacity_bucket(
        dest_rank, n_ranks, cap1,
        [tok_id, (flat_e % e_local).astype(jnp.int32)],
        fills=[0, e_local])
    send_x = x[b_tok.reshape(-1)].reshape(n_ranks, cap1, d)
    recv_x = jax.lax.all_to_all(send_x, axis, 0, 0)              # (n_ranks,cap1,d)
    recv_el = jax.lax.all_to_all(b_el, axis, 0, 0)

    # --- level 2: bucket received rows by local expert, batched matmul
    M = n_ranks * cap1
    cap2 = max(1, math.ceil(N * k / e_local * cfg.moe_capacity_factor))
    flat_recv = recv_x.reshape(M, d)
    flat_el = recv_el.reshape(-1)
    (e_in,), slot2, _ = routing.fixed_capacity_bucket(
        flat_el, e_local + 1, cap2, [flat_recv])
    drop2 = jnp.sum((flat_el < e_local) &
                    (slot2 >= (e_local + 1) * cap2))
    e_in = e_in[:e_local]                                        # discard bucket
    h = jnp.einsum("ecd,edf->ecf", e_in, p["gate"])
    h = layers.activation_fn(cfg.activation)(h) * jnp.einsum(
        "ecd,edf->ecf", e_in, p["up"])
    e_out = jnp.einsum("ecf,efd->ecd", h, p["down"])             # (e_local,cap2,d)
    e_out = jnp.concatenate(
        [e_out, jnp.zeros((1, cap2, d), e_out.dtype)], axis=0)

    # --- inverse: unbucket level 2, a2a back, unbucket level 1, combine
    back = routing.gather_from_buckets(slot2, e_out)             # (M, d)
    ret = jax.lax.all_to_all(back.reshape(n_ranks, cap1, d), axis, 0, 0)
    contrib = routing.gather_from_buckets(slot1, ret)            # (N*k, d)
    out = jax.ops.segment_sum(
        contrib.astype(jnp.float32) * flat_w[:, None], tok_id, num_segments=N
    ).astype(x.dtype)

    out = out + _shared_out(p, x, cfg.activation)
    dropped = jax.lax.psum(drop1 + drop2, axis)
    return out, {"moe_aux": aux, "moe_dropped": dropped}
