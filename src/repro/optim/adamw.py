"""Sharded AdamW on parameter pytrees (no optax in this environment).

State sharding: moments inherit the parameter PartitionSpecs, so with
FSDP-sharded params (launch/specs.py) the optimizer is ZeRO-3-
equivalent for free under GSPMD. ``state_dtype`` selects the moment
representation: float32 | bfloat16 | int8 (blockwise, optim/quant.py).
"""
from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import TrainConfig
from repro.optim.quant import (
    QuantizedTensor,
    dequantize_blockwise,
    quantize_blockwise,
)


def _encode(x, dtype: str, mode: str = "sym"):
    if dtype == "int8":
        return quantize_blockwise(x, mode)
    return x.astype(dtype)


def _decode(x):
    if isinstance(x, QuantizedTensor):
        return dequantize_blockwise(x)
    return x.astype(jnp.float32)


def adamw_init(params, tc: TrainConfig) -> Dict[str, Any]:
    dt = tc.optimizer_state_dtype
    zeros = lambda mode: lambda p: _encode(
        jnp.zeros(p.shape, jnp.float32), dt, mode)
    return {
        "m": jax.tree.map(zeros("sym"), params),
        "v": jax.tree.map(zeros("log"), params),
        "step": jnp.zeros((), jnp.int32),
    }


def cosine_schedule(step, tc: TrainConfig):
    warm = jnp.minimum(1.0, (step + 1) / max(1, tc.warmup_steps))
    t = jnp.clip((step - tc.warmup_steps) /
                 max(1, tc.total_steps - tc.warmup_steps), 0.0, 1.0)
    return tc.learning_rate * warm * 0.5 * (1.0 + jnp.cos(jnp.pi * t))


def global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(
        jnp.sum(jnp.square(x.astype(jnp.float32)))
        for x in jax.tree.leaves(tree)))


def adamw_update(grads, state, params, tc: TrainConfig):
    """Returns (new_params, new_state, metrics). Grad-clip + AdamW + decay."""
    step = state["step"]
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, tc.grad_clip / jnp.maximum(gnorm, 1e-12))
    lr = cosine_schedule(step, tc)
    b1, b2 = tc.beta1, tc.beta2
    bc1 = 1.0 - b1 ** (step.astype(jnp.float32) + 1.0)
    bc2 = 1.0 - b2 ** (step.astype(jnp.float32) + 1.0)

    def upd(p, g, m_enc, v_enc):
        g = g.astype(jnp.float32) * clip
        m = b1 * _decode(m_enc) + (1 - b1) * g
        v = b2 * _decode(v_enc) + (1 - b2) * g * g
        mh = m / bc1
        vh = v / bc2
        delta = mh / (jnp.sqrt(vh) + tc.eps)
        # weight decay on matrices only (ndim >= 2), the usual convention
        if p.ndim >= 2:
            delta = delta + tc.weight_decay * p.astype(jnp.float32)
        newp = (p.astype(jnp.float32) - lr * delta).astype(p.dtype)
        dt = tc.optimizer_state_dtype
        return newp, _encode(m, dt, "sym"), _encode(v, dt, "log")

    flat_p, treedef = jax.tree.flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(state["m"])
    flat_v = treedef.flatten_up_to(state["v"])
    out = [upd(p, g, m, v) for p, g, m, v in
           zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = treedef.unflatten([o[0] for o in out])
    new_state = {
        "m": treedef.unflatten([o[1] for o in out]),
        "v": treedef.unflatten([o[2] for o in out]),
        "step": step + 1,
    }
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
