"""Blockwise int8 quantization for optimizer state (8-bit Adam).

At deepseek-v3 scale (671 B params) fp32 Adam moments alone are 5.4 TB —
over the 4 TB HBM of a full v5e pod. Blockwise int8 moments (one f32
scale per 128 values, +3% overhead) cut that 4x.

Sharding-friendly layout: blocks run along the LAST axis only, so the
quantized payload keeps the parameter's leading-axis sharding —
``q`` has shape ``shape[:-1] + (nb, 128)`` and ``scale`` is
``shape[:-1] + (nb,)``. Under GSPMD the moments therefore inherit the
parameter PartitionSpec (plus trailing Nones) with NO resharding in the
optimizer step (launch/specs.py relies on this).
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import jax
import jax.numpy as jnp


BLOCK = 128


# log-space dynamic range for the non-negative (second-moment) mode:
# values below vmax * 1e-12 collapse to the floor — harmless for Adam
# (1/sqrt(v)+eps saturates), while relative error stays ~11% on v (5.5%
# on sqrt(v)). Linear symmetric int8 on v would round small-in-block
# entries to ZERO -> 1/eps step explosions (verified divergence).
_LOG_RANGE = 27.631  # ln(1e12)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QuantizedTensor:
    """int8 payload + per-block (last-axis) scales.

    mode "sym": linear symmetric (signed data, e.g. Adam m).
    mode "log": blockwise log-space (non-negative data, e.g. Adam v).
    """
    q: jax.Array            # int8, shape[:-1] + (nb, BLOCK)
    scale: jax.Array        # f32, shape[:-1] + (nb,)
    shape: Tuple[int, ...]  # original shape (static aux)
    mode: str = "sym"       # static aux

    def tree_flatten(self):
        return (self.q, self.scale), (self.shape, self.mode)

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(children[0], children[1], aux[0], aux[1])

    @property
    def dtype(self):
        return jnp.float32


def _blocked(x: jax.Array):
    shape = x.shape
    last = shape[-1] if shape else 1
    nb = -(-last // BLOCK)
    pad = nb * BLOCK - last
    xf = x.astype(jnp.float32).reshape(shape[:-1] + (last,))
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (xf.ndim - 1) + [(0, pad)])
    return xf.reshape(shape[:-1] + (nb, BLOCK))


def quantize_blockwise(x: jax.Array, mode: str = "sym") -> QuantizedTensor:
    blk = _blocked(x)
    if mode == "sym":
        scale = jnp.max(jnp.abs(blk), axis=-1) / 127.0
        safe = jnp.maximum(scale, 1e-20)
        q = jnp.clip(jnp.round(blk / safe[..., None]), -127, 127)
    elif mode == "log":
        scale = jnp.max(blk, axis=-1)                 # vmax per block
        safe = jnp.maximum(scale, 1e-30)
        rel = jnp.maximum(blk / safe[..., None], 0.0)
        # q in [0,127]: 0 => vmax*exp(-LOG_RANGE), 127 => vmax
        q = jnp.round(127.0 * (1.0 + jnp.log(jnp.maximum(rel, 1e-13))
                               / _LOG_RANGE))
        q = jnp.clip(q, 0, 127)
    else:
        raise ValueError(mode)
    return QuantizedTensor(q.astype(jnp.int8), scale, x.shape, mode)


def dequantize_blockwise(t: QuantizedTensor) -> jax.Array:
    qf = t.q.astype(jnp.float32)
    if t.mode == "sym":
        blk = qf * t.scale[..., None]
    else:
        blk = t.scale[..., None] * jnp.exp(
            _LOG_RANGE * (qf / 127.0 - 1.0))
    last = t.shape[-1] if t.shape else 1
    flat = blk.reshape(t.shape[:-1] + (blk.shape[-2] * BLOCK,))
    return flat[..., :last].reshape(t.shape)
