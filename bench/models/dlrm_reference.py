"""DLRM as the benchmark defines it, independent of the program under test.

Everything here is plain ``jax.numpy`` in float32 and imports nothing
of the program: the embedding rows as a function of the seed, the
reference forward that decides ``correct``, its lower-precision control,
and the operation and byte counts that the per-layer metrics divide by.

Embedding rows are a counter-based hash of ``(seed, table, row, column)``
mapped to a uniform value of variance ``1/D`` (the scale of TorchRec's
default initialisation).  So the benchmark can draw the whole ``(T, R, D)``
stack on the device in one program for the system under test, and the
reference can draw again just the rows a sample reads: it needs no copy
of the tables, on any number of chips.

The forward is the DLRM of Naumov et al. (arXiv:1906.00091) with the dot
interaction: bottom MLP with ReLU after every layer; sum-pooled bags;
pairwise dot products of the ``T + 1`` feature vectors, the pairs taken
from the upper triangle in row-major order and placed after the bottom
MLP's output; top MLP with ReLU between layers; sigmoid.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = np.uint32(0x9E3779B9)


def seed_words(seed: int) -> np.ndarray:
    """The seed as two uint32 words (seeds may exceed 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def equal_tables(cfg: dict) -> dict:
    """``cfg``, refused where ``rows_per_table`` or ``pooling`` is a list:
    this model takes one of each for all its tables."""
    for key in ("rows_per_table", "pooling"):
        if not isinstance(cfg[key], int):
            raise ValueError(f"{key} is {cfg[key]!r}: the dlrm model takes "
                             "one int for every table")
    return cfg


def _mix32(x):
    """A 32-bit integer finaliser (lowbias32): every input bit moves
    about half of the output bits."""
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    return x ^ (x >> 16)


def rows_from_seed(words, flat_row, dim: int):
    """Embedding rows ``(..., dim)`` f32 for uint32 ``flat_row = t * R + r``.

    Bitwise the same wherever and in whatever batch it is computed: the
    system under test is handed the rows of this function and the
    reference draws them again."""
    words = jnp.asarray(words, jnp.uint32)
    s = _mix32(words[1] ^ _mix32(words[0] + _GOLDEN))
    h = _mix32(flat_row.astype(jnp.uint32) ^ s)
    h = _mix32(h[..., None] + jnp.arange(dim, dtype=jnp.uint32) * _GOLDEN)
    u = (h >> 8).astype(jnp.float32) * jnp.float32(2.0 ** -24)    # [0, 1)
    return (u - 0.5) * jnp.float32(2.0 * math.sqrt(3.0 / dim))


def mlp_dims(cfg: dict):
    n = cfg["num_sparse_features"] + 1
    inter = cfg["bottom_mlp"][-1] + n * (n - 1) // 2
    return ((cfg["num_dense_features"],) + tuple(cfg["bottom_mlp"]),
            (inter,) + tuple(cfg["top_mlp"]))


def mlp_from_seed(key, cfg: dict):
    """Bottom and top MLP weights: truncated normal scaled by fan-in**-0.5,
    small normal biases; layers as ``{"w": (in, out), "b": (out,)}``."""
    out = {}
    for name, dims in zip(("bottom", "top"), mlp_dims(cfg)):
        key, sub = jax.random.split(key)
        ks = jax.random.split(sub, 2 * (len(dims) - 1))
        out[name] = [
            {"w": jax.random.truncated_normal(ks[2 * i], -2.0, 2.0, (a, b))
                  * jnp.float32(a ** -0.5),
             "b": 0.05 * jax.random.normal(ks[2 * i + 1], (b,))}
            for i, (a, b) in enumerate(zip(dims[:-1], dims[1:]))]
    return out


# ---------------------------------------------------------------------------
# The reference forward and its control
# ---------------------------------------------------------------------------

def _split_bf16(x):
    """``x`` as ``hi + lo``, both bfloat16 values held in float32.

    ``reduce_precision`` and not a cast to bfloat16 and back: XLA may drop
    such a pair of casts (excess precision is allowed by default), which
    would leave ``lo = 0`` and turn three passes into one."""
    hi = jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
    lo = jax.lax.reduce_precision(x - hi, exponent_bits=8, mantissa_bits=7)
    return hi, lo


def _matmul(a, b, precision: str, spec: str):
    """``einsum(spec, a, b)`` in float32 at ``precision``: "highest" (full
    float32 on any backend) or "high" — three bfloat16 passes, the
    algorithm a TPU runs for ``Precision.HIGH``, written out so that it
    reads the same on a CPU: the products of bfloat16 values are exact at
    any precision, and each pass accumulates in float32."""
    if precision not in ("highest", "high"):
        raise ValueError(f"unknown precision {precision!r}")

    def dot(x, y):
        return jnp.einsum(spec, x, y, precision=jax.lax.Precision.HIGHEST)

    if precision == "highest":
        return dot(a, b)
    (ah, al), (bh, bl) = _split_bf16(a), _split_bf16(b)
    return dot(ah, bh) + (dot(ah, bl) + dot(al, bh))


def _mlp(layers, x, precision, *, final_relu):
    for i, layer in enumerate(layers):
        x = _matmul(x, layer["w"], precision, "bi,io->bo") + layer["b"]
        if final_relu or i < len(layers) - 1:
            x = jnp.maximum(x, 0.0)
    return x


def forward(words, mlp, dense, ids, lengths, *, rows: int, precision: str):
    """pCTR ``(S,)`` of ``S`` samples: ``dense (S, F)``, ``ids (S, T, L)``
    table-local rows, ``lengths (S, T)``; the rows are drawn from
    ``words`` (see :func:`rows_from_seed`)."""
    S, T, L = ids.shape
    D = mlp["bottom"][-1]["w"].shape[1]
    flat = (jnp.arange(T, dtype=jnp.uint32)[None, :, None] * jnp.uint32(rows)
            + ids.astype(jnp.uint32))
    emb = rows_from_seed(words, flat, D)                       # (S, T, L, D)
    live = jnp.arange(L)[None, None, :] < lengths[:, :, None]
    pooled = jnp.sum(jnp.where(live[..., None], emb, 0.0), axis=2)
    x = _mlp(mlp["bottom"], dense, precision, final_relu=True)  # (S, D)
    feats = jnp.concatenate([x[:, None, :], pooled], axis=1)    # (S, T+1, D)
    gram = _matmul(feats, feats, precision, "snd,smd->snm")
    iu, ju = np.triu_indices(T + 1, k=1)
    z = jnp.concatenate([x, gram[:, iu, ju]], axis=1)
    logit = _mlp(mlp["top"], z, precision, final_relu=False)[:, 0]
    return jax.nn.sigmoid(logit)


@functools.partial(jax.jit, static_argnames=("rows", "precision"))
def _reference(words, mlp, dense, ids, lengths, *, rows, precision):
    return forward(words, mlp, dense, ids, lengths, rows=rows,
                   precision=precision)


def reference_scores(seed: int, cfg: dict, mlp, dense, ids, lengths, *,
                     precision: str = "highest", block: int = 256):
    """The reference's pCTR for the given samples, ``block`` at a time on
    the default device."""
    equal_tables(cfg)
    words = jnp.asarray(seed_words(seed))
    mlp = jax.tree.map(jnp.asarray, mlp)
    n = dense.shape[0]
    out = []
    for s in range(0, n, block):
        e = min(n, s + block)
        pad = block - (e - s)   # one compiled shape for every block
        d = np.pad(dense[s:e], ((0, pad), (0, 0)))
        i = np.pad(ids[s:e], ((0, pad), (0, 0), (0, 0)))
        ln = np.pad(lengths[s:e], ((0, pad), (0, 0)))
        out.append(np.asarray(_reference(
            words, mlp, jnp.asarray(d), jnp.asarray(i), jnp.asarray(ln),
            rows=cfg["rows_per_table"], precision=precision))[: e - s])
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# Operations and bytes, from shapes
# ---------------------------------------------------------------------------

def flops_per_sample(cfg: dict) -> int:
    """Operations one sample needs: both MLPs (2 per multiply-add), the
    interaction's distinct pairs, and the pooling's multiply-adds."""
    bottom, top = mlp_dims(equal_tables(cfg))
    mlp = sum(2 * a * b for dims in (bottom, top)
              for a, b in zip(dims[:-1], dims[1:]))
    n = cfg["num_sparse_features"] + 1
    pairs = n * (n - 1) // 2
    D = cfg["embedding_dim"]
    pool = 2 * cfg["num_sparse_features"] * cfg["pooling"] * D
    return mlp + 2 * pairs * D + pool


def tbe_least_bytes(distinct_rows: int, slots: int, samples: int, tables: int,
                    dim: int, itemsize: int = 4) -> int:
    """Bytes a pooled lookup must move at the least: each distinct
    ``(table, row)`` read once, the ids and weights of every slot, and
    the ``(T, B, D)`` pooled output written once."""
    return (distinct_rows * dim * itemsize + slots * (4 + 4)
            + tables * samples * dim * 4)
