"""DLRM-DCNv2 as the benchmark defines it, independent of the program
under test.

Plain ``jax.numpy`` in float32, importing nothing of the program: the
weights as a function of the seed, the reference forward that decides
``correct``, and the operation and byte counts the per-layer metrics
divide by.  Embedding rows come from ``dlrm_reference.rows_from_seed``:
table ``t``'s row ``r`` is flat row ``offset_t + r``, ``offset_t`` the
rows of tables ``0 .. t - 1``, so the benchmark draws the whole flat
``(sum R_t, D)`` table on the device for the system under test, and the
reference draws again just the rows a sample reads.

The forward is DLRMv2 as MLPerf runs it (MLPerf Training
``recommendation_v2/torchrec_dlrm``, MLPerf Inference ``dlrm_v2``):
bottom MLP with ReLU after every layer; sum-pooled multi-hot bags, table
``t`` reading its first ``lengths[t]`` of ``pooling[t]`` ids; ``x0`` =
the bottom output then the pooled vectors in table order; the DCN-v2
low-rank cross network (Wang et al., arXiv:2008.13535),
``x_{l+1} = x0 * (W_l (V_l x_l) + b_l) + x_l``; top MLP with ReLU
between layers; sigmoid.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench.models.dlrm_reference import (  # noqa: F401 (re-exported)
    _matmul, _mlp, rows_from_seed, seed_words, tbe_least_bytes)
from bench.traffic import per_table

# A truncated normal on [-2, 2] has std 0.88, so a layer drawn at
# _RELU_GAIN * fan_in**-0.5 keeps the mean square of what it feeds a ReLU.
# The top MLP's output layer takes _LOGIT_GAIN: logits then spread about
# 1 around 0 (PERF.md), the sigmoid is far from saturation and a pCTR
# moves with every layer below it.
_RELU_GAIN = 2 ** 0.5 / 0.88
_LOGIT_GAIN = 6.0


def slots(cfg: dict):
    """``(table, position)`` of each table's live slots, table-major: the
    ``sum L_t`` lookups a sample makes."""
    pooling = per_table(cfg, "pooling")
    table = np.repeat(np.arange(pooling.size), pooling)
    return table, np.arange(table.size) - (np.cumsum(pooling)
                                           - pooling)[table]


def mlp_dims(cfg: dict):
    n = (cfg["num_sparse_features"] + 1) * cfg["embedding_dim"]
    return ((cfg["num_dense_features"],) + tuple(cfg["bottom_mlp"]),
            (n,) + tuple(cfg["top_mlp"]))


def _layers(key, dims, last_gain):
    ks = jax.random.split(key, 2 * (len(dims) - 1))
    out = []
    for i, (a, b) in enumerate(zip(dims[:-1], dims[1:])):
        scale = a ** -0.5 * (last_gain if i == len(dims) - 2 else _RELU_GAIN)
        out.append({"w": jax.random.truncated_normal(ks[2 * i], -2.0, 2.0,
                                                     (a, b))
                         * jnp.float32(scale),
                    "b": 0.05 * jax.random.normal(ks[2 * i + 1], (b,))})
    return out


def weights_from_seed(key, cfg: dict):
    """Bottom and top MLPs as ``{"w": (in, out), "b": (out,)}`` layers,
    truncated normal scaled by fan-in**-0.5 and a gain (``_RELU_GAIN``,
    ``_LOGIT_GAIN``), small normal biases; cross layers ``{"v": (N, r),
    "w": (r, N), "b": (N,)}``, truncated normal scaled by fan-in**-0.5."""
    bottom, top = mlp_dims(cfg)
    k_bottom, k_top, k_cross = jax.random.split(key, 3)
    n, r = top[0], cfg["dcn_rank"]
    cross = []
    for k in jax.random.split(k_cross, cfg["dcn_layers"]):
        kv, kw, kb = jax.random.split(k, 3)
        cross.append({
            "v": jax.random.truncated_normal(kv, -2.0, 2.0, (n, r))
                 * jnp.float32(n ** -0.5),
            "w": jax.random.truncated_normal(kw, -2.0, 2.0, (r, n))
                 * jnp.float32(r ** -0.5),
            "b": 0.05 * jax.random.normal(kb, (n,))})
    return {"bottom": _layers(k_bottom, bottom, _RELU_GAIN),
            "top": _layers(k_top, top, _LOGIT_GAIN), "cross": cross}


# ---------------------------------------------------------------------------
# The reference forward
# ---------------------------------------------------------------------------

def live_rows(words, ids, rows: tuple, pooling: tuple, dim: int):
    """The rows of each sample's live slots, ``(S, sum L_t, dim)``, drawn
    from ``words``: table ``t``'s id ``r`` is flat row ``offset_t + r``."""
    table, position = slots({"num_sparse_features": len(rows),
                             "pooling": list(pooling)})
    offsets = np.cumsum(rows) - np.asarray(rows)
    flat = (ids[:, table, position].astype(jnp.uint32)
            + jnp.asarray(offsets[table], jnp.uint32))          # (S, sum L)
    return rows_from_seed(words, flat, dim)


def forward(words, weights, dense, ids, lengths, *, rows: tuple,
            pooling: tuple, precision: str):
    """pCTR ``(S,)`` of ``S`` samples: ``dense (S, F)``, ``ids (S, T,
    max L_t)`` table-local rows, ``lengths (S, T)``.  Only each table's
    ``pooling[t]`` slots are drawn (see :func:`live_rows`)."""
    table, position = slots({"num_sparse_features": len(rows),
                             "pooling": list(pooling)})
    emb = live_rows(words, ids, rows, pooling,
                    weights["bottom"][-1]["w"].shape[1])     # (S, sum L, D)
    used = jnp.asarray(position)[None, :] < lengths[:, table]
    emb = jnp.where(used[..., None], emb, 0.0)
    starts = np.cumsum(pooling) - np.asarray(pooling)
    pooled = [jnp.sum(emb[:, s:s + L], axis=1)
              for s, L in zip(starts, pooling)]
    x = _mlp(weights["bottom"], dense, precision, final_relu=True)
    x0 = jnp.concatenate([x] + pooled, axis=1)                  # (S, (T+1)D)
    xl = x0
    for c in weights["cross"]:
        low = _matmul(xl, c["v"], precision, "bi,io->bo")
        xl = x0 * (_matmul(low, c["w"], precision, "bi,io->bo") + c["b"]) + xl
    logit = _mlp(weights["top"], xl, precision, final_relu=False)[:, 0]
    return jax.nn.sigmoid(logit)


@functools.partial(jax.jit, static_argnames=("rows", "pooling", "precision"))
def _reference(words, weights, dense, ids, lengths, *, rows, pooling,
               precision):
    return forward(words, weights, dense, ids, lengths, rows=rows,
                   pooling=pooling, precision=precision)


def reference_scores(seed: int, cfg: dict, weights, dense, ids, lengths, *,
                     precision: str = "highest", block: int = 256):
    """The reference's pCTR for the given samples, ``block`` at a time on
    the default device; ``precision`` "high" is the control (three
    bfloat16 passes per matmul, ``dlrm_reference._matmul``)."""
    words = jnp.asarray(seed_words(seed))
    weights = jax.tree.map(jnp.asarray, weights)
    rows = tuple(per_table(cfg, "rows_per_table").tolist())
    pooling = tuple(per_table(cfg, "pooling").tolist())
    n = dense.shape[0]
    out = []
    for s in range(0, n, block):
        e = min(n, s + block)
        pad = block - (e - s)   # one compiled shape for every block
        d = np.pad(dense[s:e], ((0, pad), (0, 0)))
        i = np.pad(ids[s:e], ((0, pad), (0, 0), (0, 0)))
        ln = np.pad(lengths[s:e], ((0, pad), (0, 0)))
        out.append(np.asarray(_reference(
            words, weights, jnp.asarray(d), jnp.asarray(i), jnp.asarray(ln),
            rows=rows, pooling=pooling, precision=precision))[: e - s])
    return np.concatenate(out)


# ---------------------------------------------------------------------------
# Operations, from shapes
# ---------------------------------------------------------------------------

def dense_flops_per_sample(cfg: dict) -> int:
    """Operations of the MLPs and the cross net per sample: 2 per
    multiply-add, and per cross layer and input the bias, the product with
    x0 and the residual add."""
    bottom, top = mlp_dims(cfg)
    mlp = sum(2 * a * b for dims in (bottom, top)
              for a, b in zip(dims[:-1], dims[1:]))
    n, r = top[0], cfg["dcn_rank"]
    return mlp + cfg["dcn_layers"] * (2 * 2 * n * r + 3 * n)


def flops_per_sample(cfg: dict) -> int:
    """The dense operations and the pooling's multiply-adds, one per live
    slot and column."""
    pool = 2 * int(per_table(cfg, "pooling").sum()) * cfg["embedding_dim"]
    return dense_flops_per_sample(cfg) + pool
