"""Pure-jnp oracles for every kernel in this package.

These are the correctness references the Pallas kernels are swept against,
and the fallback implementation used on non-TPU backends (including the
512-device CPU dry-run, which must not trace TPU-only primitives).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _pool_rows(rows, lengths, weights, combiner, out_dtype) -> jax.Array:
    """The shared pooling tail: mask, weighted-sum einsum, combiner, cast.

    ONE definition on purpose — the stacked ``(T, R, D)`` oracle and the
    flat ``(N, D)`` oracle (the tiered cache's slot-pool layout) must run
    the numerically IDENTICAL pooling program so cached lookups stay
    bitwise-equal to the uncached oracle.
    """
    B, L = rows.shape[0], rows.shape[1]
    if lengths is None:
        mask = jnp.ones((B, L), dtype=jnp.float32)
    else:
        mask = (jnp.arange(L)[None, :] < lengths[:, None]).astype(jnp.float32)
    w = mask if weights is None else mask * weights.astype(jnp.float32)
    out = jnp.einsum(
        "bld,bl->bd", rows.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST
    )
    if combiner == "mean":
        denom = jnp.maximum(w.sum(axis=1, keepdims=True), 1.0)
        out = out / denom
    elif combiner != "sum":
        raise ValueError(f"unknown combiner {combiner!r}")
    return out.astype(out_dtype)


def embedding_bag_ref(
    table: jax.Array,          # (R, D) embedding table (or shard)
    indices: jax.Array,        # (B, L) int32 row ids
    lengths: Optional[jax.Array] = None,   # (B,) valid counts; None => all valid
    weights: Optional[jax.Array] = None,   # (B, L) per-lookup weights
    *,
    combiner: str = "sum",
) -> jax.Array:
    """Gather + pool: ``out[b] = combine_l table[indices[b, l]]``.

    Padding slots (l >= lengths[b]) contribute zero. ``combiner`` is "sum"
    or "mean" (mean divides by lengths, guarding 0).
    Returns (B, D) in the table dtype's accumulation type (f32 accum).
    """
    rows = table[indices]                                    # (B, L, D)
    return _pool_rows(rows, lengths, weights, combiner, table.dtype)


def embedding_bag_masked_ref(
    table_shard: jax.Array,    # (R_shard, D) this device's rows
    row_offset,                # scalar int — first global row id of the shard
    indices: jax.Array,        # (B, L) GLOBAL row ids
    lengths: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Row-wise-parallel partial pool: only rows owned by this shard count.

    This is the per-device compute of the paper's RW pipeline: out-of-shard
    indices pool to zero; summing the result across shards (reduce-scatter /
    psum) reconstructs the full embedding bag.
    """
    R = table_shard.shape[0]
    local = indices - row_offset
    owned = (local >= 0) & (local < R)
    safe = jnp.where(owned, local, 0)
    B, L = indices.shape
    if lengths is None:
        mask = jnp.ones((B, L), dtype=jnp.float32)
    else:
        mask = (jnp.arange(L)[None, :] < lengths[:, None]).astype(jnp.float32)
    w = mask * owned.astype(jnp.float32)
    if weights is not None:
        w = w * weights.astype(jnp.float32)
    rows = table_shard[safe]                                 # (B, L, D)
    out = jnp.einsum(
        "bld,bl->bd", rows.astype(jnp.float32), w, precision=jax.lax.Precision.HIGHEST
    )
    return out.astype(table_shard.dtype)


def embedding_bag_batched_ref(
    tables: jax.Array,         # (T, R, D) stacked tables
    indices: jax.Array,        # (T, B, L) table-local row ids
    lengths: Optional[jax.Array] = None,   # (T, B)
    weights: Optional[jax.Array] = None,   # (T, B, L)
    *,
    combiner: str = "sum",
) -> jax.Array:
    """Table-batched oracle: per-table :func:`embedding_bag_ref`, stacked.

    Returns (T, B, D) — the reference the fused TBE kernel is swept against.
    """
    T, B, L = indices.shape
    lens = lengths if lengths is not None else jnp.full((T, B), L, jnp.int32)
    if weights is None:
        fn = lambda t, i, ln: embedding_bag_ref(t, i, ln, combiner=combiner)
        return jax.vmap(fn)(tables, indices, lens)
    fn = lambda t, i, ln, w: embedding_bag_ref(t, i, ln, w, combiner=combiner)
    return jax.vmap(fn)(tables, indices, lens, weights)


def embedding_bag_batched_flat_ref(
    flat_tables: jax.Array,    # (N, D) concatenated per-table row blocks
    row_offsets: jax.Array,    # (T,) start of table t's rows in N
    indices: jax.Array,        # (T, B, L) table-local row ids
    lengths: Optional[jax.Array] = None,   # (T, B)
    weights: Optional[jax.Array] = None,   # (T, B, L)
    *,
    combiner: str = "sum",
) -> jax.Array:
    """Table-batched oracle over a FLAT heterogeneous row space.

    Table ``t``'s rows live at ``flat_tables[row_offsets[t] :]`` — ragged
    per-table row counts, the layout of the tiered cache's ``(sum S_t, D)``
    slot pool. Runs the same vmapped gather + :func:`_pool_rows` program
    as :func:`embedding_bag_batched_ref`, so equal row payloads pool to
    bitwise-equal (T, B, D) outputs.
    """
    T, B, L = indices.shape
    lens = lengths if lengths is not None else jnp.full((T, B), L, jnp.int32)

    def fn(off, i, ln, w):
        rows = flat_tables[off + i]                          # (B, L, D)
        return _pool_rows(rows, ln, w, combiner, flat_tables.dtype)

    if weights is None:
        return jax.vmap(lambda off, i, ln: fn(off, i, ln, None))(
            row_offsets, indices, lens)
    return jax.vmap(fn)(row_offsets, indices, lens, weights)


def embedding_bag_packed_ref(
    flat_tables: jax.Array,    # (N, D) concatenated per-table row blocks
    row_offsets: jax.Array,    # (T,) start of table t's rows in N
    ids: jax.Array,            # (B, S) packed table-local ids, S = sum L_t
    weights: jax.Array,        # (B, S) f32 — 0 for masked slots
    pooling: Tuple[int, ...],  # (T,) static L_t
) -> jax.Array:
    """Packed multi-hot oracle: ``out[t, b] = sum_{s in table t} w[b, s] *
    flat_tables[clip(row_offsets[t] + ids[b, s], 0, N - 1)]`` -> (T, B, D).
    """
    N = flat_tables.shape[0]
    table = np.repeat(np.arange(len(pooling)), pooling)
    rows = flat_tables[jnp.clip(ids + row_offsets[table], 0, N - 1)]
    contrib = rows.astype(jnp.float32) * weights[..., None]   # (B, S, D)
    out = jax.ops.segment_sum(contrib.transpose(1, 0, 2), table,
                              num_segments=len(pooling),
                              indices_are_sorted=True)
    return out.astype(flat_tables.dtype)


def embedding_bag_masked_batched_ref(
    table_shards: jax.Array,   # (T, R_shard, D)
    row_offset,                # scalar — first global row id of the shard
    indices: jax.Array,        # (T, B, L) GLOBAL row ids
    lengths: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
) -> jax.Array:
    """Table-batched RW-partial oracle (see embedding_bag_masked_ref)."""
    T, B, L = indices.shape
    lens = lengths if lengths is not None else jnp.full((T, B), L, jnp.int32)
    if weights is None:
        fn = lambda t, i, ln: embedding_bag_masked_ref(t, row_offset, i, ln)
        return jax.vmap(fn)(table_shards, indices, lens)
    fn = lambda t, i, ln, w: embedding_bag_masked_ref(t, row_offset, i, ln, w)
    return jax.vmap(fn)(table_shards, indices, lens, weights)


def embedding_onehot_ref(
    table: jax.Array,          # (R, D)
    indices: jax.Array,        # (B, L)
    lengths: Optional[jax.Array] = None,
) -> jax.Array:
    """One-hot-matmul formulation (MXU-friendly alternative for tiny R).

    out = onehot(indices) @ table, summed over L. Used to cross-check the
    gather formulation and as the R-small fast path.
    """
    B, L = indices.shape
    R = table.shape[0]
    oh = jax.nn.one_hot(indices, R, dtype=table.dtype)       # (B, L, R)
    if lengths is not None:
        mask = (jnp.arange(L)[None, :] < lengths[:, None]).astype(table.dtype)
        oh = oh * mask[:, :, None]
    return jnp.einsum("blr,rd->bd", oh, table)
