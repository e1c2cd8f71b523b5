"""Public kernel ops: backend dispatch + differentiability.

``embedding_bag(...)`` (single table) and ``embedding_bag_batched(...)``
(all T stacked tables at once) are the entry points used by the rest of
the framework. ``mode`` selects:

  * "reference" — pure-jnp oracle (ref.py). Default on CPU and for the
    512-device dry-run (TPU Pallas primitives must not be traced there).
  * "pallas"    — the TPU kernel (embedding_gather.py).
  * "interpret" — the TPU kernel executed by the Pallas interpreter on CPU
    (correctness validation path used by the test suite).
  * "auto"      — "pallas" on TPU backends, else "reference".

The TPU kernel compiles for (N, 128) tables of a 32-bit dtype (see
embedding_gather.py); "pallas" raises for other shapes rather than
substituting the reference.

The batched ops additionally take ``fused``: True (default) runs the
table-batched TBE kernel — ONE ``pallas_call`` for all tables; False
falls back to calling the single-table kernel once per table (T launches),
kept as the A/B baseline for the benchmark sweep.

The Pallas forwards are wrapped in ``custom_vjp``s whose backward is the
XLA scatter-add (segment-sum) — gathers' transpose — so both kernel paths
are trainable (needed for the LM vocab-embedding integration and DLRM
training). The TBE backward scatter-adds into the FLATTENED (T*R, D) row
space with the same per-table offsets as the forward.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.jagged import packed_weights
from repro.kernels import ref as _ref
from repro.kernels.embedding_gather import (
    gather_pool_pallas,
    gather_pool_tbe_flat_pallas,
    gather_pool_tbe_multihot_pallas,
    gather_pool_tbe_pallas,
)


def _resolve_mode(mode: str) -> str:
    if mode != "auto":
        return mode
    return "pallas" if jax.default_backend() == "tpu" else "reference"


def onesided_interpret(mode: str) -> bool:
    """How the one-sided RDMA collectives run under kernel ``mode``:
    compiled (False) or in the Pallas interpreter (True).  They have no
    jnp reference, so a mode that resolves to "reference" raises."""
    mode = _resolve_mode(mode)
    if mode not in ("pallas", "interpret"):
        raise ValueError(
            f"the one-sided backend runs Pallas RDMA kernels: kernel_mode "
            f"must be 'pallas' (TPU) or 'interpret' (CPU), got {mode!r}")
    return mode == "interpret"


def _effective_weights(indices, lengths, weights):
    """Padding/length mask times optional weights. Rank-generic: serves the
    single-table (B, L)/(B,) and the batched (T, B, L)/(T, B) layouts."""
    L = indices.shape[-1]
    if lengths is None:
        mask = jnp.ones(indices.shape, jnp.float32)
    else:
        mask = (jnp.arange(L) < lengths[..., None]).astype(jnp.float32)
    if weights is not None:
        mask = mask * weights.astype(jnp.float32)
    return mask


def _premask_rw(table_rows, row_offset, indices, lengths, weights):
    """RW pre-masking shared by both kernel layouts: map out-of-shard
    GLOBAL ids to (local row 0, weight 0) so one gather kernel serves the
    single-device and row-wise-parallel paths."""
    local = indices - row_offset
    owned = (local >= 0) & (local < table_rows)
    safe = jnp.where(owned, local, 0).astype(jnp.int32)
    eff_w = _effective_weights(indices, lengths, weights) \
        * owned.astype(jnp.float32)
    return safe, eff_w


# --- differentiable pallas path --------------------------------------------
# ``interpret`` is a nondiff/static argnum: it must stay a Python bool all
# the way down to the pallas_call even when the op is called under jit.

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pooled_lookup_pallas(table, indices, eff_w, interpret):
    return gather_pool_pallas(table, indices, eff_w, interpret=interpret)


def _pooled_fwd(table, indices, eff_w, interpret):
    out = gather_pool_pallas(table, indices, eff_w, interpret=interpret)
    return out, (table, indices, eff_w)


def _pooled_bwd(interpret, res, g):
    table, indices, eff_w = res
    R, D = table.shape
    # d table[r] = sum_{b,l: idx==r} w[b,l] * g[b]  — scatter-add (gather^T)
    flat_idx = indices.reshape(-1)
    contrib = (eff_w[..., None] * g[:, None, :]).reshape(-1, D)
    d_table = jax.ops.segment_sum(contrib, flat_idx, num_segments=R)
    # d eff_w[b,l] = <table[idx[b,l]], g[b]>
    d_w = jnp.einsum("bld,bd->bl", table[indices].astype(jnp.float32), g)
    return d_table.astype(table.dtype), None, d_w


_pooled_lookup_pallas.defvjp(_pooled_fwd, _pooled_bwd)


# --- differentiable fused (table-batched) path ------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _pooled_lookup_tbe(tables, indices, eff_w, interpret):
    return gather_pool_tbe_pallas(tables, indices, eff_w, interpret=interpret)


def _tbe_fwd(tables, indices, eff_w, interpret):
    out = gather_pool_tbe_pallas(tables, indices, eff_w, interpret=interpret)
    return out, (tables, indices, eff_w)


def _tbe_bwd(interpret, res, g):
    tables, indices, eff_w = res
    T, R, D = tables.shape
    # scatter-add into the flattened (T*R, D) row space — the transpose of
    # the kernel's offset-adjusted gather
    offs = (jnp.arange(T, dtype=indices.dtype) * R)[:, None, None]
    flat_idx = (indices + offs).reshape(-1)
    contrib = (eff_w[..., None] * g[:, :, None, :]).reshape(-1, D)
    d_flat = jax.ops.segment_sum(contrib, flat_idx, num_segments=T * R)
    # d eff_w[t,b,l] = <tables[t, idx[t,b,l]], g[t,b]>
    rows = tables.reshape(T * R, D)[flat_idx].reshape(*indices.shape, D)
    d_w = jnp.einsum("tbld,tbd->tbl", rows.astype(jnp.float32), g)
    return d_flat.reshape(T, R, D).astype(tables.dtype), None, d_w


_pooled_lookup_tbe.defvjp(_tbe_fwd, _tbe_bwd)


# --- differentiable fused FLAT (heterogeneous row space) path ----------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _pooled_lookup_tbe_flat(flat_tables, row_offsets, indices, eff_w,
                            interpret):
    return gather_pool_tbe_flat_pallas(
        flat_tables, row_offsets, indices, eff_w, interpret=interpret)


def _tbe_flat_fwd(flat_tables, row_offsets, indices, eff_w, interpret):
    out = gather_pool_tbe_flat_pallas(
        flat_tables, row_offsets, indices, eff_w, interpret=interpret)
    return out, (flat_tables, row_offsets, indices, eff_w)


def _tbe_flat_bwd(interpret, res, g):
    flat_tables, row_offsets, indices, eff_w = res
    N, D = flat_tables.shape
    # scatter-add into the ragged flat (N, D) row space — the transpose of
    # the kernel's offset-adjusted gather
    offs = row_offsets.astype(indices.dtype)[:, None, None]
    flat_idx = (indices + offs).reshape(-1)
    contrib = (eff_w[..., None] * g[:, :, None, :]).reshape(-1, D)
    d_flat = jax.ops.segment_sum(contrib, flat_idx, num_segments=N)
    # d eff_w[t,b,l] = <flat_tables[off[t] + idx[t,b,l]], g[t,b]>
    rows = flat_tables[flat_idx].reshape(*indices.shape, D)
    d_w = jnp.einsum("tbld,tbd->tbl", rows.astype(jnp.float32), g)
    return d_flat.astype(flat_tables.dtype), None, None, d_w


_pooled_lookup_tbe_flat.defvjp(_tbe_flat_fwd, _tbe_flat_bwd)


# --- packed multi-hot path: inference only --------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _pooled_lookup_multihot(flat_tables, row_offsets, ids, eff_w, pooling,
                            interpret):
    return gather_pool_tbe_multihot_pallas(
        flat_tables, row_offsets, ids, eff_w, pooling=pooling,
        interpret=interpret)


def _multihot_fwd(flat_tables, row_offsets, ids, eff_w, pooling, interpret):
    return _pooled_lookup_multihot(flat_tables, row_offsets, ids, eff_w,
                                   pooling, interpret), None


def _multihot_bwd(pooling, interpret, res, g):
    raise NotImplementedError(
        "the packed multi-hot TBE kernel (embedding_bag_packed) serves "
        "inference only: it has no backward pass; train through "
        "embedding_bag_batched_flat or kernel mode 'reference'")


_pooled_lookup_multihot.defvjp(_multihot_fwd, _multihot_bwd)


def _pooled_lookup_per_table(tables, indices, eff_w, interpret):
    """Unfused baseline: one single-table kernel call per table (T
    launches). Not a vmap: the TPU lowering cannot batch a kernel whose
    table stays in HBM (``memory_space=ANY``)."""
    return jnp.stack([
        _pooled_lookup_pallas(tables[t], indices[t], eff_w[t], interpret)
        for t in range(tables.shape[0])])


# --- public API --------------------------------------------------------------

def embedding_bag(
    table: jax.Array,
    indices: jax.Array,
    lengths: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
    *,
    combiner: str = "sum",
    mode: str = "auto",
) -> jax.Array:
    """Pooled embedding lookup, ``(R, D) x (B, L) -> (B, D)``."""
    mode = _resolve_mode(mode)
    if mode == "reference":
        return _ref.embedding_bag_ref(
            table, indices, lengths, weights, combiner=combiner
        )
    if mode not in ("pallas", "interpret"):
        raise ValueError(f"unknown mode {mode!r}")
    eff_w = _effective_weights(indices, lengths, weights)
    out = _pooled_lookup_pallas(table, indices, eff_w, mode == "interpret")
    if combiner == "mean":
        denom = jnp.maximum(eff_w.sum(axis=1, keepdims=True), 1.0)
        out = out / denom
    elif combiner != "sum":
        raise ValueError(f"unknown combiner {combiner!r}")
    return out.astype(table.dtype)


def embedding_bag_rw_partial(
    table_shard: jax.Array,
    row_offset,
    indices: jax.Array,
    lengths: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
    *,
    mode: str = "auto",
) -> jax.Array:
    """Row-wise-parallel partial pool (paper §4.2 phase 2).

    ``indices`` are GLOBAL ids; rows outside ``[row_offset, row_offset+R)``
    contribute zero. Summing across shards (psum / reduce-scatter)
    reconstructs the full pooled output. Out-of-shard lookups are remapped
    to (row 0, weight 0) so the same gather kernel handles both paths.
    """
    mode = _resolve_mode(mode)
    if mode == "reference":
        return _ref.embedding_bag_masked_ref(
            table_shard, row_offset, indices, lengths, weights
        )
    safe, eff_w = _premask_rw(
        table_shard.shape[0], row_offset, indices, lengths, weights)
    out = _pooled_lookup_pallas(table_shard, safe, eff_w, mode == "interpret")
    return out.astype(table_shard.dtype)


# --- table-batched public API -----------------------------------------------

def embedding_bag_batched(
    tables: jax.Array,         # (T, R, D)
    indices: jax.Array,        # (T, B, L) table-local ids
    lengths: Optional[jax.Array] = None,   # (T, B)
    weights: Optional[jax.Array] = None,   # (T, B, L)
    *,
    combiner: str = "sum",
    mode: str = "auto",
    fused: bool = True,
) -> jax.Array:
    """Pooled lookup over ALL tables, ``(T,R,D) x (T,B,L) -> (T,B,D)``.

    ``fused=True`` executes one TBE ``pallas_call`` for every table;
    ``fused=False`` calls the single-table kernel per table (T launches).
    """
    mode = _resolve_mode(mode)
    if mode == "reference":
        return _ref.embedding_bag_batched_ref(
            tables, indices, lengths, weights, combiner=combiner
        )
    if mode not in ("pallas", "interpret"):
        raise ValueError(f"unknown mode {mode!r}")
    eff_w = _effective_weights(indices, lengths, weights)
    lookup = _pooled_lookup_tbe if fused else _pooled_lookup_per_table
    out = lookup(tables, indices, eff_w, mode == "interpret")
    if combiner == "mean":
        denom = jnp.maximum(eff_w.sum(axis=2, keepdims=True), 1.0)
        out = out / denom
    elif combiner != "sum":
        raise ValueError(f"unknown combiner {combiner!r}")
    return out.astype(tables.dtype)


def embedding_bag_batched_flat(
    flat_tables: jax.Array,    # (N, D) concatenated per-table row blocks
    row_offsets: jax.Array,    # (T,) int32 — start of table t's rows in N
    indices: jax.Array,        # (T, B, L) table-local ids, in [0, S_t)
    lengths: Optional[jax.Array] = None,   # (T, B)
    weights: Optional[jax.Array] = None,   # (T, B, L)
    *,
    combiner: str = "sum",
    mode: str = "auto",
) -> jax.Array:
    """Pooled lookup over a FLAT heterogeneous row space -> (T, B, D).

    ``out[t, b] = pool_l flat_tables[row_offsets[t] + indices[t, b, l]]``

    The entry point the tiered cache's ``(sum S_t, D)`` slot pool is
    served from: per-table row counts are ragged, described only by the
    scalar-prefetched ``row_offsets`` vector. Always ONE fused TBE
    ``pallas_call`` — there is no per-table unfused fallback, because a
    ragged pool has no ``(T, S, D)`` rectangle to vmap over.
    """
    mode = _resolve_mode(mode)
    if mode == "reference":
        return _ref.embedding_bag_batched_flat_ref(
            flat_tables, row_offsets, indices, lengths, weights,
            combiner=combiner
        )
    if mode not in ("pallas", "interpret"):
        raise ValueError(f"unknown mode {mode!r}")
    eff_w = _effective_weights(indices, lengths, weights)
    out = _pooled_lookup_tbe_flat(
        flat_tables, row_offsets, indices, eff_w, mode == "interpret")
    if combiner == "mean":
        denom = jnp.maximum(eff_w.sum(axis=2, keepdims=True), 1.0)
        out = out / denom
    elif combiner != "sum":
        raise ValueError(f"unknown combiner {combiner!r}")
    return out.astype(flat_tables.dtype)


def embedding_bag_packed(
    flat_tables: jax.Array,    # (N, D) concatenated per-table row blocks
    row_offsets: jax.Array,    # (T,) int32 — start of table t's rows in N
    ids: jax.Array,            # (B, S) packed table-local ids, S = sum L_t
    lengths: jax.Array,        # (B, T) valid lookups, <= L_t
    pooling: Tuple[int, ...],  # (T,) static L_t
    *,
    mode: str = "auto",
) -> jax.Array:
    """Sum-pooled lookup of packed multi-hot ids (``core.jagged.
    PackedBatch``) over a flat row space -> (T, B, D).

    ``out[t, b] = sum_{l < lengths[b, t]} flat_tables[row_offsets[t] +
    ids[b, s_t + l]]``: table ``t`` owns the ``pooling[t]`` slots from
    ``s_t = pooling[0] + ... + pooling[t - 1]``.  One fused
    ``pallas_call`` that starts one row DMA per packed slot; no backward.
    """
    mode = _resolve_mode(mode)
    eff_w = packed_weights(lengths, pooling)
    if mode == "reference":
        return _ref.embedding_bag_packed_ref(
            flat_tables, row_offsets, ids, eff_w, pooling)
    if mode not in ("pallas", "interpret"):
        raise ValueError(f"unknown mode {mode!r}")
    out = _pooled_lookup_multihot(flat_tables, row_offsets, ids, eff_w,
                                  tuple(pooling), mode == "interpret")
    return out.astype(flat_tables.dtype)


def embedding_bag_rw_partial_batched(
    table_shards: jax.Array,   # (T, R_shard, D) this device's row slices
    row_offset,
    indices: jax.Array,        # (T, B, L) GLOBAL row ids
    lengths: Optional[jax.Array] = None,
    weights: Optional[jax.Array] = None,
    *,
    mode: str = "auto",
    fused: bool = True,
) -> jax.Array:
    """Table-batched row-wise-parallel partial pool -> (T, B, D).

    The batched analogue of :func:`embedding_bag_rw_partial`: out-of-shard
    ids are pre-masked to (local row 0, weight 0), then ONE fused TBE call
    pools every table's owned rows (the shard's flat row space is
    ``(T * R_shard, D)`` with ``row_offsets[t] = t * R_shard``).
    """
    mode = _resolve_mode(mode)
    if mode == "reference":
        return _ref.embedding_bag_masked_batched_ref(
            table_shards, row_offset, indices, lengths, weights
        )
    if mode not in ("pallas", "interpret"):
        raise ValueError(f"unknown mode {mode!r}")
    safe, eff_w = _premask_rw(
        table_shards.shape[1], row_offset, indices, lengths, weights)
    lookup = _pooled_lookup_tbe if fused else _pooled_lookup_per_table
    out = lookup(table_shards, safe, eff_w, mode == "interpret")
    return out.astype(table_shards.dtype)


# ---------------------------------------------------------------------------
# Kernel contracts (audited by repro.analysis)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import KernelContract  # noqa: E402

# The paper's structural claims for this module, as declarative specs:
# tests, sweeps, and `python -m repro.analysis --contracts` all audit
# against THESE objects instead of re-asserting launch counts ad hoc.
KERNEL_CONTRACTS = {
    "tbe_fused": KernelContract(
        name="kernels.ops.embedding_bag_batched[fused]",
        note="ALL T tables' gather+pool execute in ONE pallas_call "
             "(flattened (T*R, D) row space, scalar-prefetched offsets)"),
    "tbe_flat": KernelContract(
        name="kernels.ops.embedding_bag_batched_flat",
        note="the flat (sum S_t, D) slot-pool TBE stays one launch"),
    "tbe_multihot": KernelContract(
        name="kernels.ops.embedding_bag_packed",
        note="packed multi-hot lookups (a static L_t per table) stay one "
             "launch over the flat (sum R_t, D) row space"),
    "rw_partial_fused": KernelContract(
        name="kernels.ops.embedding_bag_rw_partial_batched[fused]",
        note="the row-wise-sharded partial pool stays one launch; "
             "reduction across shards happens OUTSIDE the kernel"),
}
