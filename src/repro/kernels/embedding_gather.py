"""Pallas TPU kernel for the embedding-bag gather+pool phase.

The paper's phase-2 "gather kernel" (§4.3) retrieves ``L`` rows per sample
from an HBM-resident table and pools (weighted-sums) them. On GPU this is a
CUDA gather; on TPU it is a manual row DMA from HBM into VMEM followed by a
vector weighted sum.

ONE kernel, table-batched (TBE, FBGEMM-style): ``gather_pool_tbe_flat_pallas``
executes the lookups of ALL ``T`` tables in ONE ``pallas_call`` over a FLAT
heterogeneous row space ``(N, D)``; table ``t``'s rows start at
``row_offsets[t]``, so ragged per-table row counts (the tiered cache's
``(sum S_t, D)`` slot pool) only need a different offsets vector. The
uniform ``(T, R, D)`` stack is ``row_offsets[t] = t * R``
(``gather_pool_tbe_pallas``) and the single table is the ``T = 1`` call
(``gather_pool_pallas``). The paper sweeps #tables (§5); per-table
launches pay T separate grid setups and pipeline drains, fusing removes
them.

Design (what the v5e compiler accepts):

  * Grid ``(T, B_pad / Bt)``: one step pools a tile of ``Bt`` samples of
    one table into an ``(Bt, D)`` f32 output block. Both axes are
    "parallel"; no step accumulates into another's block.
  * The table stays in HBM (``memory_space=pl.ANY``). Each step starts
    ``Bt * L`` single-row DMAs ``table[row_offsets[t] + idx[t, b, l]]``
    into a VMEM scratch laid out ``(L, Bt, D)``, all on one DMA
    semaphore, then waits ONCE with a descriptor spanning the whole
    scratch: the semaphore counts the bytes that landed, so that wait
    returns only when every row is in.
  * At serving shapes the scalar core, not HBM, sets the pace: each row
    costs an SMEM id load, a clamp and a DMA start. The start loop is a
    ``fori_loop`` over groups of ``g`` samples with a static inner loop
    over the group's ``g * L`` copies, so the scratch slot ``(l, b)``
    and the id's SMEM offset ``b * L + l`` need no per-row div or mod.
    ``g`` follows from the shapes: enough samples that a group starts
    about ``_COPIES_PER_ITER`` copies (one sample at L=32, 32 at the
    one-hot L=1), and a divisor of ``Bt``. On a TPU v5e at T=26,
    B=1024, L=32 this took a row from 35.7 ns (a wait per row, per-row
    div/mod) to 15.7 ns; HBM's floor is ~0.6 ns.
  * Ids travel per tile: a ``(Bt * L,)`` int32 SMEM block of the
    ``(T, nb, 1, Bt * L)`` id array, pipelined by the grid. Prefetching
    the whole ``(T * B, L)`` id array into SMEM does not fit at serving
    batch sizes (1 MiB of SMEM; the 2-D array pads to 128 lanes, so
    T=26, L=32, B=128 already needs 1.7 MB).
  * Weights are a ``(Bt, L)`` f32 VMEM block; the pool is the static loop
    ``acc += rows[l] * w[:, l]`` over ``l`` — an f32 accumulate in the
    same ``l`` order for every table layout, so the stacked-table and the
    slot-pool paths pool equal rows to bitwise-equal outputs.
  * Row ids are clamped into ``[0, N)`` in the kernel: a DMA never reads
    outside the table, whatever a padded slot holds.

The compiler refuses single-row slices of an HBM table unless its rows
are exactly one 128-lane tile of a 32-bit dtype (``D = 128``, f32/int32):
wider or packed (bf16) rows are tiled (8, 128) / (16, 128) in HBM, and
narrower rows are not lane-aligned. ``interpret=False`` therefore raises
for other shapes instead of compiling a kernel that the chip would
refuse; the interpreter (CPU tests) takes any shape. A one-row (1, D)
BlockSpec per grid step — this module's earlier design — is refused
outright ("last two dimensions of your block shape [must be] divisible by
8 and 128").

VMEM per step: the ``(L, Bt, D)`` row scratch (``Bt`` is chosen so it
stays <= 4 MiB: 2 MiB at L=32, D=128, Bt=128), plus the double-buffered
``(Bt, L)`` weights and ``(Bt, D)`` output blocks.

Multi-hot features whose pooling ``L_t`` differs per table (DLRMv2: 1 to
100) have their own kernel, ``gather_pool_tbe_multihot_pallas``: packed
ids with only each table's ``L_t`` slots, one row DMA per packed slot.
Padding every table to the largest ``L_t`` would start 12.1x the row DMAs
at DLRMv2's sizes.

The RW-masked (row-wise-parallel, paper §4.2) variant is expressed by
pre-masking: ops.py maps out-of-shard ids to local row 0 with weight 0,
so the same kernel serves the single-device and the sharded paths (the
shard's flat row space is ``(T * R/E, D)`` with ``row_offsets[t] =
t * R/E``).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_ROW_SCRATCH_BYTES = 4 << 20   # VMEM budget for one step's (L, Bt, D) rows
_MAX_TILE = 128                # samples per grid step
_COPIES_PER_ITER = 32          # row DMAs started per iteration of the start loop


def _tile(B: int, L: int, D: int, itemsize: int) -> int:
    """Samples per grid step: a multiple of 8 (sublanes), at most
    ``_MAX_TILE``, with the row scratch inside its VMEM budget."""
    fit = max(8, _ROW_SCRATCH_BYTES // (L * D * itemsize) // 8 * 8)
    return min(_MAX_TILE, fit, -(-B // 8) * 8)


def _tbe_kernel(off_ref, ids_ref, w_ref, table_hbm, out_ref, rows, sem, *,
                L: int, tile: int, num_rows: int):
    """One grid step: pool ``tile`` samples of table ``program_id(0)``."""
    base = off_ref[pl.program_id(0)]
    # samples per iteration: about _COPIES_PER_ITER copies, dividing tile
    g = math.gcd(tile, max(1, _COPIES_PER_ITER // L))

    def start(i, carry):
        b0, k0 = i * g, i * (g * L)   # first sample, its first id
        for j in range(g):
            for l in range(L):
                row = jnp.clip(base + ids_ref[k0 + (j * L + l)],
                               0, num_rows - 1)
                pltpu.make_async_copy(table_hbm.at[pl.ds(row, 1)],
                                      rows.at[l, pl.ds(b0 + j, 1)],
                                      sem).start()
        return carry

    jax.lax.fori_loop(0, tile // g, start, 0)
    # the semaphore counts bytes landed: a wait sized to the whole
    # scratch returns once all tile * L rows are in
    pltpu.make_async_copy(rows, rows, sem).wait()
    acc = jnp.zeros(out_ref.shape, jnp.float32)
    for l in range(L):
        acc = acc + rows[l].astype(jnp.float32) * w_ref[:, l:l + 1]
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pool_tbe_flat_pallas(
    flat_tables: jax.Array,   # (N, D) concatenated per-table row blocks
    row_offsets: jax.Array,   # (T,) int32 — start of table t's rows in N
    indices: jax.Array,       # (T, B, L) int32 TABLE-LOCAL ids
    weights: jax.Array,       # (T, B, L) f32 — 0 for masked/padded slots
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused pooled lookup over a FLAT heterogeneous row space.

    ``out[t, b] = sum_l weights[t,b,l] * flat_tables[row_offsets[t] +
    indices[t,b,l]]`` — the fully general form of the TBE kernel: tables
    (or slot pools) may have RAGGED per-table row counts, described only
    by the scalar-prefetched ``row_offsets`` vector. This is what the
    tiered cache's ``(sum S_t, D)`` slot pool addresses with
    ``row_offsets = cumsum(S_t)[:-1]``.

    Returns (T, B, D) f32 (accumulation dtype; callers cast). See the
    module docstring for the grid / DMA design.
    """
    N, D = flat_tables.shape
    T, B, L = indices.shape
    if row_offsets.shape != (T,):
        raise ValueError(
            f"row_offsets must be (T,)=({T},), got {row_offsets.shape}")
    itemsize = jnp.dtype(flat_tables.dtype).itemsize
    if not interpret and (D != 128 or itemsize != 4):
        raise NotImplementedError(
            f"the TPU gather kernel needs (N, 128) rows of a 32-bit dtype "
            f"(the compiler refuses one-row DMAs from other HBM tilings); "
            f"got D={D}, {flat_tables.dtype}")
    tile = _tile(B, L, D, itemsize)
    nb = -(-B // tile)
    pad = nb * tile - B
    if pad:   # padded samples: row 0, weight 0 -> sliced off below
        indices = jnp.pad(indices, ((0, 0), (0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, 0), (0, pad), (0, 0)))
    ids = indices.astype(jnp.int32).reshape(T, nb, 1, tile * L)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,   # row_offsets (T,)
        grid=(T, nb),
        in_specs=[
            pl.BlockSpec((None, None, None, tile * L),
                         lambda t, i, off: (t, i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((None, tile, L), lambda t, i, off: (t, i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((None, tile, D), lambda t, i, off: (t, i, 0)),
        scratch_shapes=[pltpu.VMEM((L, tile, D), flat_tables.dtype),
                        pltpu.SemaphoreType.DMA(())],
    )
    out = pl.pallas_call(
        functools.partial(_tbe_kernel, L=L, tile=tile, num_rows=N),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, nb * tile, D), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(row_offsets.astype(jnp.int32), ids, weights.astype(jnp.float32),
      flat_tables)
    return out[:, :B]


def _tbe_multihot_kernel(ids_ref, w_ref, table_hbm, out_ref, rows, sem, *,
                         pooling, tile: int):
    """One grid step: pool ``tile`` samples of every table, table ``t``
    from its ``pooling[t]`` packed slots."""
    S = sum(pooling)
    g = math.gcd(tile, max(1, _COPIES_PER_ITER // S))

    def start(i, carry):
        b0, k0 = i * g, i * (g * S)   # first sample, its first id
        for j in range(g):
            for s in range(S):
                pltpu.make_async_copy(
                    table_hbm.at[pl.ds(ids_ref[k0 + (j * S + s)], 1)],
                    rows.at[s, pl.ds(b0 + j, 1)], sem).start()
        return carry

    jax.lax.fori_loop(0, tile // g, start, 0)
    pltpu.make_async_copy(rows, rows, sem).wait()
    s = 0
    for t, L in enumerate(pooling):
        acc = jnp.zeros(out_ref.shape[1:], jnp.float32)
        for _ in range(L):
            acc = acc + rows[s].astype(jnp.float32) * w_ref[:, s:s + 1]
            s += 1
        out_ref[t] = acc


@functools.partial(jax.jit, static_argnames=("pooling", "interpret"))
def gather_pool_tbe_multihot_pallas(
    flat_tables: jax.Array,   # (N, D) concatenated per-table row blocks
    row_offsets: jax.Array,   # (T,) int32 — start of table t's rows in N
    ids: jax.Array,           # (B, S) int32 packed TABLE-LOCAL ids
    weights: jax.Array,       # (B, S) f32 — 0 for masked slots
    *,
    pooling: tuple,           # (T,) static L_t, S = sum L_t
    interpret: bool = False,
) -> jax.Array:
    """Fused pooled lookup of multi-hot features whose pooling ``L_t`` is
    fixed per table, over a flat row space.

    ``out[t, b] = sum_{l < L_t} weights[b, s_t + l] * flat_tables[
    row_offsets[t] + ids[b, s_t + l]]``, ``s_t = L_0 + ... + L_{t-1}``: the
    ids hold each table's ``L_t`` slots and no padding to the largest.
    Returns (T, B, D) f32.

    The grid walks tiles of ``Bt`` samples; a step starts ``Bt * S`` row
    DMAs into a ``(S, Bt, D)`` scratch, waits once, and pools every table
    into its ``(T, Bt, D)`` output block.  So a micro-batch starts one row
    DMA per packed slot (plus the last tile's padding): at DLRMv2's
    multi-hot sizes (``S`` = 214, up to 100 per table) that is 12.1x
    fewer than one kernel pass at ``L = 100``.  Row ids are offset and
    clamped into ``[0, N)`` before the kernel, so a DMA never reads
    outside the table and the start loop does one SMEM load per row.
    """
    N, D = flat_tables.shape
    B, S = ids.shape
    T = len(pooling)
    if row_offsets.shape != (T,) or sum(pooling) != S:
        raise ValueError(
            f"row_offsets {row_offsets.shape} and pooling (sum "
            f"{sum(pooling)}) must match T={T} tables and S={S} slots")
    itemsize = jnp.dtype(flat_tables.dtype).itemsize
    if not interpret and (D != 128 or itemsize != 4):
        raise NotImplementedError(
            f"the TPU gather kernel needs (N, 128) rows of a 32-bit dtype "
            f"(the compiler refuses one-row DMAs from other HBM tilings); "
            f"got D={D}, {flat_tables.dtype}")
    table = np.repeat(np.arange(T), pooling)
    rows = jnp.clip(ids.astype(jnp.int32)
                    + row_offsets.astype(jnp.int32)[table], 0, N - 1)
    tile = _tile(B, S, D, itemsize)
    nb = -(-B // tile)
    pad = nb * tile - B
    weights = weights.astype(jnp.float32)
    if pad:   # padded samples: row 0, weight 0 -> sliced off below
        rows = jnp.pad(rows, ((0, pad), (0, 0)))
        weights = jnp.pad(weights, ((0, pad), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_tbe_multihot_kernel, pooling=pooling, tile=tile),
        grid=(nb,),
        in_specs=[
            pl.BlockSpec((None, None, tile * S), lambda i: (i, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, S), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((T, tile, D), lambda i: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((T, nb * tile, D), jnp.float32),
        scratch_shapes=[pltpu.VMEM((S, tile, D), flat_tables.dtype),
                        pltpu.SemaphoreType.DMA(())],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(rows.reshape(nb, 1, tile * S), weights, flat_tables)
    return out[:, :B]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pool_tbe_pallas(
    tables: jax.Array,    # (T, R, D) stacked tables
    indices: jax.Array,   # (T, B, L) int32 TABLE-LOCAL ids — in [0, R)
    weights: jax.Array,   # (T, B, L) f32 — 0 for masked/padded slots
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused pooled lookup over all tables, ONE ``pallas_call``.

    ``out[t, b] = sum_l weights[t,b,l] * tables[t, indices[t,b,l]]`` —
    the stacked tables are one ``(T*R, D)`` flat row space with
    ``row_offsets[t] = t * R``. Returns (T, B, D) f32.
    """
    T, R, D = tables.shape
    Ti = indices.shape[0]
    if Ti != T:
        raise ValueError(f"tables T={T} != indices T={Ti}")
    return gather_pool_tbe_flat_pallas(
        tables.reshape(T * R, D),
        jnp.arange(T, dtype=jnp.int32) * R,
        indices, weights, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_pool_pallas(
    table: jax.Array,     # (R, D)
    indices: jax.Array,   # (B, L) int32 — must be in [0, R)
    weights: jax.Array,   # (B, L) f32 — 0 for masked/padded slots
    *,
    interpret: bool = False,
) -> jax.Array:
    """Single-table pooled lookup, the ``T = 1`` call of the TBE kernel:
    ``out[b] = sum_l weights[b,l] * table[indices[b,l]]`` -> (B, D) f32.
    """
    return gather_pool_tbe_flat_pallas(
        table, jnp.zeros((1,), jnp.int32), indices[None], weights[None],
        interpret=interpret)[0]
