"""Jagged sparse-feature batches — the paper's (indices, lengths) input format.

The paper (§4.2) describes embedding-bag inputs as two arrays per table:

  indices:  flat array of row ids to look up, e.g. [14, 29, 12, 6, 13]
  lengths:  per-sample pooling sizes,          e.g. [2, 1, 0, 3, 2]

For a JIT-compiled TPU pipeline we need static shapes, so the on-device
representation is *padded-dense*: ``indices (T, B, L)`` + ``lengths (T, B)``
where ``L`` is the max pooling factor and slots ``>= lengths`` are masked.
Host-side CSR <-> padded conversion lives here too (used by the data
pipeline), along with hypothesis-tested invariants.

The paper's experimental assumption (§4.3) — constant pooling size across
the batch — corresponds to ``lengths == L`` everywhere; the framework
supports the general variable-length case.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class JaggedBatch:
    """A batch of multi-hot categorical features for ``T`` embedding tables.

    Attributes:
      indices: int32 (T, B, L) — row ids, padded with 0 beyond ``lengths``.
      lengths: int32 (T, B) — valid lookups per sample (0 <= lengths <= L).
      weights: optional float (T, B, L) — per-lookup weights (weighted pooling).
    """

    indices: jax.Array
    lengths: jax.Array
    weights: Optional[jax.Array] = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (self.indices, self.lengths, self.weights), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    # -- derived shapes ------------------------------------------------------
    @property
    def num_tables(self) -> int:
        return self.indices.shape[0]

    @property
    def batch_size(self) -> int:
        return self.indices.shape[1]

    @property
    def max_pooling(self) -> int:
        return self.indices.shape[2]

    def mask(self) -> jax.Array:
        """Boolean (T, B, L): True where the lookup slot is valid."""
        L = self.max_pooling
        return jnp.arange(L)[None, None, :] < self.lengths[:, :, None]

    def effective_weights(self) -> jax.Array:
        """Float (T, B, L): pooling weights with padding zeroed."""
        m = self.mask()
        if self.weights is None:
            return m.astype(jnp.float32)
        return jnp.where(m, self.weights, 0.0).astype(jnp.float32)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedBatch:
    """Multi-hot lookups of ``T`` tables whose pooling ``L_t`` is fixed
    per table, packed sample-major: only each table's ``L_t`` slots exist,
    so a table of ``L_t = 1`` beside one of ``L_t = 100`` costs one slot,
    not a hundred.

    Attributes:
      ids: int32 (B, S), ``S = sum L_t`` — table-local row ids; table
        ``t``'s slots are columns ``[s_t, s_t + L_t)``, ``s_t = L_0 + ...
        + L_{t-1}``.
      lengths: int32 (B, T) — valid lookups per sample and table
        (``0 <= lengths[:, t] <= L_t``); the slots past them are masked.
    """

    ids: jax.Array
    lengths: jax.Array

    def tree_flatten(self):
        return (self.ids, self.lengths), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def batch_size(self) -> int:
        return self.ids.shape[0]


def packed_slots(pooling) -> tuple:
    """``(table, position)`` int arrays ``(S,)`` of the packed layout's
    slots, table-major: slot ``s`` is lookup ``position[s]`` of table
    ``table[s]``."""
    pooling = np.asarray(pooling, np.int64)
    table = np.repeat(np.arange(pooling.size), pooling)
    starts = np.cumsum(pooling) - pooling
    return table, np.arange(table.size) - starts[table]


def packed_weights(lengths: jax.Array, pooling) -> jax.Array:
    """Float (B, S): 1 where a packed slot is within its table's length."""
    table, position = packed_slots(pooling)
    return (position[None, :] < lengths[:, table]).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Host-side CSR (paper format) <-> padded-dense conversions
# ---------------------------------------------------------------------------

def csr_to_padded(
    indices: np.ndarray, lengths: np.ndarray, max_pooling: Optional[int] = None
):
    """Convert the paper's flat (indices, lengths) format to padded (B, L).

    Args:
      indices: 1-D flat lookup ids, ``len == lengths.sum()``.
      lengths: 1-D per-sample pooling sizes, length B.
      max_pooling: pad target L; defaults to ``lengths.max()`` (min 1).
    Returns:
      (padded_indices (B, L) int32, lengths (B,) int32)
    """
    indices = np.asarray(indices, dtype=np.int32)
    lengths = np.asarray(lengths, dtype=np.int32)
    if indices.ndim != 1 or lengths.ndim != 1:
        raise ValueError("csr_to_padded expects 1-D indices and lengths")
    if int(lengths.sum()) != indices.shape[0]:
        raise ValueError(
            f"lengths.sum()={int(lengths.sum())} != len(indices)={indices.shape[0]}"
        )
    B = lengths.shape[0]
    L = int(max_pooling if max_pooling is not None else max(1, lengths.max(initial=0)))
    if lengths.max(initial=0) > L:
        raise ValueError(f"max length {lengths.max()} exceeds pad target {L}")
    out = np.zeros((B, L), dtype=np.int32)
    offsets = np.concatenate([[0], np.cumsum(lengths)])
    for b in range(B):
        out[b, : lengths[b]] = indices[offsets[b] : offsets[b + 1]]
    return out, lengths


def padded_to_csr(padded: np.ndarray, lengths: np.ndarray):
    """Inverse of :func:`csr_to_padded` — recover flat indices."""
    padded = np.asarray(padded)
    lengths = np.asarray(lengths, dtype=np.int32)
    flat = [padded[b, : lengths[b]] for b in range(padded.shape[0])]
    return (
        np.concatenate(flat) if flat else np.zeros((0,), np.int32)
    ).astype(np.int32), lengths


def offsets_from_lengths(lengths: np.ndarray) -> np.ndarray:
    """CSR row offsets: [0, cumsum(lengths)] — length B + 1."""
    lengths = np.asarray(lengths, dtype=np.int64)
    return np.concatenate([[0], np.cumsum(lengths)])


# ---------------------------------------------------------------------------
# Synthetic generation (benchmark + test helper)
# ---------------------------------------------------------------------------

def zipf_ranks(
    rng: np.random.Generator,
    a: float,
    num_rows: int,
    size,
) -> np.ndarray:
    """0-based Zipfian rank samples over exactly ``num_rows`` ids.

    Two regimes, matching ``perf_model.zipf_hit_rate``'s traffic model:

      * ``a > 1`` — numpy's infinite-support zipf sampler, ranks clipped
        to ``num_rows`` (the rank tail collapses onto the last row);
      * ``0 < a <= 1`` — the infinite-support zeta diverges (and
        ``rng.zipf`` refuses it), so ranks are drawn from the TRUNCATED
        zeta over exactly ``num_rows`` ids via inverse-CDF sampling:
        ``p_k ∝ k^-a``, k = 1..num_rows.

    Rank 0 is the hottest id — generators that remap popularity to
    different rows (e.g. the drift workload's hot-set rotation) shift
    these ranks before using them as row ids.
    """
    if a <= 0:
        raise ValueError(f"zipf_a must be positive, got {a}")
    if a <= 1.0:
        pmf = np.arange(1, num_rows + 1, dtype=np.float64) ** -a
        cdf = np.cumsum(pmf)
        cdf /= cdf[-1]
        return np.searchsorted(cdf, rng.random(size))
    ranks = rng.zipf(a, size=size)
    return np.minimum(ranks - 1, num_rows - 1)


def random_jagged_batch(
    rng: np.random.Generator,
    num_tables: int,
    batch_size: int,
    pooling: int,
    num_rows: int,
    *,
    fixed_pooling: bool = True,
    zipf_a: Optional[float] = None,
) -> JaggedBatch:
    """Random batch matching the paper's generator (§4.4: uniform random ids).

    ``zipf_a`` switches to a Zipfian row-popularity distribution — real CTR
    traffic is heavily skewed (hot rows), which matters for cache behaviour;
    see :func:`zipf_ranks` for the two sampling regimes.
    """
    T, B, L = num_tables, batch_size, pooling
    if zipf_a is None:
        idx = rng.integers(0, num_rows, size=(T, B, L), dtype=np.int64)
    else:
        idx = zipf_ranks(rng, zipf_a, num_rows, (T, B, L))
    if fixed_pooling:
        lengths = np.full((T, B), L, dtype=np.int32)
    else:
        lengths = rng.integers(0, L + 1, size=(T, B), dtype=np.int32)
    return JaggedBatch(
        indices=jnp.asarray(idx, dtype=jnp.int32),
        lengths=jnp.asarray(lengths),
    )
