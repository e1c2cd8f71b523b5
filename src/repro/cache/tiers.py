"""``TableStore`` — the pluggable tier interface of the embedding store.

The PR-2 cache hard-wired two tiers: a device slot pool over a local
host-numpy array.  Scale-out inference deployments hold tables on REMOTE
hosts precisely because one node can't (capacity-driven scale-out —
PAPERS.md), so the store is now a tier stack behind one small interface:

  * :class:`SlotPool`   — tier "hbm": the flat ``(sum S_t, D)`` device
    pool the fused TBE kernel reads, addressed by per-table slot offsets
    (``slot_offsets[t] + slot``).  Rows are written by ONE flat scatter
    per prefetch (jitted, pool donated — in-place on accelerators).
  * :class:`HostStore`  — tier "host": the full ``(T, R, D)`` tables in
    the serving host's memory (numpy); a fetch is one ``np.take`` gather
    over their flat ``(T*R, D)`` view, written straight into the pool's
    host staging buffer, which the scatter then moves across the
    host<->device link.
  * :class:`RemoteStore` — tier "remote": every table row-split across
    ``hosts`` ranks (host h owns rows ``[h*R/H, (h+1)*R/H)`` of every
    table, the paper's RW layout §4.2); a fetch is ONE batched
    ``comm.fetch_rows`` collective per prefetch — bulk ``psum_scatter``
    or the device-initiated one-sided RDMA kernel
    (kernels/onesided_a2a.onesided_fetch_rows), per ``backend``.

The single-process simulation backs each "host" with one device of the
local jax mesh (``XLA_FLAGS=--xla_force_host_platform_device_count=N``
on CPU, one chip per rank on a real slice); the serving rank is host
``home`` (device 0), so rows it owns are HOST-tier traffic and rows
owned by peers are REMOTE-tier traffic — :class:`repro.cache.CacheStats`
keeps the split.

Exactness contract is tier-independent: a fetched row's payload is
bitwise the source table row whichever tier served it, so the pooled
output stays bitwise-equal to the uncached oracle under ANY tier layout.
"""
from __future__ import annotations

import abc
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.stats import CacheStats
from repro.core import comm
from repro.obs.trace import span
from jax import shard_map


def _pow2(m: int) -> int:
    """The power of two an (m, ...) scatter operand is padded to."""
    return 1 << (m - 1).bit_length()


def _pad_pow2(arrays):
    """Pad each (M, ...) array to the next power of two by repeating its
    last element — idempotent duplicates, bounds the jit shape count to
    O(log M_max) instead of one program per distinct M."""
    m = arrays[0].shape[0]
    pad = _pow2(m) - m
    if not pad:
        return arrays
    return [np.concatenate([a, np.repeat(a[-1:], pad, axis=0)])
            for a in arrays]


class TableStore(abc.ABC):
    """One tier of the embedding store: where row payloads live.

    ``hosts``/``home``/``rows_per_host`` describe the tier's ownership
    layout so :class:`repro.cache.manager.SlotPoolManager` can split a
    prefetch plan by serving tier (home-owned rows vs peer-owned rows).
    """

    tier: str = "?"
    hosts: int = 1
    home: int = 0

    @property
    @abc.abstractmethod
    def rows_per_host(self) -> int:
        """Rows of each table owned by one host (R for single-host tiers)."""

    @abc.abstractmethod
    def fetch(self, t_ids: np.ndarray, row_ids: np.ndarray,
              out: Optional[np.ndarray] = None) -> np.ndarray:
        """(M,) table ids x (M,) table-local row ids -> (M, D) payloads,
        written into ``out`` (and returned) when one is given."""


# ---------------------------------------------------------------------------
# Hot tier: the HBM slot pool
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter_rows(pool: jax.Array, addr: jax.Array,
                  rows: jax.Array) -> jax.Array:
    """Write fetched rows into the flat pool at ``slot_offsets[t] + slot``
    addresses.

    Jitted with the pool DONATED so accelerator backends update the
    buffer in place — O(M*D) HBM writes per prefetch, not an
    O(sum(S_t)*D) whole-pool copy (an eager ``.at[].set`` cannot alias
    its input).
    """
    return pool.at[addr].set(rows)


class SlotPool(TableStore):
    """Tier "hbm": the flat ``(sum S_t, D)`` device pool the kernel reads.

    Table ``t``'s slots are the contiguous rows
    ``[slot_offsets[t], slot_offsets[t+1])`` — heterogeneous per-table
    widths ``S_t`` allocate EXACTLY ``sum(S_t) * D * itemsize`` device
    bytes (no padding rectangle), and the fused TBE kernel addresses the
    pool through its scalar-prefetched per-table offsets.  Never
    reallocated — ``scatter`` replaces the array functionally (the
    donated jit updates it in place on accelerators), so the jitted
    consumer compiles exactly once.

    The pool also owns a host staging buffer, ``(P, D)`` in its dtype
    with P the power of two the scatter pads to: a cold tier gathers a
    prefetch's M missed rows straight into ``staging(M)``, and
    ``scatter`` fills rows ``[M, P)`` with the last row and moves the
    buffer as it is — each missed row is copied on the host once.  The
    buffer is reallocated only when P changes (``stats.staging_allocs``).
    """

    tier = "hbm"

    def __init__(self, num_tables: int, slots: int, dim: int, dtype,
                 *, slots_per_table=None,
                 stats: Optional[CacheStats] = None):
        if slots_per_table is None:
            slots_per_table = np.full(num_tables, slots, np.int64)
        self.slots_per_table = np.asarray(slots_per_table, np.int64)
        if self.slots_per_table.shape != (num_tables,) or \
                self.slots_per_table.max(initial=0) > slots:
            raise ValueError(
                f"slots_per_table must be ({num_tables},) with entries "
                f"<= {slots}, got {slots_per_table}")
        self.slot_offsets = np.zeros(num_tables + 1, np.int64)
        np.cumsum(self.slots_per_table, out=self.slot_offsets[1:])
        self.array = jnp.zeros((int(self.slot_offsets[-1]), dim), dtype)
        self.stats = stats if stats is not None else CacheStats()
        self._staging: Optional[np.ndarray] = None
        self._view: Optional[np.ndarray] = None

    @property
    def slots(self) -> int:
        """Largest per-table slot count (the old rectangle's width)."""
        return int(self.slots_per_table.max(initial=0))

    @property
    def rows_per_host(self) -> int:
        return self.slots

    @property
    def nbytes(self) -> int:
        return int(self.array.size) * self.array.dtype.itemsize

    @property
    def live_nbytes(self) -> int:
        """Bytes of addressable slots. The flat pool has NO padding, so
        this equals ``nbytes`` exactly — ``sum(S_t) * D * itemsize``, the
        figure a heterogeneous plan charged to the HBM budget."""
        return self.nbytes

    def fetch(self, t_ids, slot_ids) -> np.ndarray:
        """Read resident payloads back (test/debug hook, device->host)."""
        addr = self.slot_offsets[np.asarray(t_ids)] + np.asarray(slot_ids)
        return np.asarray(self.array)[addr]

    def staging(self, m: int) -> np.ndarray:
        """The ``(m, D)`` head of the host staging buffer, for a cold
        tier's ``fetch(..., out=)``; ``m >= 1``.

        Waits first for the pool's last scatter: the device array made
        from the buffer may alias it (CPU) or still be reading it, and
        the buffer is about to be written again."""
        p = _pow2(m)
        self.array.block_until_ready()
        if self._staging is None or self._staging.shape[0] != p:
            self._staging = np.empty((p,) + self.array.shape[1:],
                                     self.array.dtype)
            self.stats.staging_allocs += 1
        self._view = self._staging[:m]
        return self._view

    def scatter(self, flat_addr: np.ndarray, rows) -> None:
        """One flat scatter of (M, D) ``rows`` at ``slot_offsets[t] +
        slot`` addresses (see ``PrefetchPlan.flat_addr``).

        Rows gathered into :meth:`staging` move as the padded buffer
        they already sit in; rows from anywhere else are padded into a
        new one."""
        flat_addr = np.asarray(flat_addr, np.int64)
        if rows is self._view:
            m, rows = rows.shape[0], self._staging
            rows[m:] = rows[m - 1]
            (flat_addr,) = _pad_pow2([flat_addr])
        else:
            m = flat_addr.shape[0]
            flat_addr, rows = _pad_pow2([flat_addr, np.asarray(rows)])
        self.stats.pad_rows += rows.shape[0] - m
        with warnings.catch_warnings():
            # CPU backends skip donation with a warning; harmless
            warnings.simplefilter("ignore")
            self.array = _scatter_rows(
                self.array, jnp.asarray(flat_addr), jnp.asarray(rows))


# ---------------------------------------------------------------------------
# Cold tier, local: host-resident numpy tables
# ---------------------------------------------------------------------------

class HostStore(TableStore):
    """Tier "host": the full ``(T, R, D)`` tables in local host memory.

    A fetch is one unbuffered ``np.take`` over the tables' flat
    ``(T*R, D)`` view (a reshape of the C-contiguous tables, rebuilt
    whenever ``tables`` is set) at ``t * R + r``; the ids are range
    checked first, since ``mode="clip"`` would otherwise read row
    ``r - R`` of table ``t + 1`` for an ``r >= R``."""

    tier = "host"

    def __init__(self, tables):
        self.tables = tables

    @property
    def tables(self) -> Optional[np.ndarray]:
        return self._tables

    @tables.setter
    def tables(self, value) -> None:
        # None is a test hook: a store that cannot fetch
        self._tables = self._flat = None
        if value is not None:
            value = np.ascontiguousarray(value)
            if value.ndim != 3:
                raise ValueError(
                    f"tables must be (T, R, D), got {value.shape}")
            self._tables = value
            self._flat = value.reshape(-1, value.shape[2])

    @property
    def rows_per_host(self) -> int:
        return self.tables.shape[1]

    def fetch(self, t_ids, row_ids, out=None) -> np.ndarray:
        if self._flat is None:
            raise TypeError("HostStore holds no tables to fetch from")
        T, R = self._tables.shape[:2]
        t_ids = np.asarray(t_ids, np.int64)
        row_ids = np.asarray(row_ids, np.int64)
        if t_ids.size and (t_ids.min() < 0 or t_ids.max() >= T
                           or row_ids.min() < 0 or row_ids.max() >= R):
            raise IndexError(
                f"fetch ids out of range: tables [0, {T}), rows [0, {R})")
        return np.take(self._flat, t_ids * R + row_ids, axis=0, out=out,
                       mode="clip")


# ---------------------------------------------------------------------------
# Cold tier, distributed: row shards on peer ranks
# ---------------------------------------------------------------------------

class RemoteStore(TableStore):
    """Tier "remote": every table row-split across ``hosts`` ranks.

    Host h's shard is the flat ``(T * R/H, D)`` block of rows
    ``[h*R/H, (h+1)*R/H)`` of every table (owner-local address
    ``t * R/H + r % (R/H)``).  ``fetch`` runs ONE jitted shard_map
    ``comm.fetch_rows`` collective over the mesh per call (request count
    padded to powers of two to bound program shapes) and returns the
    payloads to the serving host — rows the home rank owns are part of
    the same batched program but are accounted as HOST-tier traffic by
    the manager's plan split.
    """

    tier = "remote"

    def __init__(self, tables, *, hosts: Optional[int] = None,
                 backend: str = "bulk", home: int = 0,
                 axis_name: str = "hosts", interpret: bool = False):
        tables = np.asarray(tables)
        if tables.ndim != 3:
            raise ValueError(f"tables must be (T, R, D), got {tables.shape}")
        T, R, D = tables.shape
        if backend not in ("bulk", "onesided"):
            raise ValueError(f"unknown remote backend {backend!r}")
        n_dev = len(jax.devices())
        H = int(hosts) if hosts else n_dev
        if H < 2:
            raise ValueError(
                f"RemoteStore needs >= 2 hosts (got {H}) — use HostStore "
                f"(cold_tier='host') for a single-host cold tier")
        if R % H:
            raise ValueError(
                f"rows_per_table ({R}) must divide evenly over {H} hosts")
        if H > n_dev:
            raise ValueError(
                f"RemoteStore: {H} hosts > {n_dev} local devices — the "
                f"single-process simulation backs each host with one device "
                f"(set XLA_FLAGS=--xla_force_host_platform_device_count)")
        self.hosts, self.home, self.backend = H, int(home), backend
        self._rows_per_host = R // H
        self.axis_name = axis_name
        # (H, T * R/H, D): host h's flat shard, device-sharded over the mesh
        shards = (tables.reshape(T, H, self._rows_per_host, D)
                  .transpose(1, 0, 2, 3).reshape(H, T * self._rows_per_host,
                                                 D))
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
        self.mesh = mesh = Mesh(np.asarray(jax.devices()[:H]), (axis_name,))
        self.shards = jax.device_put(
            shards, NamedSharding(self.mesh, P(axis_name)))
        # one-sided fetches run the Pallas RDMA kernel: compiled, or in
        # the Pallas interpreter when the caller asks (CPU tests).  The
        # closure holds the mesh, not ``self``: a cycle through the jitted
        # function would keep a dropped store's shards in HBM until the
        # garbage collector runs.

        def _fetch(shards, addr, owner):
            def inner(shard, a, o):
                return comm.fetch_rows(shard[0], a, o, axis_name,
                                       backend=backend, interpret=interpret)
            return shard_map(
                inner, mesh=mesh,
                in_specs=(P(axis_name), P(), P()), out_specs=P(),
                check_vma=False)(shards, addr, owner)

        self._fetch = jax.jit(_fetch)

    @property
    def rows_per_host(self) -> int:
        return self._rows_per_host

    def owner_of(self, row_ids: np.ndarray) -> np.ndarray:
        return np.asarray(row_ids) // self._rows_per_host

    def fetch(self, t_ids, row_ids, out=None) -> np.ndarray:
        t_ids = np.asarray(t_ids, np.int64)
        row_ids = np.asarray(row_ids, np.int64)
        owner = (row_ids // self._rows_per_host).astype(np.int32)
        local = (t_ids * self._rows_per_host
                 + row_ids % self._rows_per_host).astype(np.int32)
        m = local.shape[0]
        local, owner = _pad_pow2([local, owner])
        with span("repro.comm.fetch_rows") as wire:
            payload = self._fetch(self.shards, jnp.asarray(local),
                                  jnp.asarray(owner))
            # device->host roundtrip: the payloads land on the serving
            # host (modeling NIC -> host RAM) before the pool scatter
            # moves them h2d
            result = np.asarray(payload)[:m]
        # compiled programs never re-trace, so comm._record's trace-time
        # event carries no per-execution wall clock — record the measured
        # dispatch->materialize interval with the stacked payload bytes
        # (H contributions of the padded request, matching _record's
        # accounting of the traced (E, M, D) contrib tensor)
        comm.record_runtime(
            "fetch_rows",
            self.hosts * local.shape[0] * self.shards.shape[-1]
            * self.shards.dtype.itemsize,
            self.hosts, self.backend, wire.t0, wire.t1)
        if out is None:
            return result
        out[...] = result
        return out


# ---------------------------------------------------------------------------
# Kernel contracts (audited by repro.analysis)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import KernelContract  # noqa: E402

KERNEL_CONTRACTS = {
    "scatter_rows": KernelContract(
        name="cache.tiers.scatter_rows",
        min_pallas_calls=0, max_pallas_calls=0,
        donate_argnums=(0,),
        note="the slot-pool admission scatter is a donated in-place "
             "XLA scatter (argnum 0 buffer-aliased) — dropping the "
             "donation would copy the whole pool every prefetch"),
}
