"""``CachedEmbeddingBag`` — tiered lookup: HBM slot pool over a cold tier.

The store is a tier stack behind the :class:`repro.cache.tiers.TableStore`
interface: a flat ``(sum S_t, D)`` device :class:`SlotPool` (the hot tier
the fused TBE kernel addresses through per-table slot offsets) fronting
ONE cold tier —

  * :class:`HostStore` (``cold_tier="host"``): the full ``(T, R, D)``
    tables in the serving host's memory, misses cross the host<->device
    link (the PR-2 layout);
  * :class:`RemoteStore` (``cold_tier="remote"``): tables row-split over
    peer ranks, misses batch into ONE cross-host ``comm.fetch_rows``
    collective per prefetch (bulk psum_scatter or the device-initiated
    one-sided RDMA kernel, per ``remote_backend``).

The serving protocol is two explicit steps:

  1. ``prefetch(batch)`` — host-side: admit the batch's working set (the
     :class:`SlotPoolManager`'s per-tier PrefetchPlan: cold fetch + ONE
     flat pool scatter), update the LFU/LRU state and per-tier
     :class:`CacheStats`, and return the batch with ids remapped to pool
     slots;
  2. ``lookup(batch)`` / ``device_lookup(...)`` — device-side: one fused
     TBE ``pallas_call`` over the slot pool, identical kernel to the
     uncached ``pooled_lookup_local`` path (the slot remap happens in the
     indices, not the kernel), so the hot path stays one launch.

Exactness: after ``prefetch`` every valid lookup's row is pool-resident
and the pooled output is BITWISE equal to the uncached oracle — same
kernel, same weights, same summation order, same row payloads — under
ANY tier layout (a fetched row's payload is bitwise the source table row
whichever tier served it).
"""
from __future__ import annotations

import itertools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache.manager import PrefetchPlan, SlotPoolManager
from repro.cache.stats import CacheStats
from repro.cache.tiers import HostStore, RemoteStore, SlotPool, TableStore
from repro.core.cache_config import CacheConfig
from repro.core.embedding_bag import EmbeddingBagConfig
from repro.core.jagged import JaggedBatch
from repro.kernels import ops as kops
from repro.obs.trace import span


def _valid_mask(indices: np.ndarray, lengths: Optional[np.ndarray]):
    """(T, B, L) ids + (T, B) lengths -> (indices, (T, B, L) bool valid);
    ``lengths`` None means every slot is a live lookup."""
    indices = np.asarray(indices)
    if lengths is None:
        return indices, np.ones(indices.shape, bool)
    L = indices.shape[-1]
    return indices, np.arange(L) < np.asarray(lengths)[..., None]


# process-wide prefetch sequence: tags each prefetch's cache-lane spans
# so the obs tracer can group one prefetch's fetch + scatter into one
# calibration sample even when several bags share a timeline
_PREFETCH_SEQ = itertools.count()


def make_cold_store(tables, cache: CacheConfig,
                    kernel_mode: str = "auto") -> TableStore:
    """Build the cold tier named by ``cache.cold_tier``; a one-sided
    remote tier runs its RDMA kernel under ``kernel_mode``."""
    if cache.cold_tier == "host":
        return HostStore(tables)
    if cache.cold_tier == "remote":
        onesided = cache.remote_backend == "onesided"
        return RemoteStore(
            tables, hosts=cache.remote_hosts or None,
            backend=cache.remote_backend,
            interpret=onesided and kops.onesided_interpret(kernel_mode))
    raise ValueError(
        f"unknown cold_tier {cache.cold_tier!r}; pick 'host' or 'remote'")


class CachedEmbeddingBag:
    def __init__(self, tables, cfg: EmbeddingBagConfig, *,
                 cache: Optional[CacheConfig] = None,
                 cold_store: Optional[TableStore] = None,
                 stats: Optional[CacheStats] = None):
        if cfg.combiner not in ("sum", "mean"):
            raise NotImplementedError(
                f"CachedEmbeddingBag: combiner {cfg.combiner!r} "
                f"(EmbeddingBagConfig.combiner) is not supported")
        self.cfg = cfg
        cc = cache if cache is not None else cfg.cache
        self.cache_cfg = cc
        tables = np.asarray(tables)
        if tables.ndim != 3:
            raise ValueError(f"tables must be (T, R, D), got {tables.shape}")
        self.cold = cold_store if cold_store is not None \
            else make_cold_store(tables, cc, cfg.kernel_mode)
        T, R, D = tables.shape
        self.dtype = tables.dtype
        # slot sizing: the CacheConfig's per-table vector (the planner ->
        # engine round trip) wins over the uniform scalar.
        if cc.rows_per_table is not None:
            S = np.asarray(cc.rows_per_table, np.int64)
        else:
            S = int(cc.rows)
        if np.min(S) <= 0:
            raise ValueError(
                "cache rows must be > 0 (for every table) to build a "
                "CachedEmbeddingBag (set CacheConfig.rows / rows_per_table "
                "on EmbeddingBagConfig.cache)")
        self.mgr = SlotPoolManager(
            T, R, S, cc.policy,
            rows_per_host=self.cold.rows_per_host, home=self.cold.home)
        # stats may be SHARED: the double-buffered pipeline pool passes
        # one CacheStats so every buffer's traffic lands in one record
        self.stats = stats if stats is not None else CacheStats()
        self.hot = SlotPool(T, self.mgr.S, D, self.dtype,
                            slots_per_table=self.mgr.slots_per_table,
                            stats=self.stats)
        # the kernel's scalar-prefetched per-table slot offsets — a jit
        # constant, so the jitted consumer compiles exactly once
        self._row_offsets = jnp.asarray(self.mgr.slot_offsets[:-1],
                                        jnp.int32)
        # optional obs tracer (duck-typed: anything with add_span) — the
        # engine attaches it so admit/fetch/scatter land on the unified
        # timeline's cache lane; None costs one attribute check
        self.tracer = None
        self.row_bytes = D * self.dtype.itemsize
        if cc.warmup_freqs is not None:
            self.mgr.seed_frequencies(np.asarray(cc.warmup_freqs))
            self._apply_fetch(self.mgr.warmup_admit(), count_batch=False)

    # -- tier plumbing -------------------------------------------------------

    @property
    def pool(self) -> jax.Array:
        """The hot tier's flat ``(sum S_t, D)`` device array (the kernel
        operand)."""
        return self.hot.array

    @property
    def host(self):
        """The local cold tier's numpy tables (None-able test hook)."""
        if not isinstance(self.cold, HostStore):
            raise AttributeError(
                f"cold tier {self.cold.tier!r} has no local host tables")
        return self.cold.tables

    @host.setter
    def host(self, value):
        if not isinstance(self.cold, HostStore):
            raise AttributeError(
                f"cold tier {self.cold.tier!r} has no local host tables")
        self.cold.tables = value

    def _apply_fetch(self, plan: PrefetchPlan, *, count_batch: bool) -> None:
        """Execute a plan's cold fetch + pool scatter, update stats.

        Metadata stays honest on failure: prepare()/warmup_admit()
        committed residency for the fetched rows, so any error between
        the cold fetch and the pool scatter rolls that back
        (``invalidate_fetch``) — no slot ever claims an uncopied row."""
        if plan.fetch_rows.size:
            args = None
            if self.tracer is not None:
                # one seq per prefetch pairs this fetch with its scatter
                # on the trace
                args = {"seq": next(_PREFETCH_SEQ),
                        "bytes": int(plan.fetch_rows.size) * self.row_bytes,
                        "tier": self.cold.tier}
            try:
                with span("repro.cache.fetch", self.tracer, "cache.fetch",
                          lane="cache", cat="cache", args=args) as fetch:
                    rows = self.cold.fetch(
                        plan.fetch_tables, plan.fetch_rows,
                        out=self.hot.staging(plan.fetch_rows.size))
                with span("repro.cache.scatter", self.tracer,
                          "cache.scatter", lane="cache", cat="cache",
                          args=None if args is None else dict(args)) \
                        as scatter:
                    self.hot.scatter(plan.flat_addr(self.mgr.slot_offsets),
                                     rows)
            except BaseException:
                self.mgr.invalidate_fetch(plan)
                raise
            self.stats.add_time("prefetch", fetch.seconds)
            self.stats.add_time("scatter", scatter.seconds)
        self.stats.update(**plan.stats_kwargs(self.row_bytes),
                          count_batch=count_batch)

    # -- tier-1 protocol: prefetch then lookup -------------------------------

    def prefetch_arrays(self, indices: np.ndarray,
                        lengths: Optional[np.ndarray]) -> np.ndarray:
        """Host-array prefetch: (T, B, L) ids -> (T, B, L) pool slots.

        Pulls every missing row of the batch cold-tier -> pool (one
        batched cold fetch + one flat scatter), updates stats, and
        returns the slot-remapped indices.  ``lengths`` None means every
        slot valid.
        """
        with span("repro.cache.admit", self.tracer, "cache.admit",
                  lane="cache", cat="cache",
                  args={"tier": self.cold.tier}) as admit:
            plan = self.mgr.prepare(*_valid_mask(indices, lengths))
        self.stats.add_time("prefetch", admit.seconds)
        self._apply_fetch(plan, count_batch=True)
        return plan.remapped

    def prefetch(self, batch: JaggedBatch) -> JaggedBatch:
        """Admit ``batch``'s working set; return the slot-remapped batch."""
        remapped = self.prefetch_arrays(
            np.asarray(batch.indices),
            None if batch.lengths is None else np.asarray(batch.lengths))
        return JaggedBatch(jnp.asarray(remapped), batch.lengths,
                           batch.weights)

    def device_lookup(self, pool: jax.Array, indices: jax.Array,
                      lengths: Optional[jax.Array],
                      weights: Optional[jax.Array]) -> jax.Array:
        """Pure hot-path: flat (sum S_t, D) pool x (T, B, L) TABLE-LOCAL
        slot ids -> (B, T, D).

        One fused TBE ``pallas_call`` over the flat pool, addressed by
        the manager's scalar-prefetched per-table slot offsets (always
        fused — a ragged pool has no rectangle to vmap per table).
        Jit/jaxpr-safe: the offsets are a trace-time constant."""
        out = kops.embedding_bag_batched_flat(
            pool, self._row_offsets, indices, lengths, weights,
            combiner=self.cfg.combiner, mode=self.cfg.kernel_mode)
        return out.transpose(1, 0, 2)                        # (B, T, D)

    def lookup(self, batch: JaggedBatch, *,
               prefetched: bool = False) -> jax.Array:
        """Tiered pooled lookup, drop-in for ``pooled_lookup_local``.

        Pass ``prefetched=True`` when ``batch`` already came out of
        :meth:`prefetch` (its ids are pool slots, not row ids)."""
        if not prefetched:
            batch = self.prefetch(batch)
        return self.device_lookup(self.pool, batch.indices, batch.lengths,
                                  batch.weights)

    # -- introspection -------------------------------------------------------

    @property
    def cache_ratio(self) -> float:
        """Mean resident fraction: total live slots over total rows."""
        return float(self.mgr.slots_per_table.sum()) / (self.mgr.T
                                                        * self.mgr.R)

    @property
    def pool_bytes(self) -> int:
        return self.hot.nbytes


# ---------------------------------------------------------------------------
# Kernel contracts (audited by repro.analysis)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import KernelContract  # noqa: E402

KERNEL_CONTRACTS = {
    "device_lookup": KernelContract(
        name="cache.cached_bag.device_lookup",
        note="the cached hot path is ONE fused gather+pool pallas_call "
             "over the flat slot pool — no collectives, no callbacks; "
             "every miss byte moved by the explicit prefetch instead"),
}
