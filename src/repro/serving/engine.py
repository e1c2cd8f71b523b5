"""DLRM serving engines.

``DLRMEngine`` micro-batches CTR scoring requests into one fixed-shape
jitted forward whose embedding pooling runs the fused table-batched (TBE)
kernel — one ``pallas_call`` per batch for all 26 Criteo-like tables
instead of 26 launches (the paper's #tables axis).
``PipelinedDLRMEngine`` (selected by ``DLRMConfig.cache.pipeline_depth
>= 2`` via :func:`make_dlrm_engine`) runs the same scoring as a software
pipeline over double-buffered slot pools (repro/pipeline/): batch k+1's
cold fetch and admission scatter target the shadow buffer while batch
k's forward reads the live one — bitwise-identical scores, overlapped
latency.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.dlrm import DLRMConfig
from repro.core.jagged import JaggedBatch, PackedBatch
from repro.core.parallel import ParallelContext
from repro.models import dlrm as dlrm_mod
from repro.obs.trace import span


# ---------------------------------------------------------------------------
# DLRM CTR scoring engine (fused-TBE consumer)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CTRRequest:
    """One scoring request: dense features + per-table sparse lookups."""
    rid: int
    dense: np.ndarray          # (num_dense_features,)
    indices: np.ndarray        # (T, max L_t) table-local row ids (padded)
    lengths: np.ndarray        # (T,) valid lookups per table, <= L_t


def make_scorer(cfg: DLRMConfig, ctx: Optional[ParallelContext] = None):
    """The engines' jitted scoring program: ``(params, dense (B, F),
    batch) -> pCTR (B,)``.  ``params["tables"]`` is the stacked
    ``(T, R, D)`` tables, the flat ``(sum R_t, D)`` tables of a per-table
    config (``batch`` a ``PackedBatch``), or the tiered store's flat
    ``(sum S_t, D)`` slot pool with slot ids in ``batch``."""
    def fwd(p, dense, batch):
        return jax.nn.sigmoid(dlrm_mod.forward(p, dense, batch, cfg, ctx))

    return jax.jit(fwd)


class DLRMEngine:
    """Micro-batching CTR inference over the DLRM forward.

    Requests accumulate in a queue; ``flush`` pads them to the engine's
    fixed ``batch_size`` and runs ONE jitted forward — the embedding
    pooling inside is the fused TBE path (``cfg.fused``), so every flush
    costs a single gather kernel launch regardless of the table count.
    Fixed shapes mean the forward compiles exactly once.

    A per-table config (``cfg.per_table``: rows and pooling ``R_t``,
    ``L_t`` per table) takes requests of ``(T, max L_t)`` ids and stages
    only each table's ``L_t`` slots (``PackedBatch``) for the packed
    multi-hot kernel; it serves from one device, tables in HBM.

    With ``cfg.cache.enabled`` (``cache.rows > 0`` or a per-table
    vector) the tables live behind a tiered cache (repro/cache/):
    ``flush`` PREFETCHES the micro-batch's working set into the flat HBM
    slot pool, remaps ids to TABLE-LOCAL slots, and runs the same jitted
    forward over the pool — the pool is a same-shape argument every
    flush, so admission/eviction never recompiles.  The cold tier is
    ``cfg.cache.cold_tier``: the serving host's memory, or row-shards on
    ``cfg.cache.remote_hosts`` peer ranks fetched cross-host at flush
    (``comm.fetch_rows``); ``cfg.cache.warmup_freqs`` pre-admits the
    logged-hot rows so the first flushes skip the cold-start miss burst.

    ``cfg.sharding_plan`` closes the planner -> engine round trip: each
    "cached" ``Placement.cache_rows`` sizes THAT table's slot pool
    (heterogeneous ``S_t`` segments of ONE flat ``(sum S_t, D)`` pool —
    tables mapped by position, never by name), and the per-table measured
    hit rate (``cache_stats().hit_rate_t``) is directly comparable against the
    plan's priced ``est_hit_rate`` — see
    benchmarks/plan_roundtrip_sweep.py.

    ``telemetry`` (a :class:`repro.obs.Telemetry`) opts the engine into
    the unified timeline: ``submit`` stamps each request's enqueue time,
    ``flush`` records prefetch/forward spans on the engine lane and one
    enqueue->score latency observation per scored request
    (``<obs_name>.request_latency_s`` histogram + request-lane span),
    the cache's admit/fetch/scatter spans land on the cache lane, and
    the engine's ``CacheStats`` joins ``telemetry.metrics`` as the
    ``<obs_name>.cache`` producer.  Default None: zero overhead beyond
    one attribute check per flush.

    With or without ``telemetry``, every stage of ``flush`` is one
    ``repro.obs.span`` call, so a ``jax.profiler`` trace puts the host's
    ``repro.engine.pad`` / ``prefetch`` / ``dispatch`` / ``collect``
    (and the cache's ``repro.cache.*``) beside the device's ops.
    """

    OBS_NAME = "dlrm"

    def __init__(self, params, cfg: DLRMConfig, batch_size: int,
                 ctx: Optional[ParallelContext] = None, *,
                 telemetry=None, obs_name: Optional[str] = None):
        self.params, self.cfg, self.ctx = params, cfg, ctx
        self.batch_size = batch_size
        self.queue: List[CTRRequest] = []
        self.telemetry = telemetry
        self.obs_name = obs_name if obs_name is not None else self.OBS_NAME
        # rid -> perf_counter enqueue stamp; popped at scoring so a
        # pipeline failure's requeued requests keep their ORIGINAL stamps
        # (latency measures from first submit, not the retry)
        self._enqueue_t: Dict[int, float] = {}
        # rid -> dequeue stamp (the micro-batch carve) — splits each
        # request's latency into queue-wait vs service time; a capacity
        # split's survivors are re-stamped at their NEXT carve, so the
        # split point always reflects the flush that actually scored
        self._dequeue_t: Dict[int, float] = {}
        # cache counter snapshot at the last batch tick (windowed
        # hit-rate deltas); None until the cache exists
        self._cache_counter_state = None

        # request geometry: each table's pooling L_t, the slots of a
        # (T, max L_t) request within them (flat, table-major; what a
        # packed batch keeps), and each such slot's table, position and
        # row bound R_t; unsigned, for submit's range check
        self._pooling = np.asarray(cfg.table_pooling(), np.uint64)
        self._width = int(self._pooling.max())
        table, self._live_pos = np.nonzero(
            np.arange(self._width) < self._pooling[:, None])
        self._live = table * self._width + self._live_pos
        self._live_table = table
        self._live_rows = np.asarray(cfg.table_rows(), np.uint64)[table]

        self.cache = None
        if cfg.cache.enabled or cfg.sharding_plan is not None:
            if cfg.per_table:
                raise NotImplementedError(
                    "DLRMEngine: the tiered store over per-table "
                    "rows_per_table / pooling needs per-table tiered "
                    "metadata, not implemented (ROADMAP Reach 1)")
            if ctx is not None:
                raise NotImplementedError(
                    "DLRMEngine: the tiered cache path scores on a single "
                    "serving device (an enabled cfg.cache with a "
                    "ParallelContext is not supported) — a cluster-wide "
                    "COLD tier is cache.cold_tier='remote', which manages "
                    "its own mesh")
            per_table = cfg.cache_rows_vector()
            if per_table is not None:
                # plan-driven heterogeneous pools: EVERY table's own S_t
                # must fit a single request's working set
                small = [(t, s) for t, s in enumerate(per_table)
                         if s < cfg.pooling]
                if small:
                    raise ValueError(
                        f"sharding_plan slot pools {small} are smaller "
                        f"than pooling ({cfg.pooling}) — every table's "
                        f"cache_rows must fit one request's working set")
            elif cfg.cache.rows < cfg.pooling:
                raise ValueError(
                    f"cache rows ({cfg.cache.rows}) must be >= pooling "
                    f"({cfg.pooling}) so a single request's working set "
                    f"always fits the slot pool (CacheConfig.rows, "
                    f"formerly cache_rows)")
            self.cache = self._make_cache(params["tables"],
                                          cfg.embedding_config())
            # the cold tier now lives host-side inside the cache; drop the
            # engine's device-resident tables so serving holds only the
            # slot pool in HBM — the whole point of the tiered cache
            self.params = {**params, "tables": None}
            if self.telemetry is not None:
                # cache-lane spans: every bag of the (possibly
                # double-buffered) pool records onto the one timeline
                bags = (self.cache.buffers
                        if hasattr(self.cache, "buffers") else [self.cache])
                for bag in bags:
                    bag.tracer = self.telemetry.tracer
                self.telemetry.metrics.register_producer(
                    f"{self.obs_name}.cache", self.cache.stats.as_dict,
                    replace=True)
                self._cache_counter_state = \
                    self.cache.stats.counter_state()

        self._fwd = make_scorer(cfg, ctx)

    def _make_cache(self, tables, ebcfg):
        """Tiered-store construction hook — the pipelined engine swaps in
        its double-buffered ring here."""
        from repro.core.embedding_bag import make_cache

        return make_cache(tables, ebcfg)

    def submit(self, req: CTRRequest):
        T = self.cfg.num_sparse_features
        L = self._width
        F = self.cfg.num_dense_features
        # validate every field here: flush() pops requests before scoring,
        # so a shape error there would silently drop the whole micro-batch
        if (req.dense.shape != (F,) or req.indices.shape != (T, L)
                or req.lengths.shape != (T,)):
            raise ValueError(
                f"request {req.rid}: want dense ({F},) / indices ({T}, {L})"
                f" / lengths ({T},), got {req.dense.shape} / "
                f"{req.indices.shape} / {req.lengths.shape}")
        # dtypes too: float indices/lengths would be silently truncated by
        # the astype into the staging buffers and poison the jitted forward
        if req.indices.dtype.kind not in "iu":
            raise TypeError(
                f"request {req.rid}: indices must be an integer dtype, "
                f"got {req.indices.dtype}")
        if req.lengths.dtype.kind not in "iu":
            raise TypeError(
                f"request {req.rid}: lengths must be an integer dtype, "
                f"got {req.lengths.dtype}")
        if req.dense.dtype.kind != "f":
            raise TypeError(
                f"request {req.rid}: dense must be a float dtype, "
                f"got {req.dense.dtype}")
        # value ranges: the uncached gather clamps out-of-range ids into a
        # wrong-but-silent score, the cached path would refuse the whole
        # micro-batch at prefetch — reject per-request instead, up front.
        # Only WITHIN-LENGTH slots are checked: padding beyond lengths is
        # arbitrary (sentinels like -1 are masked downstream).  One
        # comparison over all tables, unsigned so that a negative length
        # or id reads as out of range
        if (req.lengths.astype(np.uint64) > self._pooling).any():
            raise ValueError(f"request {req.rid}: lengths must be in "
                             f"[0, {self.cfg.pooling}]")
        bad = req.indices.take(self._live).astype(np.uint64) \
            >= self._live_rows
        if bad.any() and (
                bad & (self._live_pos < req.lengths[self._live_table])).any():
            raise ValueError(f"request {req.rid}: indices must be in "
                             f"[0, {self.cfg.rows_per_table})")
        self.queue.append(req)
        if self.telemetry is not None:
            self._enqueue_t[req.rid] = time.perf_counter()
            self.telemetry.metrics.gauge(
                f"{self.obs_name}.queue_depth").set(len(self.queue))

    def _pad_batch(self, todo: List[CTRRequest]
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Pad ``todo`` to the engine's fixed shapes: (B, F) dense,
        (T, B, L) indices, (T, B) lengths — tail slots stay all-masked.
        A per-table config packs instead: (B, sum L_t) ids of each
        table's live slots, (B, T) lengths."""
        if self.cfg.per_table:
            return self._pack_batch(todo)
        B = self.batch_size
        T, L = self.cfg.num_sparse_features, self.cfg.pooling
        F = self.cfg.num_dense_features
        dense = np.zeros((B, F), np.float32)
        idx = np.zeros((T, B, L), np.int32)
        lens = np.zeros((T, B), np.int32)
        for i, req in enumerate(todo):
            dense[i] = req.dense
            idx[:, i, :] = req.indices
            lens[:, i] = req.lengths
        return dense, idx, lens

    def _pack_batch(self, todo: List[CTRRequest]
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        B = self.batch_size
        T, F = self.cfg.num_sparse_features, self.cfg.num_dense_features
        dense = np.zeros((B, F), np.float32)
        ids = np.zeros((B, self._live.size), np.int32)
        lens = np.zeros((B, T), np.int32)
        for i, req in enumerate(todo):
            dense[i] = req.dense
            ids[i] = req.indices.take(self._live)
            lens[i] = req.lengths
        return dense, ids, lens

    def _device_batch(self, idx, lens):
        if self.cfg.per_table:
            return PackedBatch(ids=jnp.asarray(idx), lengths=jnp.asarray(lens))
        return JaggedBatch(indices=jnp.asarray(idx), lengths=jnp.asarray(lens))

    def flush(self) -> Dict[int, float]:
        """Score up to ``batch_size`` queued requests; returns rid -> pCTR."""
        if not self.queue:
            return {}
        # peek, don't pop: the cached path's prefetch can refuse the batch
        # (working set over the slot pool) and the requests must survive
        todo = self.queue[: self.batch_size]
        self._stamp_dequeue(todo)
        if self.cache is not None:
            from repro.cache import CacheCapacityError

        tracer = None if self.telemetry is None else self.telemetry.tracer
        while True:
            with span("repro.engine.pad"):
                dense, idx, lens = self._pad_batch(todo)
            params = self.params
            if self.cache is not None:
                # prefetch-at-flush: pin this micro-batch's rows in the
                # slot pool and score against the pool — ids become slot
                # ids. A refused union (working set over the pool) splits
                # the micro-batch instead of stalling the queue head; the
                # __init__ floor (cache_rows >= pooling) guarantees a
                # single request always fits.
                try:
                    with span("repro.engine.prefetch", tracer,
                              f"{self.obs_name}.prefetch", cat="engine",
                              args={"engine": self.obs_name,
                                    "batch": len(todo)}):
                        idx = self.cache.prefetch_arrays(idx, lens)
                except CacheCapacityError:
                    if len(todo) == 1:
                        raise
                    todo = todo[: len(todo) // 2]
                    continue
                params = {**self.params, "tables": self.cache.pool}
            break
        with span("repro.engine.dispatch") as dispatch:
            batch = self._device_batch(idx, lens)
            scores = self._fwd(params, jnp.asarray(dense), batch)
        p = np.asarray(scores)
        with span("repro.engine.collect") as collect:
            # the forward: dispatch until the scores are on the host —
            # the same interval the pipeline scheduler logs
            t0, t1 = dispatch.t0, collect.t0
            if self.cache is not None:
                self.cache.stats.add_time("forward", t1 - t0)
            if tracer is not None:
                tracer.add_span(
                    f"{self.obs_name}.forward", t0, t1, lane="engine",
                    cat="engine",
                    args={"engine": self.obs_name, "batch": len(todo)})
            self.queue = self.queue[len(todo):]
            self._record_scored(todo, t1)
            return {req.rid: float(p[i]) for i, req in enumerate(todo)}

    def _stamp_dequeue(self, todo) -> None:
        """Stamp each carved request's dequeue time (the queue-wait vs
        service-time split point) and sample the queue depth into the
        gauge + windowed histogram."""
        if self.telemetry is None or not todo:
            return
        t = time.perf_counter()
        for req in todo:
            self._dequeue_t[req.rid] = t
        m = self.telemetry.metrics
        depth = len(self.queue)
        m.gauge(f"{self.obs_name}.queue_depth").set(depth)
        m.windowed_histogram(
            f"{self.obs_name}.queue_depth", unit="1",
            window=self.telemetry.window, lo=0.5, hi=1e7,
            buckets_per_decade=5).observe(depth)

    def _observe_cache_window(self) -> None:
        """Fold this micro-batch's cache counter movement into the
        windowed hit-rate instruments: rolling window hits/lookups
        (their ratio = the windowed hit rate the SLO monitor reads) and
        the per-table EWMA ``hit_rate_t`` (the drift detector's
        measured side).  Under the pipelined engine the next batch's
        prefetch may already have landed when batch k is collected —
        one batch of attribution skew, bounded by the pipeline depth."""
        if self.cache is None:
            return
        stats = self.cache.stats
        delta = stats.delta_since(self._cache_counter_state)
        self._cache_counter_state = stats.counter_state()
        if delta.lookups == 0:
            return
        m = self.telemetry.metrics
        w = self.telemetry.window
        m.rolling_counter(f"{self.obs_name}.window.hits",
                          window=w).inc(delta.hits)
        m.rolling_counter(f"{self.obs_name}.window.lookups",
                          window=w).inc(delta.lookups)
        lt = delta.lookups_t
        if lt is not None:
            mask = lt > 0
            rate = np.where(mask, delta.hits_t / np.maximum(lt, 1), 0.0)
            m.ewma(f"{self.obs_name}.hit_rate_t").update(rate, mask=mask)

    def _record_scored(self, reqs, t_scored: float) -> None:
        """Close each scored request's enqueue->score latency span,
        feed the windowed instruments, and tick the window over: one
        scored micro-batch = one tick (SLO listeners fire, then the
        engine's windows rotate)."""
        if self.telemetry is None:
            return
        m = self.telemetry.metrics
        w = self.telemetry.window
        lat = m.windowed_histogram(f"{self.obs_name}.request_latency_s",
                                   unit="s", window=w)
        wait = m.windowed_histogram(f"{self.obs_name}.queue_wait_s",
                                    unit="s", window=w)
        service = m.windowed_histogram(f"{self.obs_name}.service_s",
                                       unit="s", window=w)
        for req in reqs:
            t_enq = self._enqueue_t.pop(req.rid, None)
            if t_enq is None:
                continue
            self.telemetry.record_request(self.obs_name, req.rid,
                                          t_enq, t_scored)
            lat.observe(max(0.0, t_scored - t_enq))
            t_deq = self._dequeue_t.pop(req.rid, None)
            if t_deq is not None:
                wait.observe(max(0.0, t_deq - t_enq))
                service.observe(max(0.0, t_scored - t_deq))
        self._observe_cache_window()
        self.telemetry.batch_tick(self.obs_name)

    def cache_stats(self):
        """The tiered cache's CacheStats (None when the cache is off).

        Miss traffic is split by source tier: ``bytes_h2d`` /
        ``misses_host`` for rows the serving host owns, ``bytes_remote``
        / ``misses_remote`` for rows fetched from peer hosts — see
        repro/cache/stats.py for the counting semantics.  Per-stage
        wall-clock spans (``prefetch_s`` / ``scatter_s`` / ``forward_s``
        / ``overlap_s``) are recorded by BOTH engines, so serialized and
        pipelined runs are directly comparable."""
        return None if self.cache is None else self.cache.stats

    def run_to_completion(self) -> Dict[int, float]:
        out: Dict[int, float] = {}
        while self.queue:
            out.update(self.flush())
        return out


class PipelinedDLRMEngine(DLRMEngine):
    """DLRM scoring as a software pipeline over double-buffered pools.

    ``run_to_completion`` carves the queue into micro-batches and drives
    the ``admit -> fetch -> scatter -> forward -> swap`` scheduler
    (repro/pipeline/): batch k+1's admission scatter and cold-tier
    ``fetch_rows`` target the shadow buffer while batch k's fused-TBE
    forward reads the live one.  Scores are BITWISE equal to the
    serialized :class:`DLRMEngine` — only the latency structure changes.

    ``flush`` stays the SERIALIZED path against the live buffer: it is
    both the one-micro-batch API and the pipeline's head-of-line
    fallback — a batch whose working set overflows the shadow buffer
    falls back to the inherited split-on-``CacheCapacityError`` loop
    instead of deadlocking the ring.

    Observability: ``self.trace`` holds every stage's wall-clock
    :class:`~repro.pipeline.StageSpan`; the shared ``cache_stats()``
    record carries the same ``prefetch_s/scatter_s/forward_s`` spans the
    serialized engine logs, plus the measured ``overlap_s``.
    """

    OBS_NAME = "dlrm_pipelined"

    def __init__(self, params, cfg: DLRMConfig, batch_size: int,
                 ctx: Optional[ParallelContext] = None, *,
                 telemetry=None, obs_name: Optional[str] = None):
        if cfg.cache.pipeline_depth < 2:
            raise ValueError(
                f"PipelinedDLRMEngine needs pipeline_depth >= 2 (got "
                f"{cfg.cache.pipeline_depth}); depth 1 is the serialized "
                f"DLRMEngine — use make_dlrm_engine to pick by config")
        if not cfg.cache.enabled and cfg.sharding_plan is None:
            raise ValueError(
                "PipelinedDLRMEngine requires the tiered cache (an enabled "
                "cfg.cache — CacheConfig.rows > 0, formerly cache_rows — "
                "or a cfg.sharding_plan): with fully device-resident "
                "tables there is no prefetch stage to overlap")
        from repro.pipeline import PipelineScheduler, PipelineTrace

        super().__init__(params, cfg, batch_size, ctx,
                         telemetry=telemetry, obs_name=obs_name)
        self.trace = PipelineTrace(
            tracer=None if telemetry is None else telemetry.tracer,
            label=self.obs_name,
            metrics=None if telemetry is None else telemetry.metrics,
            window=32 if telemetry is None else telemetry.window)
        self.scheduler = PipelineScheduler(
            self.cache, forward=self._pipeline_forward,
            collect=self._pipeline_collect, fallback=self._pipeline_fallback,
            prestage=self._pipeline_prestage, trace=self.trace)

    def _make_cache(self, tables, ebcfg):
        from repro.pipeline import DoubleBufferedSlotPool

        return DoubleBufferedSlotPool(tables, ebcfg,
                                      depth=self.cfg.cache.pipeline_depth)

    # -- scheduler hooks -----------------------------------------------------

    def _pipeline_prestage(self, payload, remapped, lengths):
        """Stage the forward's device operands (runs on the scheduler's
        background thread, hidden under the in-flight forward)."""
        _, dense = payload
        return (jnp.asarray(dense),
                JaggedBatch(indices=jnp.asarray(remapped),
                            lengths=jnp.asarray(lengths)))

    def _pipeline_forward(self, payload, remapped, lengths, pool, *,
                          staged=None):
        """DISPATCH one micro-batch's jitted forward over ``pool``."""
        if staged is None:
            staged = self._pipeline_prestage(payload, remapped, lengths)
        dense, batch = staged
        params = {**self.params, "tables": pool}
        return self._fwd(params, dense, batch)

    def _pipeline_collect(self, payload, host_scores) -> Dict[int, float]:
        todo, _ = payload
        self._record_scored(todo, time.perf_counter())
        return {req.rid: float(host_scores[i])
                for i, req in enumerate(todo)}

    def _pipeline_fallback(self, payload) -> Dict[int, float]:
        """Serialized split flush for an overflowing micro-batch: requeue
        just this batch and reuse the inherited CacheCapacityError split
        loop against the LIVE buffer."""
        todo, _ = payload
        rest = self.queue
        self.queue = list(todo)
        try:
            scores: Dict[int, float] = {}
            while self.queue:
                scores.update(DLRMEngine.flush(self))
        finally:
            self.queue = rest
        return scores

    # -- pipelined serving ---------------------------------------------------

    def run_to_completion(self) -> Dict[int, float]:
        """Score the whole queue through the stage pipeline.

        The serialized engine's "requests survive a failed flush"
        contract holds here too: if the pipeline dies mid-run (e.g. a
        cold-tier fetch failure — its residency is already rolled
        back), every submitted request goes back on the queue; the
        raising call delivered no scores, so a retry re-scores them all
        (deterministic — same results)."""
        batches, submitted = [], []
        while self.queue:
            todo = self.queue[: self.batch_size]
            self._stamp_dequeue(todo)
            self.queue = self.queue[len(todo):]
            submitted.extend(todo)
            with span("repro.engine.pad"):
                dense, idx, lens = self._pad_batch(todo)
            batches.append(((todo, dense), idx, lens))
        out: Dict[int, float] = {}
        try:
            self.scheduler.run(batches, out)
        except BaseException:
            self.queue = submitted + self.queue
            raise
        return out


def make_dlrm_engine(params, cfg: DLRMConfig, batch_size: int,
                     ctx: Optional[ParallelContext] = None, *,
                     telemetry=None,
                     obs_name: Optional[str] = None) -> DLRMEngine:
    """Build the engine ``cfg.cache.pipeline_depth`` selects: 1 =
    serialized :class:`DLRMEngine`, >= 2 = :class:`PipelinedDLRMEngine`
    over a ``pipeline_depth``-deep double-buffered slot-pool ring.
    ``telemetry``/``obs_name`` thread through to the engine (see
    :class:`DLRMEngine` — the unified-timeline opt-in)."""
    cls = PipelinedDLRMEngine if cfg.cache.pipeline_depth > 1 else DLRMEngine
    return cls(params, cfg, batch_size, ctx, telemetry=telemetry,
               obs_name=obs_name)


# ---------------------------------------------------------------------------
# Kernel contracts (audited by repro.analysis)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import KernelContract  # noqa: E402

KERNEL_CONTRACTS = {
    "tiered_forward": KernelContract(
        name="serving.engine.tiered_forward",
        note="the tiered serving program (flat-pool DLRM forward + "
             "sigmoid) runs ONE fused TBE launch and must compile to "
             "ZERO collectives and ZERO host callbacks — all cold-tier "
             "traffic happens in the explicit prefetch phase"),
}
