"""Stage scheduler: ``admit -> fetch -> scatter -> forward -> swap``.

The software pipeline over a :class:`DoubleBufferedSlotPool`.  One
micro-batch is in flight on the device at a time; while its forward
runs, the NEXT batch moves through the host-side stages against the
shadow buffer:

  admit    shadow-manager metadata (``prepare_next``) — numpy, run on
           the BACKGROUND prefetch thread: the shadow buffer's state is
           untouched by the in-flight batch, so the whole admission
           plans under the live forward;
  fetch    the cold-tier row fetch on the same background thread (numpy
           gathers and the ``fetch_rows`` collective both release the
           GIL), started BEFORE the previous forward's scores are
           materialized so the two genuinely overlap whichever way the
           backend dispatches (async: the materialize blocks while the
           thread fetches; sync: the dispatch itself computes under the
           thread);
  scatter  the flat donated-jit pool scatter into the shadow buffer,
           dispatched from the SAME background thread: it touches only
           the shadow pool (the in-flight forward reads the live one),
           so its host-side staging cost hides under the forward too,
           and no ``block_until_ready`` is ever needed between stages —
           dispatch order alone guarantees the scatter lands before the
           batch's own forward reads the pool;
  forward  dispatch the batch's forward on the (about-to-be-live)
           shadow pool; its scores are materialized one iteration
           later, under the NEXT batch's prefetch stages;
  swap     rotate the ring (``DoubleBufferedSlotPool.swap``) — the
           prepared epoch is published.

Overlap is OBSERVED, not assumed: every stage is timed by one
``repro.obs.span`` call on the thread that runs it (so a ``jax.profiler``
trace shows it as ``repro.cache.admit`` / ``fetch`` / ``scatter``,
``repro.pipeline.prestage`` / ``swap``, ``repro.engine.dispatch`` /
``collect``), and its stamps become a :class:`StageSpan` in a
:class:`PipelineTrace`; ``overlap_s`` is the measured intersection of
prefetch-side spans (admit/fetch) with open forward spans, and is pushed
into the shared ``CacheStats`` so the serialized and pipelined engines
report comparable numbers.

Head-of-line behavior: a micro-batch whose working set overflows the
shadow buffer (``CacheCapacityError`` from admit — atomic, nothing to
roll back) drains the in-flight forward and falls back to the caller's
serialized split flush, then the pipeline resumes.  A failed background
fetch already invalidated its slots (``fetch_next``); the error is
re-raised after the in-flight batch's scores are safely materialized.
"""
from __future__ import annotations

import dataclasses
import functools
import threading
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.cache.manager import CacheCapacityError
from repro.obs.trace import span
from repro.pipeline.double_buffer import DoubleBufferedSlotPool

STAGES = ("admit", "fetch", "scatter", "forward", "swap")


@dataclasses.dataclass(frozen=True)
class StageSpan:
    """One stage's wall-clock span for one micro-batch."""

    stage: str
    batch: int
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start


def _overlap(spans: Sequence[StageSpan]) -> float:
    """Prefetch-side wall-clock (admit + fetch spans) of ``spans`` that
    lies inside one of their forward spans."""
    fwd = [(s.start, s.end) for s in spans if s.stage == "forward"]
    out = 0.0
    for s in spans:
        if s.stage not in ("admit", "fetch"):
            continue
        for f0, f1 in fwd:
            out += max(0.0, min(s.end, f1) - max(s.start, f0))
    return out


class _StageSpanCall(span):
    """A :class:`repro.obs.span` that hands its stamps to ``keep`` when
    its body returns."""

    __slots__ = ("_keep",)

    def __init__(self, *args, keep=None, **kw):
        super().__init__(*args, **kw)
        self._keep = keep

    def __exit__(self, *exc) -> bool:
        super().__exit__(*exc)
        if self._keep is not None and exc[0] is None:
            self._keep(self.t0, self.t1)
        return False


class PipelineTrace:
    """Recorded stage spans — the pipeline's observability surface.

    Each stage is timed once, by the :class:`repro.obs.span` call that
    :meth:`stage` makes, which keeps its stamps as a :class:`StageSpan`
    (:meth:`record`).  With a ``tracer`` (:class:`repro.obs.Tracer`)
    that call also lands the stage on the unified timeline's pipeline
    lane as ``pipeline.<stage>``, tagged with the owning engine's
    ``label`` — one merged view across the serialized and pipelined
    engines.  The forward, built from other spans' stamps, lands there
    through :meth:`derived`.

    With a ``metrics`` registry (:class:`repro.obs.MetricsRegistry`)
    every span additionally feeds a per-stage WINDOWED histogram
    ``<label>.stage.<stage>_s`` — the live per-stage latency readout.
    The engine passes ``label=obs_name``, so the names sit under the
    engine's prefix and rotate with its ``batch_tick``.
    """

    def __init__(self, tracer=None, label: str = "pipeline",
                 metrics=None, window: int = 32):
        self.spans: List[StageSpan] = []
        self.tracer = tracer
        self.label = label
        self.metrics = metrics
        self.window = window
        self._run_start = 0          # first span of the run still open
        self._closed_overlap = 0.0   # overlap of every closed run

    @staticmethod
    def _check(stage: str) -> None:
        if stage not in STAGES:
            raise ValueError(f"unknown stage {stage!r}; one of {STAGES}")

    def _lane_args(self, batch: int) -> Dict[str, object]:
        return {"engine": self.label, "batch": batch}

    def stage(self, stage: str, batch: int, name: str, *,
              keep: bool = True) -> "_StageSpanCall":
        """The span call that times ``stage`` of micro-batch ``batch``:
        ``name`` on the profiler, ``pipeline.<stage>`` on the tracer.
        With ``keep`` it keeps its stamps (:meth:`record`) when its body
        returns; a worker thread passes ``keep=False`` and the thread that
        owns the trace records the closed span's stamps after joining."""
        self._check(stage)
        return _StageSpanCall(
            name, self.tracer, f"pipeline.{stage}", lane="pipeline",
            cat="pipeline", args=self._lane_args(batch),
            keep=functools.partial(self.record, stage, batch) if keep
            else None)

    def record(self, stage: str, batch: int, start: float,
               end: float) -> None:
        self._check(stage)
        self.spans.append(StageSpan(stage, batch, start, end))
        if self.metrics is not None:
            self.metrics.windowed_histogram(
                f"{self.label}.stage.{stage}_s", unit="s",
                window=self.window).observe(max(0.0, end - start))

    def derived(self, stage: str, batch: int, start: float,
                end: float) -> None:
        """Record an interval built from other spans' stamps, which no
        span call times, and land it on the tracer's pipeline lane."""
        self.record(stage, batch, start, end)
        if self.tracer is not None:
            self.tracer.add_span(f"pipeline.{stage}", start, end,
                                 lane="pipeline", cat="pipeline",
                                 args=self._lane_args(batch))

    def by_stage(self, stage: str) -> List[StageSpan]:
        return [s for s in self.spans if s.stage == stage]

    def total(self, stage: str) -> float:
        return sum(s.seconds for s in self.by_stage(stage))

    def overlap_s(self) -> float:
        """Prefetch-side wall-clock (admit + fetch spans) that lies
        inside a forward span — the measured hidden latency."""
        return self._closed_overlap + _overlap(self.spans[self._run_start:])

    def close_run(self) -> float:
        """End one scheduler run: add the overlap of the spans recorded
        since the last close to the total and return it.  A run drains
        its in-flight forward before it returns, so no overlap crosses
        runs, and each close costs only its own run's spans."""
        run = _overlap(self.spans[self._run_start:])
        self._closed_overlap += run
        self._run_start = len(self.spans)
        return run

    def overlap_fraction(self) -> float:
        pre = self.total("admit") + self.total("fetch")
        return min(1.0, self.overlap_s() / pre) if pre > 0 else 0.0

    def clear(self) -> None:
        self.spans = []
        self._run_start = 0
        self._closed_overlap = 0.0


class PipelineScheduler:
    """Drives the stage pipeline over caller-supplied micro-batches.

    The caller provides three callables so the scheduler stays
    model-agnostic:

      ``forward(payload, remapped, lengths, pool, staged=None)`` —
        DISPATCH the batch's forward over the given device pool and
        return the un-materialized device output (no
        ``block_until_ready``); ``staged`` is whatever ``prestage``
        returned for this batch (None when no prestage hook is set);
      ``collect(payload, host_out)`` — turn materialized scores into
        the caller's result dict;
      ``fallback(payload)`` — serialized split flush for a batch whose
        working set overflowed the shadow buffer;
      ``prestage(payload, remapped, lengths)`` (optional) — build the
        forward's device operands; runs on the BACKGROUND thread right
        after the scatter so host->device staging also hides under the
        in-flight forward.
    """

    def __init__(self, pool: DoubleBufferedSlotPool, *,
                 forward: Callable[..., Any],
                 collect: Callable[[Any, np.ndarray], Dict],
                 fallback: Callable[[Any], Dict],
                 prestage: Optional[Callable[..., Any]] = None,
                 trace: Optional[PipelineTrace] = None):
        self.pool = pool
        self.forward, self.collect, self.fallback = forward, collect, fallback
        self.prestage = prestage
        self.trace = trace if trace is not None else PipelineTrace()
        self._seq = 0                 # global micro-batch counter (spans)

    def run(self, batches: Sequence[Tuple[Any, np.ndarray, np.ndarray]],
            out: Optional[Dict] = None) -> Dict:
        """Pipeline ``batches`` (payload, (T,B,L) indices, (T,B) lengths)
        through the ring; returns the union of ``collect``ed results.

        Results accumulate into ``out`` IN PLACE as each batch drains,
        so a caller passing its own dict keeps every already-scored
        result even when a later stage raises — the engine uses this to
        requeue only the genuinely unscored requests."""
        stats = self.pool.stats
        trace = self.trace
        if out is None:
            out = {}
        inflight = None     # (payload, device_out, dispatch_t0, batch_id)
        for payload, indices, lengths in batches:
            k = self._seq
            self._seq += 1
            # -- admit + fetch + scatter for batch k on a background
            #    thread: every stage touches only the SHADOW buffer (the
            #    in-flight forward reads the live one), so the whole
            #    prefetch pipeline hides under batch k-1's forward...
            box: Dict[str, Any] = {"stages": []}

            def _worker(box=box, k=k, payload=payload, indices=indices,
                        lengths=lengths):
                def stage(name, profiled):
                    s = trace.stage(name, k, profiled, keep=False)
                    box["stages"].append((name, s))
                    return s

                try:
                    with stage("admit", "repro.cache.admit"):
                        plan = box["plan"] = self.pool.prepare_next(indices,
                                                                    lengths)
                    with stage("fetch", "repro.cache.fetch"):
                        rows = self.pool.fetch_next(plan)
                    with stage("scatter", "repro.cache.scatter"):
                        self.pool.commit_next(plan, rows)
                    if self.prestage is not None:   # operand staging too
                        pre = box["prestage"] = span("repro.pipeline.prestage")
                        with pre:
                            box["staged"] = self.prestage(
                                payload, plan.remapped, lengths)
                except BaseException as e:  # noqa: BLE001 — rethrown below
                    box["err"] = e

            # one short-lived thread per micro-batch: spawn cost is tens
            # of microseconds against millisecond-scale batches, and a
            # dead thread can never leak a half-finished stage into the
            # next batch the way a reused worker could
            th = threading.Thread(target=_worker, daemon=True)
            th.start()
            # -- ...while batch k-1's forward completes under it
            if inflight is not None:
                out.update(self._drain(inflight))
                inflight = None
            th.join()
            # the worker's spans are closed: keep their stamps here, on
            # the thread that owns the trace and its histograms
            for name, s in box["stages"]:
                trace.record(name, k, s.t0, s.t1)
                stats.add_time("scatter" if name == "scatter"
                               else "prefetch", s.seconds)
            if "prestage" in box:   # operand staging counts as scatter time
                stats.add_time("scatter", box["prestage"].seconds)
            err = box.get("err")
            if isinstance(err, CacheCapacityError):
                # head-of-line fallback: the working set overflowed the
                # shadow buffer (atomic — nothing admitted); score this
                # batch through the serialized split path and resume
                out.update(self.fallback(payload))
                continue
            if err is not None:    # residency already invalidated in-thread
                raise err
            # -- dispatch forward k on the shadow pool, then publish it
            plan = box["plan"]
            with span("repro.engine.dispatch") as dispatch:
                dev = self.forward(payload, plan.remapped, lengths,
                                   self.pool.shadow.pool,
                                   staged=box.get("staged"))
            with trace.stage("swap", k, "repro.pipeline.swap"):
                self.pool.swap()
            inflight = (payload, dev, dispatch.t0, k)
        if inflight is not None:
            out.update(self._drain(inflight))
        # the run's measured overlap, into the shared stats record
        stats.add_time("overlap", trace.close_run())
        return out

    def _drain(self, inflight) -> Dict:
        """Materialize the in-flight forward's scores (the only blocking
        point of the pipeline) and record its span: from its dispatch
        until its scores are on the host."""
        payload, dev, t_dispatch, k = inflight
        host = np.asarray(dev)
        with span("repro.engine.collect") as collect:
            self.trace.derived("forward", k, t_dispatch, collect.t0)
            self.pool.stats.add_time("forward", collect.t0 - t_dispatch)
            return self.collect(payload, host)
