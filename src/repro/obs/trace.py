"""``Tracer`` — one merged timeline, exportable as Chrome trace-event JSON,
and :class:`span`, the one call that times every serving stage.

Before this module the repro had three disjoint timing fragments:
``CacheStats`` stage timers, the pipeline's ``StageSpan`` list, and
``comm.instrument()`` collective events — none sharing a clock.  The
tracer merges all of them onto ONE monotonic clock
(``time.perf_counter``, the clock every existing timer already uses) and
exports the result in the Chrome trace-event format, so a serving run
opens directly in Perfetto (https://ui.perfetto.dev) or
``chrome://tracing``.

Every stage boundary of the serving path is ONE :class:`span` call,
opened where the work runs, on the thread that runs it.  It enters a
``jax.profiler.TraceAnnotation`` under the stage's profiler name
(``repro.<layer>.<stage>``), so a ``jax.profiler`` trace shows the
program's stages beside the device's ops on one clock; lands the
interval on an attached tracer's lane under its in-memory name; and
keeps its own ``t0``/``t1``, which ``CacheStats``, ``PipelineTrace`` and
the comm events read instead of timing the interval again.

Lanes (Chrome ``tid``s inside one ``pid``):

  ======== ===========================================================
  lane     what lands there
  ======== ===========================================================
  engine   ``DLRMEngine.flush`` prefetch/forward spans
  pipeline the pipelined engine's stage spans (admit/fetch/scatter/
           forward/swap), one per ``PipelineTrace`` ``StageSpan``
  request  per-request enqueue -> score latency spans
  cache    ``CachedEmbeddingBag`` admit/fetch/scatter spans (bytes in
           ``args``)
  comm     timestamped ``CollectiveEvent``s (``comm.fetch_rows`` etc.)
  slo      zero-duration SLO breach / drift events from
           ``repro.obs.slo`` (the structured event dict in ``args``)
  ======== ===========================================================

Export schema: every event is a complete-event (``ph: "X"``) or
metadata (``ph: "M"``) record carrying ``ph/ts/dur/pid/tid/name`` —
``ts``/``dur`` in microseconds relative to the tracer's epoch, as the
format requires.  :func:`validate_chrome_trace` pins that contract (the
golden-schema test runs it).

Threading: ``add_span`` locks, so the pipeline's background prefetch
threads and the main serving thread interleave safely on one timeline.
"""
from __future__ import annotations

import dataclasses
import json
import threading
import time
from typing import Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

# lane name -> Chrome tid; the export emits one thread_name metadata
# record per lane so Perfetto labels the rows
LANES: Dict[str, int] = {
    "engine": 0,
    "pipeline": 1,
    "request": 2,
    "cache": 3,
    "comm": 4,
    "slo": 5,
}


@dataclasses.dataclass(frozen=True)
class Span:
    """One named interval on the merged timeline (perf_counter seconds)."""

    name: str
    t0: float
    t1: float
    lane: str = "engine"
    cat: str = ""
    args: Optional[Dict[str, object]] = None

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


# one entry per ``jax.profiler`` session this process has seen, in order:
# profiler name -> [seconds, count] of the spans that opened and closed
# inside it.  Process-wide, as the profiler is: it lets a reader of one
# traced window total the stages without parsing the trace.  Spans close
# on the main and the prefetch threads alike.
_SESSIONS: List[Dict[str, List[float]]] = []
_RECORDING = [False]     # whether the last span found the profiler on
_PROFILED_LOCK = threading.Lock()


def _session() -> int:
    """Index in ``_SESSIONS`` of the profiler session recording now, or
    -1.  A span that finds the profiler on after one found it off opens
    the next session; two sessions with no span between them count as
    one."""
    if not TraceAnnotation.is_enabled():
        _RECORDING[0] = False
        return -1
    if not _RECORDING[0]:
        with _PROFILED_LOCK:
            if not _RECORDING[0]:
                _SESSIONS.append({})
                _RECORDING[0] = True
    return len(_SESSIONS) - 1


class span:
    """One stage interval, timed once, where the work runs.

    ``with span("repro.cache.admit", tracer, "cache.admit", lane="cache")
    as s:`` does three things:

      * enters ``jax.profiler.TraceAnnotation(name)``, which a running
        profiler records on the host plane of the thread that opened it;
      * on exit, lands the interval on ``tracer`` (a :class:`Tracer`, or
        None) under ``label`` (default ``name``) with ``lane``/``cat``/
        ``args``, as :meth:`Tracer.add_span` does — also when the body
        raised, since the time was spent;
      * exposes ``t0``/``t1`` (``perf_counter`` stamps) and ``seconds``
        after exit, for the stage timers that read them.

    ``name`` is the profiler's ``repro.<layer>.<stage>``; ``label`` the
    in-memory lane name (``cache.admit``, ``pipeline.fetch``, ...).
    """

    __slots__ = ("name", "tracer", "label", "lane", "cat", "args", "t0",
                 "t1", "_annotation", "_session")

    def __init__(self, name: str, tracer: Optional["Tracer"] = None,
                 label: Optional[str] = None, *, lane: str = "engine",
                 cat: str = "", args: Optional[Dict[str, object]] = None):
        self.name, self.tracer, self.label = name, tracer, label
        self.lane, self.cat, self.args = lane, cat, args
        self.t0 = self.t1 = 0.0

    def __enter__(self) -> "span":
        self._annotation = TraceAnnotation(self.name)
        self._annotation.__enter__()
        self._session = _session()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        session = _session()
        if session >= 0 and session == self._session:
            with _PROFILED_LOCK:
                acc = _SESSIONS[session].setdefault(self.name, [0.0, 0])
                acc[0] += self.t1 - self.t0
                acc[1] += 1
        if self.tracer is not None:
            self.tracer.add_span(self.label or self.name, self.t0, self.t1,
                                 lane=self.lane, cat=self.cat,
                                 args=self.args)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def profiled_spans() -> List[Dict[str, Tuple[float, int]]]:
    """For each ``jax.profiler`` session of this process, in order, the
    seconds and count per profiler name of the :class:`span` calls that
    opened and closed while it recorded: the program's stages of each
    traced window, totalled."""
    with _PROFILED_LOCK:
        return [{name: (acc[0], int(acc[1])) for name, acc in totals.items()}
                for totals in _SESSIONS]


def validate_chrome_trace(obj) -> int:
    """Assert ``obj`` is well-formed Chrome trace-event JSON; returns the
    event count.  Every event must carry valid ``ph``/``ts``/``dur``/
    ``pid``/``tid``/``name`` fields — the contract the golden-schema test
    pins."""
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        raise ValueError("trace must be a dict with a 'traceEvents' list")
    events = obj["traceEvents"]
    if not isinstance(events, list):
        raise ValueError("traceEvents must be a list")
    for i, e in enumerate(events):
        for key in ("ph", "ts", "dur", "pid", "tid", "name"):
            if key not in e:
                raise ValueError(f"event {i} missing {key!r}: {e}")
        if e["ph"] not in ("X", "M"):
            raise ValueError(f"event {i} has unknown phase {e['ph']!r}")
        for key in ("ts", "dur"):
            v = e[key]
            if not isinstance(v, (int, float)) or v < 0:
                raise ValueError(f"event {i} has invalid {key}: {v!r}")
        for key in ("pid", "tid"):
            if not isinstance(e[key], int):
                raise ValueError(f"event {i} has non-int {key}: {e[key]!r}")
    return len(events)


class Tracer:
    """Process-wide span recorder on the ``perf_counter`` clock.

    ``enabled=False`` turns every record call into a no-op.  The
    engines' :class:`span` calls run whether or not a tracer is
    attached (the profiler sees them); without one they land nothing
    here.
    """

    def __init__(self, *, enabled: bool = True, pid: int = 0):
        self.enabled = enabled
        self.pid = pid
        self.epoch = time.perf_counter()   # ts origin of the export
        self._spans: List[Span] = []
        self._lock = threading.Lock()
        self._prev_sink = None
        self._sink_installed = False

    # -- recording -----------------------------------------------------------

    @staticmethod
    def now() -> float:
        """The timeline's clock — use for spans recorded by hand."""
        return time.perf_counter()

    def add_span(self, name: str, t0: float, t1: float, *,
                 lane: str = "engine", cat: str = "",
                 args: Optional[Dict[str, object]] = None) -> None:
        if not self.enabled:
            return
        if lane not in LANES:
            raise ValueError(f"unknown lane {lane!r}; one of {list(LANES)}")
        with self._lock:
            self._spans.append(Span(name, t0, t1, lane, cat, args))

    def span(self, name: str, *, lane: str = "engine", cat: str = "",
             args: Optional[Dict[str, object]] = None) -> span:
        """A :class:`span` named ``name`` on the profiler and on this
        timeline."""
        return span(name, self, lane=lane, cat=cat, args=args)

    def add_collective_event(self, ev, *, name: Optional[str] = None) -> bool:
        """Land one :class:`repro.core.comm.CollectiveEvent` on the comm
        lane.  Events without wall-clock stamps (``t0 == t1 == 0.0``, the
        back-compat default) are skipped — returns whether it landed."""
        if not self.enabled or (ev.t0 == 0.0 and ev.t1 == 0.0):
            return False
        self.add_span(name or ev.op, ev.t0, ev.t1, lane="comm", cat="comm",
                      args={"bytes": ev.bytes_in, "axis_size": ev.axis_size,
                            "backend": ev.backend})
        return True

    # -- comm integration ----------------------------------------------------

    def install_comm_sink(self) -> None:
        """Route every ``comm`` collective event (including runtime
        ``fetch_rows`` records from background threads) onto this
        timeline until :meth:`remove_comm_sink`."""
        from repro.core import comm

        if self._sink_installed:
            return
        self._prev_sink = comm.set_event_sink(self.add_collective_event)
        self._sink_installed = True

    def remove_comm_sink(self) -> None:
        from repro.core import comm

        if self._sink_installed:
            comm.set_event_sink(self._prev_sink)
            self._prev_sink, self._sink_installed = None, False

    # -- readout -------------------------------------------------------------

    def spans(self, *, lane: Optional[str] = None,
              cat: Optional[str] = None,
              name: Optional[str] = None) -> List[Span]:
        with self._lock:
            out = list(self._spans)
        if lane is not None:
            out = [s for s in out if s.lane == lane]
        if cat is not None:
            out = [s for s in out if s.cat == cat]
        if name is not None:
            out = [s for s in out if s.name == name]
        return out

    @property
    def event_count(self) -> int:
        with self._lock:
            return len(self._spans)

    def clear(self) -> None:
        with self._lock:
            self._spans = []

    # -- export --------------------------------------------------------------

    def to_chrome(self) -> Dict[str, object]:
        """The merged timeline as a Chrome trace-event object
        (``{"traceEvents": [...]}`` — load in Perfetto as-is)."""
        events: List[Dict[str, object]] = [
            {"ph": "M", "ts": 0, "dur": 0, "pid": self.pid, "tid": tid,
             "name": "thread_name", "args": {"name": lane}}
            for lane, tid in LANES.items()]
        for s in self.spans():
            ev = {
                "ph": "X",
                "ts": max(0.0, (s.t0 - self.epoch) * 1e6),
                "dur": max(0.0, (s.t1 - s.t0) * 1e6),
                "pid": self.pid,
                "tid": LANES[s.lane],
                "name": s.name,
            }
            if s.cat:
                ev["cat"] = s.cat
            if s.args:
                ev["args"] = dict(s.args)
            events.append(ev)
        return {"traceEvents": events,
                "displayTimeUnit": "ms"}

    def export(self, path: str) -> str:
        """Write the Chrome trace JSON to ``path``; returns ``path``."""
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path
