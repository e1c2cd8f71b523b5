"""Readers of the program's own stage spans.

The program times each serving stage with one ``repro.obs.span`` call,
named ``repro.<layer>.<stage>`` on the profiler, and
``repro.obs.profiled_spans()`` totals, per profiler session, the spans
that opened and closed while it recorded.  A ``--trace 1`` run starts
one session, around the measured window, so that session's totals are
the window's.  Each reader gives host milliseconds per micro-batch of
the window in some stages, and returns None where the program keeps no
such spans (a program older than the span call, or a cell that does not
run the stage) or where the process saw other than one session, so the
run leaves the metric out of its line.
"""
from __future__ import annotations

ENGINE_HOST = ("repro.engine.pad", "repro.engine.dispatch",
               "repro.engine.collect")


def profiled():
    """The program's span totals of the traced window, ``{name: (seconds,
    count)}``: those of the process's one profiler session.  None where
    the program keeps none, or where the process saw more sessions than
    one, whose totals could not be told apart from the window's."""
    try:
        from repro.obs import profiled_spans
    except ImportError:
        return None
    sessions = profiled_spans()
    return sessions[0] if len(sessions) == 1 else None


def ms_per_batch(run, names) -> float | None:
    """Seconds in the spans ``names``, all of which the window must hold,
    per micro-batch of the window, in milliseconds."""
    spans = profiled()
    if run.trace is None or not run.window.batches or not spans:
        return None
    if not all(n in spans for n in names):
        return None
    return sum(spans[n][0] for n in names) / len(run.window.batches) * 1e3


def flush_host_ms(run):
    """The engine's host work around each micro-batch's forward: padding
    the requests, moving operands and dispatching the scorer, and
    building the answers."""
    return ms_per_batch(run, ENGINE_HOST)


def admit_ms(run):
    """The tiered store's admission (slot planning, LFU victims)."""
    return ms_per_batch(run, ("repro.cache.admit",))


def fetch_ms(run):
    """The tiered store's gather of missed rows from the cold tier."""
    return ms_per_batch(run, ("repro.cache.fetch",))


def scatter_ms(run):
    """The tiered store's pool scatter: padding, host-to-device copy and
    the scatter's dispatch."""
    return ms_per_batch(run, ("repro.cache.scatter",))
