"""From a profiler trace to the numbers the per-layer metrics read.

The profiler writes an ``.xplane.pb``; :func:`load` flattens it to
``Event`` tuples and :func:`summarize` reduces them, so that the
reduction can be checked on synthetic events.  Device planes are
``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds one event per
operation that ran.  The host plane holds the benchmark's own
``TraceAnnotation`` spans, named ``bench.<activity>``; ``bench.window``
spans the measured window and bounds every device number.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
COLLECTIVE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)")


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float


def load(log_dir: str) -> List[Event]:
    """Every event of the one ``.xplane.pb`` under ``log_dir``."""
    import jax

    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"want one trace under {log_dir}, found {paths}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    return [Event(plane.name, line.name, ev.name, ev.start_ns, ev.duration_ns)
            for plane in data.planes for line in plane.lines
            for ev in line.events]


def union_ns(intervals: Iterable[Tuple[float, float]], lo: float,
             hi: float) -> float:
    """Length of the union of ``[start, end)`` intervals clipped to
    ``[lo, hi)``."""
    total, reach = 0.0, lo
    for s, e in sorted(intervals):
        s, e = max(s, reach), min(e, hi)
        if e > s:
            total += e - s
            reach = e
    return total


def gaps(intervals: Iterable[Tuple[float, float]], lo: float,
         hi: float) -> List[Tuple[float, float]]:
    """The idle ``[start, end)`` stretches of ``[lo, hi)`` that no
    interval covers."""
    out, reach = [], lo
    for s, e in sorted(intervals):
        if s > reach:
            out.append((reach, min(s, hi)))
        reach = max(reach, e)
        if reach >= hi:
            break
    if reach < hi:
        out.append((reach, hi))
    return [(s, e) for s, e in out if e > s]


@dataclasses.dataclass
class Summary:
    """Device time over the traced window, per chip."""

    window_s: float
    busy_s: List[float]                 # union of op intervals, per chip
    op_s: List[Dict[str, float]]        # seconds per op name, per chip
    idle_by_host: Dict[str, float]      # idle seconds of chip 0, by host span

    @property
    def chips(self) -> int:
        return len(self.busy_s)

    def busy_mean_s(self) -> float:
        return sum(self.busy_s) / self.chips

    def matching_s(self, pattern: str) -> List[float]:
        """Seconds per chip in ops whose name matches ``pattern``."""
        rx = re.compile(pattern)
        return [sum(s for name, s in ops.items() if rx.search(name))
                for ops in self.op_s]

    def breakdown(self, n: int = 10) -> dict:
        """The ``n`` ops that took most time (mean over chips) and the
        host activities under which chip 0 was idle longest."""
        ops: Dict[str, float] = {}
        for per_chip in self.op_s:
            for name, s in per_chip.items():
                ops[name] = ops.get(name, 0.0) + s / self.chips
        top = sorted(ops.items(), key=lambda kv: -kv[1])[:n]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in idle]}


def summarize(events: List[Event], chips: int) -> Summary:
    """Reduce the events of one traced window (the one ``bench.window``
    host span) on chips ``0 .. chips - 1``."""
    windows = [e for e in events if e.name == WINDOW
               and not DEVICE_PLANE.match(e.plane)]
    if len(windows) != 1:
        raise RuntimeError(f"want one {WINDOW} span, found {len(windows)}")
    lo = windows[0].start_ns
    hi = lo + windows[0].dur_ns
    ops: List[List[Tuple[float, float, str]]] = [[] for _ in range(chips)]
    for e in events:
        m = DEVICE_PLANE.match(e.plane)
        if m and e.line == OPS_LINE and int(m.group(1)) < chips:
            s, t = max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi)
            if t > s:
                ops[int(m.group(1))].append((s, t, e.name))
    if not any(ops):
        raise RuntimeError("the trace holds no device op in the window")
    busy, op_s = [], []
    for chip in ops:
        busy.append(union_ns(((s, t) for s, t, _ in chip), lo, hi) * 1e-9)
        per: Dict[str, float] = {}
        for s, t, name in chip:
            name = _short(name)
            per[name] = per.get(name, 0.0) + (t - s) * 1e-9
        op_s.append(per)
    host = sorted((e.start_ns, e.start_ns + e.dur_ns, e.name) for e in events
                  if e.name.startswith("bench.") and e.name != WINDOW
                  and not DEVICE_PLANE.match(e.plane))
    idle: Dict[str, float] = {}
    i, open_spans = 0, []
    for s, t in gaps(((s, t) for s, t, _ in ops[0]), lo, hi):
        mid = (s + t) / 2
        while i < len(host) and host[i][0] <= mid:
            open_spans.append(host[i])
            i += 1
        open_spans = [h for h in open_spans if h[1] > mid]
        # the innermost benchmark span open on the host at the gap
        name = (max(open_spans)[2] if open_spans else "bench.other")
        idle[name] = idle.get(name, 0.0) + (t - s) * 1e-9
    return Summary((hi - lo) * 1e-9, busy, op_s, idle)


_HLO = re.compile(r"^%?(\S+) = (.*?) ([\w-]+)\(")


def _short(name: str) -> str:
    """``fusion.2 fusion f32[1024,27,27]`` for a TPU op event named by its
    whole HLO instruction (layouts dropped); other names as they are."""
    m = _HLO.match(name)
    if not m:
        return name
    return f"{m.group(1)} {m.group(3)} {re.sub(r'{[^}]*}', '', m.group(2))}"
