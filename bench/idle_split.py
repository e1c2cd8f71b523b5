"""Run one cell as ``bench/run.py --trace 1`` does, and split chip 0's idle
time by the program's own stage spans besides.

    python3 bench/idle_split.py --workload <name> --seed <n> --seconds <s>

Standard output and standard error are ``bench/run.py``'s; one more line
on standard error, ``IDLE_SPLIT {...}``, holds :func:`split` of the
traced window.  The breakdown a traced run prints names each idle gap by
the benchmark's own span alone; this names it by the program's stage
too, so that ``bench.drain`` falls apart into pad, admit, fetch, scatter
and the rest.
"""
from __future__ import annotations

import bisect
import json
import sys
from pathlib import Path
from typing import List

ROOT = Path(__file__).resolve().parents[1]
PROGRAM = "repro."


def split(events: List) -> dict:
    """From the events of one traced window (``bench.trace.Event``s):

    * ``span_s`` / ``span_n``: seconds and count per ``repro.*`` host
      span, clipped to ``bench.window`` and summed over threads;
    * ``idle``: chip 0's idle seconds by name.  A gap is named
      ``<bench span>/<repro span>`` after the latest-started ``repro.*``
      span open on any host thread at its midpoint, else by the
      benchmark span alone, as ``bench.trace.summarize`` names it;
    * ``idle_in_program_pct``: chip 0's idle under a ``repro.*`` span,
      as a share of the window.
    """
    from bench import trace

    def on_host(e):
        return not trace.DEVICE_PLANE.match(e.plane)

    def on_chip0(e):
        m = trace.DEVICE_PLANE.match(e.plane)
        return (m is not None and m.group(1) == "0"
                and e.line == trace.OPS_LINE)

    (win,) = [e for e in events if e.name == trace.WINDOW and on_host(e)]
    lo, hi = win.start_ns, win.start_ns + win.dur_ns
    span_s, span_n = {}, {}
    program, bench = [], []
    for e in events:
        if not on_host(e):
            continue
        s, t = e.start_ns, e.start_ns + e.dur_ns
        if e.name.startswith(PROGRAM):
            program.append((s, t, e.name))
            s, t = max(s, lo), min(t, hi)
            if t > s:
                span_s[e.name] = span_s.get(e.name, 0.0) + (t - s) * 1e-9
                span_n[e.name] = span_n.get(e.name, 0) + 1
        elif e.name.startswith("bench.") and e.name != trace.WINDOW:
            bench.append((s, t, e.name))
    program.sort()
    bench.sort()
    starts = [s for s, _, _ in program]
    longest = max((t - s for s, t, _ in program), default=0.0)
    ops = [(max(e.start_ns, lo), min(e.start_ns + e.dur_ns, hi))
           for e in events if on_chip0(e)]
    idle, in_program = {}, 0.0
    i, open_bench = 0, []
    for s, t in trace.gaps([(a, b) for a, b in ops if b > a], lo, hi):
        mid = (s + t) / 2
        while i < len(bench) and bench[i][0] <= mid:
            open_bench.append(bench[i])
            i += 1
        open_bench = [b for b in open_bench if b[1] > mid]
        name = max(open_bench)[2] if open_bench else "bench.other"
        # the program's spans that started no longer ago than the
        # longest one lasted, and are still open at the midpoint
        first = bisect.bisect_left(starts, mid - longest)
        last = bisect.bisect_right(starts, mid)
        inner = [p for p in program[first:last] if p[1] > mid]
        if inner:
            name = f"{name}/{max(inner)[2]}"
            in_program += t - s
        idle[name] = idle.get(name, 0.0) + (t - s) * 1e-9
    return {"window_s": (hi - lo) * 1e-9, "span_s": span_s,
            "span_n": span_n,
            "idle": dict(sorted(idle.items(), key=lambda kv: -kv[1])),
            "idle_in_program_pct": 100.0 * in_program / (hi - lo)}


def main(argv=None) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import run, trace

    load, out = trace.load, {}

    def load_and_split(log_dir):
        events = load(log_dir)
        out.update(split(events))
        return events

    trace.load = load_and_split
    rc = run.main(argv + ["--trace", "1"])
    if out:
        print("IDLE_SPLIT " + json.dumps(out), file=sys.stderr)
    return rc


if __name__ == "__main__":
    sys.exit(main())
