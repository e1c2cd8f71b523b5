"""Run one benchmark cell once, on the chips of this machine.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` at the checkout's root.  The
run draws weights and traffic from ``--seed``, warms up every shape the
cell uses (set-up, reported as ``setup_s``), measures for ``--seconds``,
then checks a sample of the answers against the plain reference.  With
``--trace 0`` it reports the cell's end-to-end metrics; with ``--trace 1``
it profiles the window and reports the per-layer metrics.  The last lines
of standard error give each number compared beside its limit; the last
line of standard output is the result as one JSON object.

It refuses to run, printing no result, without a TPU or with fewer chips
than the cell asks for.  The compile cache lives in ``<checkout>/.jax_cache``
unless ``JAX_COMPILATION_CACHE_DIR`` names another directory.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()   # set-up starts with the process

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {args.workload} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 2
    from bench.peaks import peaks
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    trace_dir = (ROOT / ".bench_trace" / args.workload) if args.trace else None
    result, compared, info = harness.execute(
        cell, args.seed, args.seconds, trace_dir, T0, devices[:cell.chips],
        peaks(devices[0].device_kind))
    print(json.dumps(info), file=sys.stderr)
    for name, c in compared.items():
        print(f"compared {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
