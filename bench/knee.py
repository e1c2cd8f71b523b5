"""Find the highest query rate a server cell sustains: one set-up, then the
cell's open loop at each rate in turn.

    python3 bench/knee.py --workload <server cell> --seed 1 --seconds 10 \\
        --rates 20 40 60 80

For each rate it prints one JSON line: the query latency median, 99th
percentile and maximum, and how long after the window the last answer
came (a backlog that grows shows as a drain of seconds).  The cell's
``rate_qps`` is set once, from such a sweep, to about four fifths of the
knee; a run never searches for it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import importlib

    import jax

    from bench import harness, stats, traffic
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cell = harness.load_cell(ROOT, args.workload)
    cfg, mix = cell.config, cell.mix
    jax.config.update("jax_default_matmul_precision", cfg["matmul_precision"])
    model = importlib.import_module(f"bench.models.{cfg['model']}")
    system = model.System(args.seed, cfg, jax.devices()[:cell.chips])
    pool = traffic.samples(args.seed, cfg, mix, mix["distinct_samples"])
    B = cfg["batch_size"]
    harness.submit(system, pool, 0, mix["warmup_batches"] * B)
    system.drain()
    start = mix["warmup_batches"] * B
    for rate in args.rates:
        win = harness.server(system, pool, dict(mix, rate_qps=rate),
                             args.seconds, start,
                             harness.no_annotation)
        start += win.submitted
        ms = list(win.latencies_s * 1e3)
        print(json.dumps({
            "rate_qps": rate, "queries": win.attempted, "failed": win.failed,
            "query_p50_ms": stats.percentile(ms, 50),
            "query_p99_ms": stats.percentile(ms, 99),
            "query_max_ms": max(ms),
            "drain_s": win.seconds - args.seconds,
            "samples_per_s": win.scored / win.seconds,
            "generator_late_max_ms": win.late_s * 1e3,
            "at": time.strftime("%H:%M:%S")}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
