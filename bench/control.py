"""The control of a cell's check: the reference computed one precision
below the configuration's, put in the program's place.

    python3 bench/control.py --workload <name> --seeds 1 2 3

For each seed it draws the cell's traffic as a run does, takes
``harness.CHECK_SAMPLES`` of its samples, and prints as one JSON line the
widest pCTR gap between the reference at the configuration's precision
("highest") and the control (three bfloat16 passes, "high").  The check's
limit has to lie below what the control reads (see ``PERF.md``).  It runs
on the chip, at the cell's own widths; a benchmark run does not run it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def control_gap(cell, seed: int) -> float:
    import jax

    from bench import harness, traffic
    from bench.models import dlrm_reference as ref

    cfg = cell.config
    pool = traffic.samples(seed, cfg, cell.mix, harness.CHECK_SAMPLES)
    key = jax.random.fold_in(
        jax.random.wrap_key_data(jax.numpy.asarray(ref.seed_words(seed)),
                                 impl="threefry2x32"), 1)
    mlp = jax.tree.map(np.asarray, ref.mlp_from_seed(key, cfg))
    lengths = np.ascontiguousarray(
        np.broadcast_to(pool.lengths, (len(pool), pool.lengths.size)))
    got = {p: ref.reference_scores(seed, cfg, mlp, pool.dense, pool.ids,
                                   lengths, precision=p)
           for p in ("highest", "high")}
    return float(np.max(np.abs(got["high"].astype(np.float64)
                               - got["highest"])))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness

    cell = harness.load_cell(ROOT, args.workload)
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_max_abs_dpctr": control_gap(cell, seed)}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
