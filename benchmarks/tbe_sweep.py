"""Fused table-batched (TBE) vs per-table embedding-bag launches across the
paper's #tables axis (§5): T in {1, 4, 16, 64}.

Three views per (T, path):

  * ``launches`` — pallas_call count in the traced program (structural
    proof: fused == 1 regardless of T, per_table == T).
  * modeled per-phase times (core/perf_model.tbe_gather_phases): ``launch``
    (per-kernel setup floor, the term TBE amortizes) and ``stream`` (HBM
    row traffic, identical in both layouts) on both calibrated platforms.
  * ``measured`` — wall-clock of the real op in the active kernel mode.
    On TPU this is the hardware number; on CPU the kernels run under the
    Pallas INTERPRETER, whose cost scales with grid steps, so measured
    CPU times characterize the emulator, not the hardware — the modeled
    rows carry the hardware story there (flagged in the mode column).

CSV: sweep,value,path,phase,platform,us,launches,mode
"""
from __future__ import annotations

import argparse
import io
import time

import jax
import numpy as np

from repro.core.perf_model import (
    H100_DGX,
    TPU_V5E,
    EmbeddingWorkload,
    tbe_gather_phases,
)

TABLE_COUNTS = [1, 4, 16, 64]
# CPU-tractable interpret shapes; the modeled rows use the paper's scale.
R, D, B, L = 256, 64, 8, 4
PAPER = dict(batch_per_device=1024, pooling=8, dim=128)


def count_launches(T: int, fused: bool) -> int:
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    from repro.analysis import audit, count_pallas_calls

    tables = jax.ShapeDtypeStruct((T, R, D), jnp.float32)
    idx = jax.ShapeDtypeStruct((T, B, L), jnp.int32)
    w = jax.ShapeDtypeStruct((T, B, L), jnp.float32)

    def fn(t, i, ww):
        return kops.embedding_bag_batched(t, i, None, ww,
                                          mode="interpret", fused=fused)

    if fused:
        # the sweep's structural claim: audit the attached contract
        report = audit(fn, (tables, idx, w),
                       kops.KERNEL_CONTRACTS["tbe_fused"])
        report.raise_if_failed()
        return report.summary.pallas_calls
    return count_pallas_calls(fn, tables, idx, w)


def measure(T: int, fused: bool, mode: str, reps: int) -> float:
    import jax.numpy as jnp
    from repro.kernels import ops as kops

    rng = np.random.default_rng(T)
    tables = jnp.asarray(rng.standard_normal((T, R, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, R, (T, B, L)), jnp.int32)

    def run():
        return kops.embedding_bag_batched(
            tables, idx, mode=mode, fused=fused).block_until_ready()

    run()                                   # compile + warm
    t0 = time.perf_counter()
    for _ in range(reps):
        run()
    return (time.perf_counter() - t0) / reps


def run(table_counts=None, max_reps: int = 3) -> str:
    out = io.StringIO()
    print("sweep,value,path,phase,platform,us,launches,mode", file=out)
    on_tpu = jax.default_backend() == "tpu"
    kernel_mode = "pallas" if on_tpu else "interpret"
    measured_tag = kernel_mode if on_tpu else "interpret-emulation"

    for T in (table_counts or TABLE_COUNTS):
        w = EmbeddingWorkload(num_tables=T, **PAPER)
        for fused in (True, False):
            path = "fused" if fused else "per_table"
            launches = count_launches(T, fused)
            for hw in (H100_DGX, TPU_V5E):
                phases = tbe_gather_phases(w, hw, fused=fused)
                for phase, t in phases.items():
                    print(f"tables,{T},{path},{phase},{hw.name},"
                          f"{t*1e6:.3f},{launches},modeled", file=out)
                print(f"tables,{T},{path},total,{hw.name},"
                      f"{sum(phases.values())*1e6:.3f},{launches},modeled",
                      file=out)
            reps = 1 if (not on_tpu and fused and T >= 16) else max_reps
            t = measure(T, fused, kernel_mode, reps)
            print(f"tables,{T},{path},total,{jax.default_backend()},"
                  f"{t*1e6:.1f},{launches},{measured_tag}", file=out)
    return out.getvalue()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="T in {1, 4}, single rep — the CI rot check")
    args = ap.parse_args()
    counts = [1, 4] if args.smoke else TABLE_COUNTS

    csv = run(counts, max_reps=1 if args.smoke else 3)
    print(csv)
    import csv as _csv

    rows = list(_csv.DictReader(io.StringIO(csv)))
    launches = {(int(r["value"]), r["path"]): int(r["launches"])
                for r in rows}
    # structural win: fused is ONE launch at every T; per-table pays T
    flat = all(launches[(T, "fused")] == 1 for T in counts)
    linear = all(launches[(T, "per_table")] == T for T in counts)
    print(f"# fused launches == 1 for all T: {flat}; "
          f"per-table launches == T: {linear}")
    assert flat and linear, "TBE launch-count invariant broken"
    modeled = {(int(r["value"]), r["path"]): float(r["us"]) for r in rows
               if r["mode"] == "modeled" and r["phase"] == "total"
               and r["platform"] == "h100-dgx-nvlink"}
    for T in counts:
        s = modeled[(T, "per_table")] / modeled[(T, "fused")]
        print(f"# modeled H100 gather-phase speedup @T={T}: {s:.2f}x")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
