"""Tiered-cache zipf sweep: hit-rate and modeled serving time vs cache size.

Sweeps the repro/cache/ slot-pool cache over cache-size ratio
{0.5%, 1%, 5%, 20%} x zipf a {1.05, 1.2} (clipped-zipf traffic from
data/jagged.random_jagged_batch — real CTR skew).  Per configuration:

  * MEASURED — drive the real CachedEmbeddingBag through warmup batches
    (LFU counters converge), reset stats, then measure a steady-state
    window: hits/misses/evictions/hit-rate/bytes moved, with the first
    measured batch cross-checked bitwise against the uncached oracle.
  * ANALYTIC — core/perf_model.zipf_hit_rate for the same (a, ratio),
    the closed-form steady-state the measured rate should approach.
  * MODELED — hit-rate-parameterized phase times
    (core/perf_model.cached_phase_times) on both calibrated platforms,
    and the Fig. 9-style projection: one cached device vs distributing
    the paper-scale table over N = ceil(bytes/HBM) devices.

The hot path's single-launch guarantee is asserted structurally (jaxpr
pallas_call count of the device lookup == 1), so the sweep can measure
hit rates in cheap reference mode without losing the kernel story.

CSV: sweep,ratio,zipf_a,policy,cache_rows,hit_rate,analytic_hit_rate,
     hits,misses,evictions,mb_h2d,platform,cached_us,dist_us,speedup
"""
from __future__ import annotations

import argparse
import io

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import CacheConfig
from repro.core.embedding_bag import (
    EmbeddingBagConfig,
    pooled_lookup_local,
)
from repro.core.jagged import random_jagged_batch
from repro.core.perf_model import (
    H100_DGX,
    TPU_V5E,
    EmbeddingWorkload,
    cache_speedup_vs_distributed,
    cached_embedding_bag_time,
    devices_for_table,
    embedding_bag_time,
    zipf_hit_rate,
)
from repro.obs import SweepReport

RATIOS = (0.005, 0.01, 0.05, 0.20)
ZIPF_AS = (1.05, 1.2)

# Host-tractable sweep shapes: R large enough that a 1% pool beats 90%
# under zipf 1.2 (the hot mass grows with R — see perf_model.zipf_hit_rate)
FULL = dict(rows=1 << 20, tables=2, dim=8, batch=256, pooling=16,
            warmup=150, measure=50, ratios=RATIOS)
# smoke: the pool must still hold one batch's working set (<= batch*pooling
# uniques), so the tiny table uses larger ratios — it proves the driver and
# exactness, not the hit-rate bar
SMOKE = dict(rows=4096, tables=2, dim=16, batch=16, pooling=4,
             warmup=4, measure=2, ratios=(0.02, 0.05))
# modeled serving-time rows use the paper's workload scale
PAPER = dict(num_tables=26, batch_per_device=1024, pooling=32, dim=128)
PAPER_TABLE_BYTES = 10e12 / 26     # Fig. 9's 10 TB model, per table


def count_cached_launches(shape: dict) -> int:
    """Structural single-launch proof for the cached hot path — the
    device-lookup contract audited over the sweep's own shapes."""
    from repro.analysis import audit
    from repro.cache import CachedEmbeddingBag, cached_bag

    cfg = EmbeddingBagConfig(
        num_tables=shape["tables"], rows_per_table=shape["rows"],
        dim=shape["dim"], kernel_mode="interpret",
        cache=CacheConfig(rows=64))
    host = np.zeros((shape["tables"], 64, shape["dim"]), np.float32)
    bag = CachedEmbeddingBag(host, cfg)
    pool = jax.ShapeDtypeStruct(bag.pool.shape, bag.pool.dtype)
    idx = jax.ShapeDtypeStruct(
        (shape["tables"], shape["batch"], shape["pooling"]), jnp.int32)
    w = jax.ShapeDtypeStruct(idx.shape, jnp.float32)
    report = audit(lambda p, i, ww: bag.device_lookup(p, i, None, ww),
                   (pool, idx, w),
                   cached_bag.KERNEL_CONTRACTS["device_lookup"])
    report.raise_if_failed()
    return report.summary.pallas_calls


def run_config(ratio: float, a: float, policy: str, shape: dict,
               *, check_exact: bool, kernel_mode: str):
    from repro.cache import CachedEmbeddingBag

    R, T, D = shape["rows"], shape["tables"], shape["dim"]
    cache_rows = max(1, int(R * ratio))
    cfg = EmbeddingBagConfig(
        num_tables=T, rows_per_table=R, dim=D, kernel_mode=kernel_mode,
        cache=CacheConfig(rows=cache_rows, policy=policy))
    rng = np.random.default_rng(int(1000 * ratio) + int(100 * a))
    host = rng.standard_normal((T, R, D), dtype=np.float32)
    bag = CachedEmbeddingBag(host, cfg)

    def batches(n):
        for _ in range(n):
            yield random_jagged_batch(
                rng, T, shape["batch"], shape["pooling"], R, zipf_a=a)

    for b in batches(shape["warmup"]):
        bag.prefetch(b)
    bag.stats.reset()
    for i, b in enumerate(batches(shape["measure"])):
        if check_exact and i == 0:
            got = bag.lookup(b)
            want = pooled_lookup_local(jnp.asarray(host), b, cfg)
            if not bool((np.asarray(got) == np.asarray(want)).all()):
                raise AssertionError(
                    f"cached lookup diverged from oracle at ratio={ratio}")
        else:
            bag.prefetch(b)
    return bag.stats


def run(smoke: bool) -> str:
    shape = SMOKE if smoke else FULL
    kernel_mode = "interpret" if smoke else "reference"
    rep = SweepReport(
        "sweep", "ratio", "zipf_a", "policy", "cache_rows", "hit_rate",
        "analytic_hit_rate", "hits", "misses", "evictions", "mb_h2d",
        "platform", "cached_us", "dist_us", "speedup")
    w = EmbeddingWorkload(**PAPER)
    n_dist = devices_for_table(PAPER_TABLE_BYTES * 26, H100_DGX)
    for a in ZIPF_AS:
        for ratio in shape["ratios"]:
            stats = run_config(ratio, a, "lfu", shape,
                               check_exact=True, kernel_mode=kernel_mode)
            analytic = zipf_hit_rate(a, shape["rows"],
                                     int(shape["rows"] * ratio))
            for hw in (H100_DGX, TPU_V5E):
                cached = cached_embedding_bag_time(
                    w, hw, hit_rate=stats.hit_rate)
                dist = embedding_bag_time(w, n_dist, hw)
                speed = cache_speedup_vs_distributed(
                    PAPER_TABLE_BYTES * 26, w, hw, hit_rate=stats.hit_rate)
                rep.add(sweep="cache", ratio=ratio, zipf_a=a, policy="lfu",
                        cache_rows=int(shape["rows"] * ratio),
                        hit_rate=f"{stats.hit_rate:.4f}",
                        analytic_hit_rate=f"{analytic:.4f}",
                        hits=stats.hits, misses=stats.misses,
                        evictions=stats.evictions,
                        mb_h2d=f"{stats.bytes_h2d/2**20:.3f}",
                        platform=hw.name, cached_us=f"{cached*1e6:.2f}",
                        dist_us=f"{dist*1e6:.2f}", speedup=f"{speed:.2f}")
    return rep.csv()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + interpret-mode exactness (CI)")
    args = ap.parse_args()

    launches = count_cached_launches(SMOKE)
    csv = run(args.smoke)
    print(csv)
    print(f"# cached hot-path pallas_call launches: {launches} "
          f"(single fused TBE: {launches == 1})")
    assert launches == 1, "cached hot path must stay ONE fused pallas_call"

    import csv as _csv

    rows = list(_csv.DictReader(io.StringIO(csv)))
    by = {(float(r["ratio"]), float(r["zipf_a"])): float(r["hit_rate"])
          for r in rows}
    if not args.smoke:
        target = by[(0.01, 1.2)]
        print(f"# hit-rate @ 1% cache, zipf a=1.2: {target:.4f} "
              f"(target >= 0.90: {target >= 0.90})")
        assert target >= 0.90, (
            f"steady-state hit-rate {target:.4f} below the 90% bar")
    ratios = SMOKE["ratios"] if args.smoke else RATIOS
    for a in ZIPF_AS:
        curve = ", ".join(f"{r*100:g}%={by[(r, a)]:.3f}" for r in ratios)
        print(f"# zipf a={a} hit-rate vs cache ratio: {curve}")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
