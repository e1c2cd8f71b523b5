"""Cluster-wide tiered-store sweep: hit-rate x hosts x transport.

The paper's Fig. 9 projects a 22.8x-108.2x slowdown when one embedding
table spans N = ceil(bytes / HBM) devices.  The tiered store's answer is
a slot-pool cache over a CLUSTER-WIDE cold tier (repro/cache/tiers.py):
only the miss traffic pays the network, in one batched
``comm.fetch_rows`` per prefetch.  This driver quantifies how much of
the Fig. 9 slowdown that recovers, per transport:

  * MEASURED — this process forces a 4-device CPU backend and drives the
    real ``CachedEmbeddingBag`` over a ``RemoteStore`` (rows split over
    4 simulated hosts): steady-state hit rate, per-tier miss/byte split
    (host vs remote), transport equivalence (bulk vs one-sided RDMA in
    interpret mode), first-batch bitwise cross-check vs the uncached
    oracle, and the fused single-launch jaxpr assert.  The ``fetch_rows``
    CollectiveEvent is traced (comm.instrument) so the reported network
    bytes come from instrumentation, not HLO parsing.
  * MODELED — ``perf_model.tiered_phase_times`` on both calibrated
    platforms: serving time vs (cache ratio via ``zipf_hit_rate``, hosts,
    transport), and the Fig. 9-style recovery ratio
    ``tiered_speedup_vs_distributed`` (one cached serving device + remote
    cold tier vs the N-device RW pipeline).
  * PLANNED — ``sharding_plan.plan`` with the fourth "cached" strategy on
    a paper-scale table set: which tables the planner caches, the pool
    rows it buys with the leftover HBM budget, and the priced hit rate.

CSV: sweep,hosts,transport,ratio,zipf_a,hit_rate,platform,tiered_us,
     dist_us,recovery
"""
from __future__ import annotations

import os
# MUST precede jax import: the measured section simulates a 4-host
# cluster with one CPU device per host (setdefault: callers may override)
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.cache import CacheConfig
from repro.core import comm
from repro.core.embedding_bag import (
    EmbeddingBagConfig,
    init_tables,
    make_cache,
    pooled_lookup_local,
)
from repro.core.jagged import random_jagged_batch
from repro.core.perf_model import (
    H100_DGX,
    TPU_V5E,
    EmbeddingWorkload,
    devices_for_table,
    embedding_bag_time,
    tiered_embedding_bag_time,
    zipf_hit_rate,
)
from repro.core.sharding_plan import TableSpec, plan
from repro.obs import SweepReport

HOSTS = (1, 2, 8, 32, 128)
RATIOS = (0.005, 0.01, 0.05, 0.20)
ZIPF_A = 1.2

# modeled serving-time rows use the paper's workload scale (Fig. 9: 10 TB)
PAPER = dict(num_tables=26, batch_per_device=1024, pooling=32, dim=128)
PAPER_TABLE_BYTES = 10e12

# measured section shapes (4 simulated hosts; R divides over 4)
FULL = dict(rows=1 << 16, tables=2, dim=16, batch=64, pooling=8,
            warmup=40, measure=10, ratio=0.01)
SMOKE = dict(rows=4096, tables=2, dim=16, batch=8, pooling=4,
             warmup=4, measure=2, ratio=0.05)


def measured(shape: dict) -> dict:
    """Drive the real remote-tier bag on the forced 4-device backend."""
    n_hosts = len(jax.devices())
    R, T, D = shape["rows"], shape["tables"], shape["dim"]
    cfg = EmbeddingBagConfig(
        num_tables=T, rows_per_table=R, dim=D, kernel_mode="interpret",
        cache=CacheConfig(
            rows=max(shape["batch"] * shape["pooling"],
                     int(R * shape["ratio"])),
            cold_tier="remote"))
    tables = init_tables(jax.random.key(0), cfg)
    bag = make_cache(tables, cfg)
    rng = np.random.default_rng(7)

    def batches(n):
        for _ in range(n):
            yield random_jagged_batch(rng, T, shape["batch"],
                                      shape["pooling"], R, zipf_a=ZIPF_A)

    first = True
    for b in batches(shape["warmup"]):
        if first:   # bitwise cross-check vs the uncached oracle
            got = bag.lookup(b)
            want = pooled_lookup_local(tables, b, cfg)
            assert bool((np.asarray(got) == np.asarray(want)).all()), \
                "remote-tier lookup diverged from the uncached oracle"
            first = False
        else:
            bag.prefetch(b)
    bag.stats.reset()
    for b in batches(shape["measure"]):
        bag.prefetch(b)
    s = bag.stats

    # the fused single-launch guarantee under the remote tier layout —
    # audited against the attached device-lookup contract
    from repro.analysis import audit
    from repro.cache import cached_bag
    pool = jax.ShapeDtypeStruct(bag.pool.shape, bag.pool.dtype)
    idx = jax.ShapeDtypeStruct((T, shape["batch"], shape["pooling"]),
                               jnp.int32)
    w = jax.ShapeDtypeStruct(idx.shape, jnp.float32)
    report = audit(lambda p, i, ww: bag.device_lookup(p, i, None, ww),
                   (pool, idx, w),
                   cached_bag.KERNEL_CONTRACTS["device_lookup"])
    report.raise_if_failed()
    launches = report.summary.pallas_calls

    # instrumented fetch traffic (no HLO parsing): trace one fetch program
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map
    M = 8
    mesh = Mesh(np.asarray(jax.devices()), ("hosts",))
    with comm.instrument() as events:
        jax.jit(shard_map(
            lambda sh, a, o: comm.fetch_rows(sh[0], a, o, "hosts"),
            mesh=mesh, in_specs=(P("hosts"), P(), P()), out_specs=P(),
            check_vma=False)).lower(
                np.zeros((n_hosts, 8, D), np.float32),
                np.zeros(M, np.int32), np.zeros(M, np.int32))
    fetch_events = [e for e in events if e.op == "fetch_rows"]

    return {"stats": s, "launches": launches, "hosts": n_hosts,
            "row_bytes": bag.row_bytes, "fetch_events": fetch_events}


def modeled_csv() -> str:
    rep = SweepReport("sweep", "hosts", "transport", "ratio", "zipf_a",
                      "hit_rate", "platform", "tiered_us", "dist_us",
                      "recovery")
    w = EmbeddingWorkload(**PAPER)
    rows_total = int(PAPER_TABLE_BYTES // (PAPER["dim"] * 4))
    for hosts in HOSTS:
        for onesided in (False, True):
            transport = "onesided" if onesided else "bulk"
            for ratio in RATIOS:
                hr = zipf_hit_rate(ZIPF_A, rows_total,
                                   int(rows_total * ratio))
                for hw in (H100_DGX, TPU_V5E):
                    tiered = tiered_embedding_bag_time(
                        w, hw, hit_rate=hr, hosts=hosts, onesided=onesided)
                    n = devices_for_table(PAPER_TABLE_BYTES, hw)
                    dist = embedding_bag_time(w, n, hw)
                    # == tiered_speedup_vs_distributed, from the same two
                    # numbers the row prints (consistent by construction)
                    rec = dist / tiered
                    rep.add(sweep="tiered", hosts=hosts,
                            transport=transport, ratio=ratio,
                            zipf_a=ZIPF_A, hit_rate=f"{hr:.4f}",
                            platform=hw.name,
                            tiered_us=f"{tiered*1e6:.2f}",
                            dist_us=f"{dist*1e6:.2f}",
                            recovery=f"{rec:.2f}")
    return rep.csv()


def planned(smoke: bool):
    """The planner's view: cached placements on a paper-scale table set."""
    n_tables = 4 if smoke else 26
    tables = [TableSpec(f"t{i}", rows=50_000_000, dim=128, pooling=32)
              for i in range(n_tables)]
    p = plan(tables, num_shards=8, batch_per_shard=1024,
             hbm_budget_bytes=8e9, hw=H100_DGX, zipf_a=ZIPF_A,
             cache_hosts=8, cache_backend="onesided")
    lines = []
    for pl in p.placements:
        extra = (f" cache_rows={pl.cache_rows} "
                 f"hit={pl.est_hit_rate:.3f}") if pl.strategy == "cached" \
            else ""
        lines.append(f"#   {pl.table.name}: {pl.strategy} "
                     f"(est {pl.est_time_s*1e6:.1f}us){extra}")
    n_cached = sum(pl.strategy == "cached" for pl in p.placements)
    return p, n_cached, lines


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny measured shapes (CI)")
    args = ap.parse_args()
    shape = SMOKE if args.smoke else FULL

    m = measured(shape)
    s = m["stats"]
    print(f"# measured ({m['hosts']} simulated hosts, zipf a={ZIPF_A}, "
          f"ratio={shape['ratio']}):")
    print(f"#   {s}")
    print(f"#   remote miss fraction: {s.remote_miss_fraction:.3f} "
          f"(cold rows split over {m['hosts']} hosts)")
    print(f"#   hot-path pallas_call launches: {m['launches']} "
          f"(single fused TBE: {m['launches'] == 1})")
    ev = m["fetch_events"]
    print(f"#   instrumented fetch_rows events: {len(ev)} "
          f"(payload {ev[0].bytes_in if ev else 0} B over "
          f"axis {ev[0].axis_size if ev else 0})")
    assert m["launches"] == 1, "hot path must stay ONE fused pallas_call"
    assert len(ev) == 1, "fetch_rows must be instrumented"
    assert s.misses_remote > 0 and s.bytes_remote > 0, \
        "a 4-host cold tier must see remote misses"
    assert s.misses_host + s.misses_remote == s.misses

    print(modeled_csv())

    p, n_cached, lines = planned(args.smoke)
    print(f"# planner (zipf a={ZIPF_A}, 8 shards x 8 GB leftover, "
          f"cold tier over 8 hosts, onesided fetch):")
    for ln in lines:
        print(ln)
    print(f"# cached placements: {n_cached}")
    assert n_cached >= 1, \
        "the planner must price at least one table as 'cached' here"


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
