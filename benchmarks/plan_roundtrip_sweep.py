"""Planner -> engine round trip: serve a heterogeneous plan, check its prices.

The paper's sweeps (batch x tables x table sizes x pooling x dims, §5)
show embedding tables are wildly heterogeneous, and RecShard-style
planning (PAPERS.md) assigns each table its own statistical capacity.
``sharding_plan.plan`` prices a per-table ``cache_rows``/``est_hit_rate``
on every "cached" ``Placement``; this driver closes the loop by SERVING
the plan and checking the prices against measured ``CacheStats``:

  * PLAN     — the greedy planner on T same-spec tables under a tight
    HBM budget: as the budget drains, later tables get smaller pools, so
    one plan carries >= 2 DISTINCT per-table ``cache_rows`` (asserted).
  * MEASURED — ``make_dlrm_engine`` consumes the plan via
    ``DLRMConfig.sharding_plan`` (heterogeneous per-table slot pools in
    ONE FLAT ``(sum S_t, D)`` device pool — exactly
    ``slot_pool_bytes`` on device, strictly less than the padded
    ``T x max(S_t)`` rectangle; asserted), serves zipf traffic warmed
    from the same
    popularity statistics the planner assumed, and the per-table
    measured hit rate (``CacheStats.hit_rate_t``) must land within
    ``TOL_HIT`` of each placement's ``est_hit_rate`` (asserted).  Engine
    scores are cross-checked against the uncached direct forward.
  * PRICED   — the fetch-traffic side: measured unique fetched rows per
    batch vs ``perf_model.expected_unique_misses`` (what
    ``tiered_phase_times`` now charges when given the traffic model),
    within ``TOL_FETCH`` relative (asserted).

Both checks are ENABLED by the perf-model bugfixes: ``zipf_hit_rate``
prices ``0 < a <= 1`` by the truncated-zeta mass (it used to claim
uniform ``cache_rows / rows`` — the sweep runs at a = 0.9, where that
error is ~4x), and miss traffic is priced per unique missed ROW, not
per missed lookup.

CSV: sweep,table,strategy,cache_rows,est_hit_rate,measured_hit_rate,
     hit_err,model_fetch_rows_per_batch,measured_fetch_rows_per_batch
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import dlrm as dlrm_cfg
from repro.core.jagged import JaggedBatch, random_jagged_batch
from repro.core.perf_model import (
    H100_DGX,
    expected_unique_misses,
    padded_slot_pool_bytes,
    slot_pool_bytes,
    zipf_hit_rate,
)
from repro.core.sharding_plan import TableSpec, plan
from repro.models import dlrm as dlrm_mod
from repro.obs import SweepReport
from repro.serving.engine import CTRRequest, make_dlrm_engine

ZIPF_A = 0.9          # <= 1: exercises the truncated-zeta hit-rate fix
TOL_HIT = 0.06        # |measured - est_hit_rate| per table
TOL_FETCH = 0.15      # relative, unique fetched rows per batch

# budgets drain over 3 tables per shard: the greedy pass buys the 0.20,
# 0.10 and 0.05 CACHE_RATIOS rungs in turn -> 3 distinct pool sizes
FULL = dict(tables=6, rows=8192, dim=16, pooling=8, batch=32,
            warmup=6, measure=12, budget=190_000)
SMOKE = dict(tables=6, rows=2048, dim=16, pooling=8, batch=8,
             warmup=3, measure=6, budget=48_000)


def build_plan(shape):
    """Planner view: same-spec tables, tight budget -> distinct pools."""
    specs = [TableSpec(f"t{i}", rows=shape["rows"], dim=shape["dim"],
                       pooling=shape["pooling"])
             for i in range(shape["tables"])]
    # 2 model shards so RW pays collectives the slot pool avoids; the
    # budget drains as the greedy pass charges each pool, so identical
    # specs still land on different CACHE_RATIOS rungs
    p = plan(specs, num_shards=2, batch_per_shard=shape["batch"],
             hbm_budget_bytes=shape["budget"], hw=H100_DGX, zipf_a=ZIPF_A)
    cached = [pl for pl in p.placements if pl.strategy == "cached"]
    assert len(cached) == len(specs), \
        f"expected every table cached, got {[pl.strategy for pl in p.placements]}"
    distinct = {pl.cache_rows for pl in cached}
    assert len(distinct) >= 2, \
        f"plan is not heterogeneous: one pool size {distinct} — tune budget"
    return p


def roundtrip(shape, p):
    """Serve the plan through make_dlrm_engine; measure per-table stats."""
    T, R, L = shape["tables"], shape["rows"], shape["pooling"]
    base = dataclasses.replace(
        dlrm_cfg.smoke(), num_sparse_features=T, rows_per_table=R,
        embedding_dim=shape["dim"], pooling=L,
        bottom_mlp=(32, shape["dim"]), kernel_mode="reference")
    # warm from the SAME popularity statistics the planner priced with
    # (the offline ids_freq_mapping): residency starts at each table's
    # top-S_t, which is exactly the steady state est_hit_rate assumes
    freqs = (np.arange(1, R + 1, dtype=np.float64) ** -ZIPF_A) * 1e7
    cfg = dataclasses.replace(
        base, sharding_plan=p,
        cache=dataclasses.replace(base.cache, warmup_freqs=freqs))
    params = dlrm_mod.init_params(jax.random.key(0), base)
    eng = make_dlrm_engine(params, cfg, batch_size=shape["batch"])
    slots = eng.cache.mgr.slots_per_table
    # the flat pool's whole point: exactly sum(S_t) rows on device, no
    # padding to max(S_t) — measured bytes must equal the exact price
    # and undercut the padded rectangle whenever the plan is heterogeneous
    flat_b = slot_pool_bytes(slots, shape["dim"])
    padded_b = padded_slot_pool_bytes(slots, shape["dim"])
    assert eng.cache.pool.shape == (int(slots.sum()), shape["dim"])
    assert eng.cache.hot.live_nbytes == flat_b == eng.cache.hot.nbytes, \
        (eng.cache.hot.live_nbytes, flat_b, eng.cache.hot.nbytes)
    assert flat_b < padded_b, \
        f"flat pool {flat_b} B must shrink below padded {padded_b} B"
    print(f"# engine slot vector S_t = {slots.tolist()} "
          f"(flat pool {tuple(eng.cache.pool.shape)}: {flat_b} B vs "
          f"{padded_b} B padded to max S_t — saves "
          f"{1 - flat_b / padded_b:.1%})")

    rng = np.random.default_rng(7)
    rid = 0

    def flush_once(check_scores):
        nonlocal rid
        b = random_jagged_batch(rng, T, shape["batch"], L, R, zipf_a=ZIPF_A)
        idx = np.asarray(b.indices)
        reqs = []
        for i in range(shape["batch"]):
            reqs.append(CTRRequest(
                rid=rid, dense=rng.standard_normal(
                    base.num_dense_features).astype(np.float32),
                indices=idx[:, i, :].astype(np.int32),
                lengths=np.full(T, L, np.int32)))
            rid += 1
            eng.submit(reqs[-1])
        out = eng.run_to_completion()
        if check_scores:   # engine over the plan == uncached direct forward
            for r in reqs:
                jb = JaggedBatch(jnp.asarray(r.indices[:, None, :]),
                                 jnp.asarray(r.lengths[:, None]))
                want = float(jax.nn.sigmoid(dlrm_mod.forward(
                    params, jnp.asarray(r.dense[None]), jb, base))[0])
                assert abs(out[r.rid] - want) < 1e-6, \
                    (r.rid, out[r.rid], want)

    flush_once(check_scores=True)
    for _ in range(shape["warmup"] - 1):
        flush_once(check_scores=False)
    eng.cache_stats().reset()
    for _ in range(shape["measure"]):
        flush_once(check_scores=False)
    return eng.cache_stats()


def report(shape, p, stats) -> str:
    rep = SweepReport(
        "sweep", "table", "strategy", "cache_rows", "est_hit_rate",
        "measured_hit_rate", "hit_err", "model_fetch_rows_per_batch",
        "measured_fetch_rows_per_batch")
    M = shape["measure"]
    hr_t = stats.hit_rate_t
    lookups_per_table = shape["batch"] * shape["pooling"]
    worst_hit = 0.0
    model_fetch_total = 0.0
    for i in range(shape["tables"]):
        pl = p.placement_at(i)
        measured = float(hr_t[i])
        err = abs(measured - pl.est_hit_rate)
        worst_hit = max(worst_hit, err)
        model_fetch = expected_unique_misses(
            ZIPF_A, pl.table.rows, pl.cache_rows, lookups_per_table)
        model_fetch_total += model_fetch
        # fetched rows are split per TIER (not per table), so the
        # per-table column reports the model and the totals line below
        # compares against the measured sum
        rep.add(sweep="roundtrip", table=i, strategy=pl.strategy,
                cache_rows=pl.cache_rows,
                est_hit_rate=f"{pl.est_hit_rate:.4f}",
                measured_hit_rate=f"{measured:.4f}",
                hit_err=f"{err:.4f}",
                model_fetch_rows_per_batch=f"{model_fetch:.1f}",
                measured_fetch_rows_per_batch="")
    measured_fetch = stats.fetch_host + stats.fetch_remote
    meas_per_batch = measured_fetch / M
    rel = abs(meas_per_batch - model_fetch_total) / max(meas_per_batch, 1e-9)
    rep.comment(f"totals: measured fetch rows/batch = {meas_per_batch:.1f}, "
                f"modeled (unique-miss pricing) = {model_fetch_total:.1f} "
                f"(rel err {rel:.3f}); worst per-table |hit err| = "
                f"{worst_hit:.4f}")
    # the old per-lookup charge for contrast (what the model used to bill)
    old_total = sum(
        (1.0 - p.placement_at(i).est_hit_rate) * lookups_per_table
        for i in range(shape["tables"]))
    rep.comment(f"old per-lookup pricing would bill {old_total:.1f} "
                f"rows/batch")
    assert worst_hit <= TOL_HIT, \
        f"measured per-table hit rate {worst_hit:.4f} off the plan's price" \
        f" by more than {TOL_HIT} — the round trip does not close"
    assert rel <= TOL_FETCH, \
        f"measured fetch traffic off the unique-miss model by {rel:.3f}" \
        f" (> {TOL_FETCH})"
    return rep.csv()


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="tiny measured shapes (CI)")
    args = ap.parse_args()
    shape = SMOKE if args.smoke else FULL

    p = build_plan(shape)
    print(f"# plan (zipf a={ZIPF_A}, {shape['tables']} tables x "
          f"{shape['rows']} rows, budget {shape['budget']} B over 2 shards):")
    for pl in sorted(p.placements, key=lambda x: x.index):
        print(f"#   t{pl.index}: {pl.strategy} cache_rows={pl.cache_rows} "
              f"est_hit={pl.est_hit_rate:.4f} "
              f"(est {pl.est_time_s * 1e6:.1f}us)")
    old_est = [zipf_hit_rate(0.0, shape["rows"], pl.cache_rows)
               for pl in p.placements]
    print(f"# (the pre-fix a<=1 model would have priced hit rates "
          f"{[round(h, 3) for h in old_est]})")

    stats = roundtrip(shape, p)
    print(f"# measured: {stats}")
    print(report(shape, p, stats))
    print("# OK: plan prices check out against measured serving stats")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
