"""SLO / drift sweep: flash-crowd rotation must trip the drift detector.

The ROADMAP's drift-adaptive serving loop needs a trustworthy trigger:
the :class:`repro.obs.slo.DriftDetector` comparing live windowed
per-table hit rates against the sharding plan's priced
``Placement.est_hit_rate``.  This driver proves the trigger both ways:

  * CONTROL — stationary Zipf traffic (``dlrm_drift_batches`` with
    ``rotate_every=0``), served by a plan-driven engine warmed from the
    SAME popularity statistics the planner priced.  The detector must
    stay silent and the SLO monitor must record ZERO breaches: live
    traffic matching the plan is the null hypothesis.
  * DRIFT   — the identical stream until batch ``rotate_at``, then the
    whole popularity ranking relocates (the flash crowd).  The detector
    must fire within ``detect_bound`` batches of the rotation — and
    never before it — and the windowed hit rate must breach the
    policy's floor (the SLO monitor sees the same regression the
    detector attributes).

Overhead is bounded the same way obs_sweep bounds tracing: per-op costs
of the windowed instruments (observe / inc / rotate / EWMA element
update) are microbenchmarked and multiplied by the registry's actual
lifetime op counts; the projection must stay under 2% of serving
wall-clock.

Artifacts: ``--bench`` writes the canonical BenchRecord
(``BENCH_slo.json``) for the CI bench-gate; ``--csv`` the per-batch
window trace.
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np
import jax

from repro.configs import dlrm as dlrm_cfg
from repro.core.perf_model import H100_DGX
from repro.core.sharding_plan import TableSpec, plan
from repro.data.synthetic import dlrm_drift_batches
from repro.models import dlrm as dlrm_mod
from repro.obs import (DriftDetector, SLOMonitor, SLOPolicy, SweepReport,
                       Telemetry, expected_hit_rates)
from repro.obs.bench import make_bench_record, make_metric, write_bench
from repro.obs.timeseries import (EwmaSeries, RollingCounter,
                                  WindowedHistogram)
from repro.serving.engine import CTRRequest, make_dlrm_engine

ZIPF_A = 0.9           # <= 1: the truncated-zeta planner regime
DRIFT_THRESHOLD = 0.15  # |ewma hit_rate_t - est_hit_rate| that flags
MIN_UPDATES = 3         # EWMA evidence floor before a table can flag

# tight budget -> heterogeneous per-table pools (>= 2 distinct rungs),
# same recipe as plan_roundtrip_sweep; rotate_at is in BATCHES
FULL = dict(tables=6, rows=8192, dim=16, pooling=8, batch=32,
            budget=190_000, window=8, batches=40, rotate_at=20,
            detect_bound=8)
SMOKE = dict(tables=6, rows=2048, dim=16, pooling=8, batch=8,
             budget=48_000, window=4, batches=24, rotate_at=12,
             detect_bound=8)


def build_plan(shape):
    specs = [TableSpec(f"t{i}", rows=shape["rows"], dim=shape["dim"],
                       pooling=shape["pooling"])
             for i in range(shape["tables"])]
    p = plan(specs, num_shards=2, batch_per_shard=shape["batch"],
             hbm_budget_bytes=shape["budget"], hw=H100_DGX, zipf_a=ZIPF_A)
    cached = [pl for pl in p.placements if pl.strategy == "cached"]
    assert len(cached) == len(specs), \
        f"expected every table cached, got " \
        f"{[pl.strategy for pl in p.placements]}"
    return p


def make_engine(shape, p, telemetry):
    T, R, L = shape["tables"], shape["rows"], shape["pooling"]
    base = dataclasses.replace(
        dlrm_cfg.smoke(), num_sparse_features=T, rows_per_table=R,
        embedding_dim=shape["dim"], pooling=L,
        bottom_mlp=(32, shape["dim"]), kernel_mode="reference")
    # warm from the planner's assumed popularity: residency starts at
    # each table's top-S_t of PHASE 0 — the state the rotation breaks
    freqs = (np.arange(1, R + 1, dtype=np.float64) ** -ZIPF_A) * 1e7
    cfg = dataclasses.replace(
        base, sharding_plan=p,
        cache=dataclasses.replace(base.cache, warmup_freqs=freqs))
    params = dlrm_mod.init_params(jax.random.key(0), base)
    eng = make_dlrm_engine(params, cfg, batch_size=shape["batch"],
                           telemetry=telemetry)
    return eng, cfg


def serve(shape, rotate_every: int, policy_floor: float,
          expected: np.ndarray):
    """One serving run; returns (engine, monitor, detector, wall_s,
    per-batch windowed hit-rate trace)."""
    tel = Telemetry(window=shape["window"])
    p = build_plan(shape)
    eng, cfg = make_engine(shape, p, tel)
    policy = SLOPolicy(name="serving", hit_rate_floor=policy_floor,
                       min_window_lookups=1)
    monitor = SLOMonitor(tel, policy, engine=eng.obs_name)
    detector = DriftDetector(tel, expected, engine=eng.obs_name,
                             threshold=DRIFT_THRESHOLD,
                             min_updates=MIN_UPDATES)
    # per-batch trace of the windowed aggregate hit rate (CSV artifact)
    trace = []

    def _snap(engine, tick):
        m = tel.metrics
        hits = m.rolling_counter(f"{engine}.window.hits",
                                 window=tel.window).total
        lookups = m.rolling_counter(f"{engine}.window.lookups",
                                    window=tel.window).total
        trace.append((tick, hits / lookups if lookups else 0.0))

    tel.add_tick_listener(_snap)

    gen = dlrm_drift_batches(cfg, shape["batch"], seed=3, zipf_a=ZIPF_A,
                             rotate_every=rotate_every)
    rid = 0
    B, T = shape["batch"], shape["tables"]
    wall = 0.0
    for _ in range(shape["batches"]):
        d = next(gen)
        idx = np.asarray(d["batch"].indices)
        lens = np.asarray(d["batch"].lengths)
        t0 = time.perf_counter()
        for i in range(B):
            eng.submit(CTRRequest(
                rid=rid, dense=d["dense"][i],
                indices=idx[:, i, :].astype(np.int32),
                lengths=lens[:, i].astype(np.int32)))
            rid += 1
        eng.run_to_completion()
        wall += time.perf_counter() - t0
    return eng, monitor, detector, wall, trace


def _per_op_cost(fn, n: int = 20_000) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def windowed_overhead(metrics, wall: float):
    """Projected windowed-instrument cost: microbenchmarked per-op costs
    x the registry's actual lifetime op counts (a wall-clock A/B on a
    noisy CI host would drown a sub-2% signal)."""
    wh = WindowedHistogram("bench", window=8)
    rc = RollingCounter("bench", window=8)
    ew = EwmaSeries("bench")
    T = 8
    sample = np.full(T, 0.5)
    costs = {
        "observe": _per_op_cost(lambda: wh.observe(1e-3)),
        "inc": _per_op_cost(lambda: rc.inc(3)),
        # rotate cost measured with a freshly-fed tick each time —
        # the realistic (non-empty eviction) path
        "rotate": _per_op_cost(
            lambda: (wh.observe(1e-3), wh.rotate(), rc.rotate())),
        "ewma": _per_op_cost(lambda: ew.update(sample)) / T,
    }
    counts = metrics.windowed_op_counts()
    overhead = sum(costs[k] * counts[k] for k in costs)
    return overhead, overhead / wall, costs, counts


def run(shape, bench_path, csv_path):
    p = build_plan(shape)
    expected = expected_hit_rates(p, shape["tables"])
    # breach floor: comfortably below the stationary aggregate windowed
    # hit rate, comfortably above the post-rotation crater
    floor = max(0.05, float(expected.mean()) - 0.15)
    print(f"# plan est_hit_rate = {[round(float(e), 3) for e in expected]}, "
          f"SLO hit-rate floor = {floor:.3f}, drift threshold = "
          f"{DRIFT_THRESHOLD}")

    # -- CONTROL: stationary traffic, everything must stay quiet ------------
    eng_c, mon_c, det_c, wall_c, trace_c = serve(shape, 0, floor, expected)
    stats_c = eng_c.cache_stats()
    print(f"# CONTROL: {shape['batches']} batches, hit_rate="
          f"{stats_c.hit_rate:.4f}, monitor={mon_c.summary()}, "
          f"drift={det_c.summary()}")
    assert det_c.summary()["events"] == 0, \
        f"stationary control raised drift events: {det_c.summary()}"
    assert mon_c.breaches == 0, \
        f"stationary control breached the SLO: {mon_c.summary()}"
    assert mon_c.windows_evaluated == shape["batches"]

    # -- DRIFT: identical stream until rotate_at, then the flash crowd ------
    eng_d, mon_d, det_d, wall_d, trace_d = serve(
        shape, shape["rotate_at"], floor, expected)
    stats_d = eng_d.cache_stats()
    first = det_d.first_detection_tick
    print(f"# DRIFT: rotation at batch {shape['rotate_at']}, hit_rate="
          f"{stats_d.hit_rate:.4f}, monitor={mon_d.summary()}, "
          f"drift={det_d.summary()}")
    assert first is not None, \
        "drift detector never fired on the rotated hot set"
    # ticks are 1-based; batch index rotate_at (0-based) is tick
    # rotate_at + 1 — detection strictly after the rotation, within bound
    detect_latency = first - shape["rotate_at"]
    assert detect_latency > 0, \
        f"drift flagged at tick {first}, BEFORE the rotation at batch " \
        f"{shape['rotate_at']} — false positive"
    assert detect_latency <= shape["detect_bound"], \
        f"drift detected {detect_latency} batches after rotation " \
        f"(bound {shape['detect_bound']})"
    hr_breaches = mon_d.summary()["breaches_by_rule"].get("hit_rate", 0)
    assert hr_breaches > 0, \
        "the rotation never breached the windowed hit-rate floor"
    print(f"# OK: drift flagged {detect_latency} batch(es) after "
          f"rotation (bound {shape['detect_bound']}), {hr_breaches} "
          f"hit-rate breaches")

    # -- overhead bound -----------------------------------------------------
    tel_metrics = eng_d.telemetry.metrics
    overhead, frac, costs, counts = windowed_overhead(
        tel_metrics, wall_d)
    print(f"== OVERHEAD ==\n  ops {counts} x per-op "
          f"{ {k: f'{v * 1e6:.2f}us' for k, v in costs.items()} } = "
          f"{overhead * 1e3:.2f} ms over {wall_d:.2f} s serving "
          f"({frac * 100:.3f}%)")
    assert frac < 0.02, f"windowed-metric overhead {frac:.4f} >= 2%"

    # -- artifacts ----------------------------------------------------------
    if csv_path:
        rep = SweepReport("sweep", "run", "tick", "window_hit_rate")
        for run_name, trace in (("control", trace_c), ("drift", trace_d)):
            for tick, rate in trace:
                rep.add(sweep="slo", run=run_name, tick=tick,
                        window_hit_rate=f"{rate:.4f}")
        rep.write(csv_path)
        print(f"wrote {csv_path}")
    if bench_path:
        config = dict(shape, zipf_a=ZIPF_A, threshold=DRIFT_THRESHOLD,
                      min_updates=MIN_UPDATES)
        record = make_bench_record("slo", config=config, metrics={
            # deterministic signals gate; wall-clock stays informational
            "control_drift_events": make_metric(
                det_c.summary()["events"], "1", "lower_is_better", 0.5),
            "control_breaches": make_metric(
                mon_c.breaches, "1", "lower_is_better", 0.5),
            "drift_detect_latency_batches": make_metric(
                detect_latency, "batch", "lower_is_better", 0.5),
            "control_hit_rate": make_metric(
                stats_c.hit_rate, "1", "higher_is_better", 0.02),
            "drift_hit_rate": make_metric(
                stats_d.hit_rate, "1", "higher_is_better", 0.05),
            "drift_hit_rate_breaches": make_metric(
                hr_breaches, "1", "higher_is_better", None),
            "windowed_overhead_fraction": make_metric(
                frac, "1", "lower_is_better", None),
            "worst_window_p99_ms": make_metric(
                mon_d.summary()["worst_p99_s"] * 1e3, "ms",
                "lower_is_better", None),
        })
        write_bench(bench_path, record)
        print(f"wrote {bench_path}")


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI shapes: fewer, smaller batches")
    ap.add_argument("--bench", type=str, default="BENCH_slo.json",
                    help="BenchRecord output ('' to skip)")
    ap.add_argument("--csv", type=str, default=None)
    args = ap.parse_args()
    run(SMOKE if args.smoke else FULL, args.bench, args.csv)
    print("# OK: drift fires on rotation, control stays quiet, "
          "overhead bounded")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
