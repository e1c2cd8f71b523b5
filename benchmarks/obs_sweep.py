"""Observability sweep: one merged timeline, latency quantiles, calibration.

Drives BOTH engines over a warm tiered cache with a REMOTE cold tier
(2 simulated hosts) under one :class:`repro.obs.Telemetry`, then closes
the measurement loop three ways:

  * TRACE    — exports the merged Chrome trace-event / Perfetto JSON
    (engine, pipeline, request, cache, and comm lanes on one
    ``perf_counter`` clock) and asserts the golden schema plus presence
    of spans from both engines and at least one runtime-timestamped
    ``fetch_rows`` collective;
  * LATENCY  — prints each engine's enqueue->score p50/p95/p99 from the
    ``<engine>.request_latency_s`` histograms;
  * CALIBRATE — fits ``perf_model.Hardware`` serving-stage constants
    (``gather_overhead_s`` / ``host_Bps`` / fetch-transport α–β) from
    the TRAIN window's measured spans and asserts the fitted model
    predicts the HELD-OUT window's stage times with lower relative
    error than the hand-set ``H100_DGX`` / ``TPU_V5E`` constants.
    (On this CPU host the hand-set accelerator constants underpredict
    wall-clock by orders of magnitude — the point of the assertion is
    that the fit actually tracks the measured platform.)

Telemetry cost is bounded too: per-op record costs are microbenchmarked
and multiplied by the actual event/observation counts; the projected
overhead must stay under 2% of the serving wall-clock.

Artifacts: ``--trace`` (Chrome JSON, load at ui.perfetto.dev or
chrome://tracing), ``--metrics`` (versioned ``write_snapshot`` JSON with
the calibration numbers — CI's ``BENCH_obs.json``), ``--csv`` (the
calibration error table as a :class:`repro.obs.SweepReport`).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=2")

import numpy as np          # noqa: E402
import jax                  # noqa: E402

from repro.configs import dlrm as dlrm_cfg                      # noqa: E402
from repro.core.cache_config import CacheConfig                 # noqa: E402
from repro.core.perf_model import (                             # noqa: E402
    H100_DGX, TPU_V5E, calibrate, stage_time_error)
from repro.models import dlrm as dlrm_mod                       # noqa: E402
from repro.obs import (                                         # noqa: E402
    Histogram, SweepReport, Telemetry, Tracer, validate_chrome_trace,
    write_snapshot)
from repro.obs.bench import (                                   # noqa: E402
    make_bench_record, make_metric, write_bench)
from repro.serving.engine import CTRRequest, make_dlrm_engine   # noqa: E402

SHAPE = dict(tables=4, rows=1 << 12, dim=32, pooling=8, cache=256,
             zipf=1.05, hosts=2)
# window sizes (requests per flush): varied so the h2d / fetch_remote
# least-squares design matrices span a real byte range — identical
# windows would make the affine fit rank-1
FULL = dict(train=(4, 8, 16, 32) * 3, hold=(6, 12, 24) * 2, piped=4)
SMOKE = dict(train=(4, 8, 16, 32), hold=(6, 24), piped=2)


def _config(shape: dict, *, depth: int = 1) -> dlrm_cfg.DLRMConfig:
    return dlrm_cfg.DLRMConfig(
        num_sparse_features=shape["tables"],
        rows_per_table=shape["rows"],
        embedding_dim=shape["dim"],
        pooling=shape["pooling"],
        num_dense_features=4,
        bottom_mlp=(64, shape["dim"]),
        top_mlp=(64, 1),
        kernel_mode="reference",          # CPU-tractable, same both engines
        cache=CacheConfig(rows=shape["cache"], policy="lru",
                          cold_tier="remote", remote_hosts=shape["hosts"],
                          pipeline_depth=depth),
    )


def _requests(cfg, n, rng, rid0=0, zipf=1.05):
    T, L, F = (cfg.num_sparse_features, cfg.pooling,
               cfg.num_dense_features)
    R = cfg.rows_per_table
    out = []
    for rid in range(rid0, rid0 + n):
        idx = np.minimum(rng.zipf(zipf, size=(T, L)) - 1, R - 1)
        out.append(CTRRequest(
            rid=rid, dense=rng.standard_normal(F).astype(np.float32),
            indices=idx.astype(np.int32),
            lengths=np.full(T, L, np.int32)))
    return out


def _serve(engine, cfg, windows, rng, rid0, zipf) -> float:
    """One flush per window size; returns (serving seconds, next rid)."""
    t0 = time.perf_counter()
    for n in windows:
        for r in _requests(cfg, n, rng, rid0=rid0, zipf=zipf):
            engine.submit(r)
        rid0 += n
        engine.run_to_completion()
    return time.perf_counter() - t0, rid0


def _prewarm_buckets(engine) -> None:
    """Compile the cold-tier fetch and donated pool-scatter programs for
    every power-of-two request bucket (``_pad_pow2``) a flush can hit —
    one-off jit compiles would otherwise land INSIDE measured prefetch
    spans and poison the calibration fit with multi-ms outliers."""
    cache = engine.cache
    bags = cache.buffers if hasattr(cache, "buffers") else [cache]
    sizes = [1 << i for i in range(12)]
    for bag in bags:
        row0 = np.asarray(bag.pool)[:1]             # (1, D) flat slot 0
        for m in sizes:
            bag.hot.scatter(np.zeros(m, np.int64),
                            np.repeat(row0, m, axis=0))
    for m in sizes:                                 # remote fetch buckets
        bags[0].cold.fetch(np.zeros(m, np.int64), np.zeros(m, np.int64))


def _per_op_cost(fn, n: int = 20_000) -> float:
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n


def run(shape: dict, windows: dict, trace_path: str, metrics_path: str,
        csv_path: str | None, bench_path: str | None = None,
        smoke: bool = False) -> None:
    tel = Telemetry()
    tel.tracer.install_comm_sink()
    cfg = _config(shape)
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(1)
    B = max(*windows["train"], *windows["hold"])
    serial = make_dlrm_engine(params, cfg, batch_size=B, telemetry=tel)

    # warmup: compile every pad_pow2 fetch/scatter bucket and fill the
    # pool — these spans land on the timeline but sit BEFORE the train
    # mark, so the calibration windows never see compile time
    _prewarm_buckets(serial)
    wall = 0.0
    dt, rid0 = _serve(serial, cfg, windows["train"] + windows["hold"],
                      rng, 0, shape["zipf"])
    wall += dt

    mark_train = Tracer.now()
    dt, rid0 = _serve(serial, cfg, windows["train"], rng, rid0,
                      shape["zipf"])
    wall += dt
    train = tel.tracer.stage_samples(since=mark_train)

    mark_hold = Tracer.now()
    dt, rid0 = _serve(serial, cfg, windows["hold"], rng, rid0,
                      shape["zipf"])
    wall += dt
    hold = tel.tracer.stage_samples(since=mark_hold)

    # the pipelined engine shares the timeline: pipeline-lane stage
    # spans + its own request-latency histogram
    piped = make_dlrm_engine(params, _config(shape, depth=2),
                             batch_size=B, telemetry=tel)
    _prewarm_buckets(piped)
    dt, rid0 = _serve(piped, cfg, (B,) * windows["piped"], rng, rid0,
                      shape["zipf"])
    wall += dt
    tel.tracer.remove_comm_sink()

    # -- latency quantiles --------------------------------------------------
    print(f"== LATENCY (enqueue->score, {rid0} requests) ==")
    for eng in (serial, piped):
        h = tel.request_latency(eng.obs_name)
        assert h.count > 0, f"no latency observations for {eng.obs_name}"
        print(f"  {eng.obs_name:16s} n={h.count:4d}  "
              f"p50={h.p50 * 1e3:8.3f} ms  p95={h.p95 * 1e3:8.3f} ms  "
              f"p99={h.p99 * 1e3:8.3f} ms")

    # -- merged trace -------------------------------------------------------
    tel.export_trace(trace_path)
    with open(trace_path) as f:
        obj = json.load(f)
    n_events = validate_chrome_trace(obj)
    engines_seen = {e["args"]["engine"] for e in obj["traceEvents"]
                    if e.get("args", {}).get("engine")}
    assert {"dlrm", "dlrm_pipelined"} <= engines_seen, engines_seen
    comm_spans = [s for s in tel.tracer.spans(lane="comm",
                                              name="fetch_rows")
                  if s.seconds > 0]
    assert comm_spans, "no runtime-timestamped fetch_rows event on the trace"
    print(f"== TRACE ==\n  {trace_path}: {n_events} events, engines "
          f"{sorted(engines_seen)}, {len(comm_spans)} timed fetch_rows "
          f"collectives")

    # -- calibration: train window in, held-out window judged ---------------
    stages = sorted({s.stage for s in train})
    assert {"h2d", "fetch_remote"} <= set(stages), stages
    rep = SweepReport("sweep", "base", "window", "stage", "err_before",
                      "err_after")
    print(f"== CALIBRATION ({len(train)} train / {len(hold)} held-out "
          f"samples) ==")
    extra = {"calibration": {}}
    for base in (H100_DGX, TPU_V5E):
        res = calibrate(train, base)
        before = stage_time_error(hold, base)
        after = res.error(hold)
        print(f"  base {base.name}: fitted gather_overhead_s="
              f"{res.hw.gather_overhead_s:.2e} host_Bps="
              f"{res.hw.host_Bps:.2e} alpha_s={res.hw.bulk.alpha_s:.2e} "
              f"beta_Bps={res.hw.bulk.beta_Bps:.2e}")
        for stage in [*stages, "total"]:
            print(f"    held-out {stage:12s} rel err "
                  f"{before[stage]:8.4f} -> {after[stage]:8.4f}")
            rep.add(sweep="obs", base=base.name, window="holdout",
                    stage=stage, err_before=f"{before[stage]:.4f}",
                    err_after=f"{after[stage]:.4f}")
        assert after["total"] < before["total"], (
            f"calibration did not beat hand-set {base.name} constants on "
            f"the held-out window: {after['total']:.4f} >= "
            f"{before['total']:.4f}")
        extra["calibration"][base.name] = {
            "gather_overhead_s": res.hw.gather_overhead_s,
            "host_Bps": res.hw.host_Bps,
            "alpha_s": res.hw.bulk.alpha_s,
            "beta_Bps": res.hw.bulk.beta_Bps,
            "n_h2d": res.n_h2d, "n_remote": res.n_remote,
            "holdout_err_before": before, "holdout_err_after": after,
        }
        print(f"  OK: calibrated {base.name} beats hand-set constants "
              f"({after['total']:.4f} < {before['total']:.4f})")

    # -- overhead bound -----------------------------------------------------
    # projected from microbenchmarked per-op costs x actual counts — a
    # wall-clock A/B on a noisy CI host would drown the signal
    bench_tracer = Tracer()
    span_cost = _per_op_cost(
        lambda: bench_tracer.add_span("x", 0.0, 1.0, lane="engine"))
    bench_hist = Histogram("x")
    obs_cost = _per_op_cost(lambda: bench_hist.observe(1e-3))
    overhead = (span_cost * tel.tracer.event_count
                + obs_cost * tel.metrics.observation_count)
    frac = overhead / wall
    print(f"== OVERHEAD ==\n  {tel.tracer.event_count} spans x "
          f"{span_cost * 1e6:.2f} us + {tel.metrics.observation_count} "
          f"observations x {obs_cost * 1e6:.2f} us = {overhead * 1e3:.2f} "
          f"ms over {wall:.2f} s serving ({frac * 100:.3f}%)")
    assert frac < 0.02, f"telemetry overhead {frac:.4f} >= 2%"

    extra["overhead_fraction"] = frac
    extra["trace_events"] = n_events
    write_snapshot(metrics_path, metrics=tel.metrics, extra=extra)
    print(f"wrote {metrics_path}")
    if csv_path:
        rep.write(csv_path)
        print(f"wrote {csv_path}")
    if bench_path:
        # span/observation counts are pure functions of the serving
        # shapes; the calibration fit and overhead projection are
        # wall-clock-shaped, so they ride along as informational
        h100 = extra["calibration"][H100_DGX.name]
        record = make_bench_record(
            "obs", config=dict(shape, smoke=smoke, **windows),
            metrics={
                "trace_events": make_metric(
                    n_events, "1", "higher_is_better", 0.10),
                "observations": make_metric(
                    tel.metrics.observation_count, "1",
                    "higher_is_better", 0.10),
                "overhead_fraction": make_metric(
                    frac, "1", "lower_is_better", None),
                "calib_holdout_err_h100": make_metric(
                    h100["holdout_err_after"]["total"], "1",
                    "lower_is_better", None),
            })
        write_bench(bench_path, record)
        print(f"wrote {bench_path}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI shapes: fewer serving windows")
    ap.add_argument("--trace", type=str, default="obs_trace.json")
    ap.add_argument("--metrics", type=str, default="obs_metrics.json",
                    help="write_snapshot JSON (full registry + calibration)")
    ap.add_argument("--csv", type=str, default=None)
    ap.add_argument("--bench", type=str, default="BENCH_obs.json",
                    help="BenchRecord output ('' to skip)")
    args = ap.parse_args()
    run(SHAPE, SMOKE if args.smoke else FULL, args.trace, args.metrics,
        args.csv, bench_path=args.bench or None, smoke=args.smoke)


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
