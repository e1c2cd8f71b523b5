"""Batched serving: pipelined DLRM scoring.

The same CTR request stream served by the serialized ``DLRMEngine``
(depth 1) and the ``PipelinedDLRMEngine`` (depth 2 — double-buffered slot
pools, shadow prefetch under the live forward), configured PURELY through
``DLRMConfig`` fields; scores are asserted equal, and an SLO monitor
judges the pipelined engine's windows as they complete.

    PYTHONPATH=src python examples/serve_batched.py
"""
import dataclasses

import numpy as np
import jax


def serve_dlrm_pipelined():
    """Depth-2 pipelined CTR scoring vs the serialized engine."""
    from repro.cache import CacheConfig
    from repro.configs import dlrm as dlrm_cfg
    from repro.models import dlrm as dlrm_mod
    from repro.obs import Telemetry
    from repro.obs.slo import SLOMonitor, SLOPolicy
    from repro.serving.engine import CTRRequest, make_dlrm_engine

    base = dataclasses.replace(
        dlrm_cfg.smoke(), kernel_mode="reference",
        cache=CacheConfig(rows=32, policy="lru"))
    params = dlrm_mod.init_params(jax.random.key(0), base)
    T, L, F = (base.num_sparse_features, base.pooling,
               base.num_dense_features)
    rng = np.random.default_rng(0)
    reqs = []
    for rid in range(24):
        ranks = rng.zipf(1.2, size=(T, L))
        reqs.append(CTRRequest(
            rid=rid, dense=rng.standard_normal(F).astype(np.float32),
            indices=np.minimum(ranks - 1,
                               base.rows_per_table - 1).astype(np.int32),
            lengths=rng.integers(1, L + 1, T).astype(np.int32)))

    # engine selection is pure config: cache.pipeline_depth 1 vs 2;
    # one Telemetry watches both, and an SLOMonitor evaluates the
    # pipelined engine's windows as they complete (a tick listener)
    tel = Telemetry(window=4)
    serial = make_dlrm_engine(params, base, batch_size=8, telemetry=tel)
    piped = make_dlrm_engine(
        params,
        dataclasses.replace(
            base, cache=dataclasses.replace(base.cache, pipeline_depth=2)),
        batch_size=8, telemetry=tel)
    # a generous latency budget (smoke run, includes jit compiles) plus
    # a hit-rate floor the COLD-START windows are expected to breach —
    # demonstrating the monitor actually fires
    mon = SLOMonitor(tel, SLOPolicy(
        name="example", p99_budget_s=30.0, hit_rate_floor=0.05,
        queue_depth_cap=256), engine=piped.obs_name)
    for r in reqs:
        serial.submit(r)
        piped.submit(r)
    want = serial.run_to_completion()
    got = piped.run_to_completion()
    assert sorted(got) == sorted(want)
    assert all(got[rid] == want[rid] for rid in want), \
        "pipelined scores must equal the serialized engine's"
    print(f"DLRM: {len(reqs)} reqs x 2 engines, scores equal "
          f"(depth 2 vs depth 1)")
    # end-of-run SLO summary: every completed window was judged live
    summ = mon.summary()
    print(f"  SLO [{summ['policy']}] windows={summ['windows_evaluated']} "
          f"breaches={summ['breaches']} by_rule={summ['breaches_by_rule']}")
    assert summ["windows_evaluated"] > 0, "the monitor must see windows"
    # cold-start hit_rate breaches are expected; latency/depth are not
    assert set(summ["breaches_by_rule"]) <= {"hit_rate"}, \
        "a 30s p99 budget / 256-deep queue cap must not breach here"


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    serve_dlrm_pipelined()
