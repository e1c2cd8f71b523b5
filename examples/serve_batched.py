"""Batched serving: LM continuous batching AND pipelined DLRM scoring.

LM cell: requests arrive, slots are admitted/evicted, one jitted
decode_step advances every active sequence.

DLRM cell: the same CTR request stream served by the serialized
``DLRMEngine`` (depth 1) and the ``PipelinedDLRMEngine`` (depth 2 —
double-buffered slot pools, shadow prefetch under the live forward),
configured PURELY through ``DLRMConfig`` fields; scores are asserted
equal and the measured stage spans / overlap fraction are printed.

    PYTHONPATH=src python examples/serve_batched.py
"""
import dataclasses
import time

import numpy as np
import jax

from repro import configs
from repro.models import lm
from repro.serving.engine import ContinuousBatcher, Request


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
    s, ss = piped.cache_stats(), serial.cache_stats()
    print(f"DLRM: {len(reqs)} reqs x 2 engines, scores equal "
          f"(depth 2 vs depth 1)")
    print(f"  serialized spans: prefetch={ss.prefetch_s*1e3:.1f}ms "
          f"scatter={ss.scatter_s*1e3:.1f}ms forward={ss.forward_s*1e3:.1f}ms"
          f" (overlap {ss.overlap_fraction:.2f})")
    print(f"  pipelined  spans: prefetch={s.prefetch_s*1e3:.1f}ms "
          f"scatter={s.scatter_s*1e3:.1f}ms forward={s.forward_s*1e3:.1f}ms "
          f"(overlap {s.overlap_fraction:.2f})")
    for stage in ("admit", "fetch", "scatter", "forward", "swap"):
        print(f"    stage {stage:8s} {piped.trace.total(stage)*1e3:8.2f}ms")
    # end-of-run SLO summary: every completed window was judged live
    summ = mon.summary()
    print(f"  SLO [{summ['policy']}] windows={summ['windows_evaluated']} "
          f"breaches={summ['breaches']} "
          f"worst_p99={summ['worst_p99_s']*1e3:.2f}ms "
          f"by_rule={summ['breaches_by_rule']}")
    assert summ["windows_evaluated"] > 0, "the monitor must see windows"
    # cold-start hit_rate breaches are expected; latency/depth are not
    assert set(summ["breaches_by_rule"]) <= {"hit_rate"}, \
        "a 30s p99 budget / 256-deep queue cap must not breach here"


def main():
    cfg = configs.get_smoke_config("granite-8b")
    params = lm.init_params(jax.random.key(0), cfg)
    eng = ContinuousBatcher(params, cfg, num_slots=4, max_len=64, eos_id=-1)

    rng = np.random.default_rng(0)
    n_req = 10
    for rid in range(n_req):
        eng.submit(Request(
            rid=rid,
            prompt=rng.integers(0, cfg.vocab_size,
                                rng.integers(4, 12)).astype(np.int32),
            max_new=int(rng.integers(4, 10))))

    t0 = time.perf_counter()
    done = eng.run_to_completion()
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in done.values())
    print(f"served {len(done)}/{n_req} requests, {total} tokens in "
          f"{dt:.2f}s ({total/dt:.1f} tok/s, 4 slots, continuous batching)")
    for rid in sorted(done):
        r = done[rid]
        print(f"  req {rid}: prompt_len={len(r.prompt)} -> "
              f"{len(r.generated)} tokens: {r.generated}")
    assert len(done) == n_req

    serve_dlrm_pipelined()


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
