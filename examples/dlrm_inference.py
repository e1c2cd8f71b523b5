"""End-to-end DLRM inference — the paper's model (Fig. 2) with the
distributed Embedding Bag under every sharding strategy AND the tiered
embedding store serving path.

    PYTHONPATH=src python examples/dlrm_inference.py

Single device: serves batched CTR requests through ``DLRMEngine`` with
the tiered cache configured ENTIRELY through ``DLRMConfig.cache`` (one
``CacheConfig`` carrying rows / policy / cold_tier / warmup_freqs) —
the engine's HBM holds only the flat slot pool, the cold tables stay
host-resident — and cross-checks the scores against the uncached
direct forward.

With >1 devices (XLA_FLAGS=--xla_force_host_platform_device_count=8) it
additionally compares all distributed sharding strategies (RW both
impls / CW / TW) for correctness and traces the collective traffic each
one issues (the paper's phase structure).
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.cache import CacheConfig
from repro.configs import dlrm as dlrm_cfg
from repro.core import comm
from repro.core.jagged import JaggedBatch, random_jagged_batch
from repro.core.parallel import make_context
from repro.launch.mesh import auto_mesh
from repro.models import dlrm as dlrm_mod
from repro.serving.engine import CTRRequest, DLRMEngine


def serve_tiered(base, params, rng):
    """DLRMEngine over the tiered store, configured via DLRMConfig only."""
    T, L, F = (base.num_sparse_features, base.pooling,
               base.num_dense_features)
    # logged frequencies (the offline ids_freq_mapping): zipf-ish skew
    freqs = (1.0 / np.arange(1, base.rows_per_table + 1)) * 1e4
    cfg = dataclasses.replace(
        base,
        cache=CacheConfig(
            rows=max(base.pooling, base.rows_per_table // 8),
            policy="lfu",
            cold_tier="host",        # "remote" once >1 hosts back the store
            warmup_freqs=freqs,      # skip the cold-start miss burst
        ),
    )
    engine = DLRMEngine(params, cfg, batch_size=8)
    assert engine.params["tables"] is None, "HBM must hold only the pool"

    reqs = []
    for rid in range(24):
        ranks = rng.zipf(1.2, size=(T, L))
        reqs.append(CTRRequest(
            rid=rid,
            dense=rng.standard_normal(F).astype(np.float32),
            indices=np.minimum(ranks - 1,
                               base.rows_per_table - 1).astype(np.int32),
            lengths=rng.integers(1, L + 1, T).astype(np.int32)))
        engine.submit(reqs[-1])
    scores = engine.run_to_completion()

    worst = 0.0
    for r in reqs:
        batch = JaggedBatch(jnp.asarray(r.indices[:, None, :]),
                            jnp.asarray(r.lengths[:, None]))
        want = float(jax.nn.sigmoid(dlrm_mod.forward(
            params, jnp.asarray(r.dense[None]), batch, base))[0])
        worst = max(worst, abs(scores[r.rid] - want))
    stats = engine.cache_stats()
    print(f"tiered engine: {len(reqs)} reqs scored, max |err| vs uncached "
          f"forward = {worst:.2e}")
    print(f"tiered engine: {stats}")
    assert worst < 1e-5
    # warmup pre-admitted the logged-hot head: the FIRST flush already hits
    assert stats.hits > 0 and stats.hit_rate > 0.5, str(stats)


def main():
    n_dev = len(jax.devices())
    base = dataclasses.replace(
        dlrm_cfg.smoke(), num_sparse_features=8, rows_per_table=4096,
        embedding_dim=64, pooling=16, bottom_mlp=(128, 64))
    B = 32
    rng = np.random.default_rng(0)
    dense = jnp.asarray(rng.standard_normal((B, base.num_dense_features)),
                        jnp.float32)
    batch = random_jagged_batch(rng, base.num_sparse_features, B,
                                base.pooling, base.rows_per_table)
    params = dlrm_mod.init_params(jax.random.key(0), base)

    ref = dlrm_mod.forward(params, dense, batch, base)
    print(f"local oracle CTR logits[:4] = {np.asarray(ref[:4]).round(4)}")

    serve_tiered(base, params, rng)

    if n_dev == 1:
        print("single device: distributed comparison needs >1 device "
              "(run under XLA_FLAGS=--xla_force_host_platform_device_count=8)")
        return

    mesh = auto_mesh((1, n_dev), ("data", "model"))
    ctx = make_context(mesh)
    for sharding, impl in [("row", "allgather"), ("row", "a2a"),
                           ("column", None), ("table", None)]:
        cfg = dataclasses.replace(base, sharding=sharding,
                                  rw_impl=impl or "allgather")
        with comm.instrument() as events:
            out = jax.jit(lambda p, d, b: dlrm_mod.forward(
                p, d, b, cfg, ctx))(params, dense, batch)
        err = float(jnp.abs(out - ref).max())
        traffic = {}
        for e in events:
            traffic[e.op] = traffic.get(e.op, 0) + e.bytes_in
        print(f"{sharding:7s}{('/' + impl) if impl else '':11s} "
              f"err={err:.1e}  traffic={traffic}")
    print("OK: every sharding strategy reproduces the oracle.")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
