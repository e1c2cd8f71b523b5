"""Multi-device (8 CPU) checks, run as a SUBPROCESS by test_distributed.py
so the rest of the suite keeps the real single-device backend.

Covers: all embedding-bag shardings vs the local oracle, the one-sided
RDMA kernels inside shard_map, distributed train/decode equality for
representative archs, distributed DLRM, and comm instrumentation.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.configs.base import ShapeConfig, TrainConfig
from repro.core import comm
from repro.core.embedding_bag import (
    EmbeddingBagConfig, init_tables, pooled_lookup_local,
    pooled_lookup_sharded, table_pspec,
)
from repro.core.jagged import JaggedBatch, random_jagged_batch
from repro.core.parallel import make_context
from repro.launch import specs as S
from repro.launch.mesh import auto_mesh
from repro.models import decode as dec
from repro.models import dlrm as dlrm_mod
from repro.models import lm
from repro.configs import dlrm as dlrm_cfg_mod
from repro.train.step import init_train_state, lm_loss, make_train_step

failures = []


def check(name, fn):
    try:
        fn()
        print(f"PASS {name}")
    except Exception as e:  # noqa: BLE001
        failures.append(name)
        import traceback
        traceback.print_exc()
        print(f"FAIL {name}: {e}")


# ---------------------------------------------------------------------------
def embedding_shardings():
    mesh = auto_mesh((8,), ("model",))
    rng = np.random.default_rng(0)
    R, D, B, L = 64, 16, 16, 4
    for sharding, rw_impl, backend in [
        ("row", "allgather", "bulk"), ("row", "a2a", "bulk"),
        ("column", None, "bulk"), ("table", None, "bulk"),
        ("replicated", None, "bulk"),
    ]:
        T = 8 if sharding == "table" else 4
        cfg = EmbeddingBagConfig(
            num_tables=T, rows_per_table=R, dim=D, sharding=sharding,
            rw_impl=rw_impl or "allgather", rw_backend=backend,
            capacity_factor=8.0)
        tables = init_tables(jax.random.key(0), cfg)
        batch = random_jagged_batch(rng, T, B, L, R, fixed_pooling=False)
        ref = pooled_lookup_local(tables, batch, cfg)
        out = jax.jit(shard_map(
            lambda t, b: pooled_lookup_sharded(t, b, cfg),
            mesh=mesh, in_specs=(table_pspec(cfg), P()), out_specs=P(),
            check_vma=False))(tables, batch)
        err = float(jnp.abs(out - ref).max())
        assert err < 1e-4, (sharding, rw_impl, err)
    # paper's NVSHMEM reduce-scatter workaround
    cfg = EmbeddingBagConfig(num_tables=4, rows_per_table=R, dim=D,
                             sharding="row", rw_impl="a2a",
                             emulate_rs_with_a2a=True, capacity_factor=8.0)
    tables = init_tables(jax.random.key(0), cfg)
    batch = random_jagged_batch(rng, 4, B, L, R)
    ref = pooled_lookup_local(tables, batch, cfg)
    out = jax.jit(shard_map(
        lambda t, b: pooled_lookup_sharded(t, b, cfg),
        mesh=mesh, in_specs=(table_pspec(cfg), P()), out_specs=P(),
        check_vma=False))(tables, batch)
    assert float(jnp.abs(out - ref).max()) < 1e-4


def onesided_backend_end_to_end():
    """backend="onesided" (Pallas RDMA, interpret) == local, for both RW
    impls; a one-sided request under the jnp reference mode raises."""
    mesh = auto_mesh((8,), ("model",))
    rng = np.random.default_rng(1)
    batch = random_jagged_batch(rng, 4, 16, 4, 64)
    for impl in ("a2a", "allgather"):
        cfg = EmbeddingBagConfig(
            num_tables=4, rows_per_table=64, dim=16, sharding="row",
            rw_impl=impl, rw_backend="onesided", capacity_factor=8.0,
            kernel_mode="interpret")
        tables = init_tables(jax.random.key(0), cfg)
        ref = pooled_lookup_local(tables, batch, cfg)
        out = jax.jit(shard_map(
            lambda t, b: pooled_lookup_sharded(t, b, cfg),
            mesh=mesh, in_specs=(table_pspec(cfg), P()), out_specs=P(),
            check_vma=False))(tables, batch)
        assert float(jnp.abs(out - ref).max()) < 1e-4, impl
    ref_cfg = dataclasses.replace(cfg, kernel_mode="reference")
    try:
        jax.jit(shard_map(
            lambda t, b: pooled_lookup_sharded(t, b, ref_cfg),
            mesh=mesh, in_specs=(table_pspec(cfg), P()), out_specs=P(),
            check_vma=False)).lower(tables, batch)
    except ValueError as e:
        assert "one-sided" in str(e), e
    else:
        raise AssertionError("onesided under kernel_mode='reference' ran")


def comm_instrumentation():
    mesh = auto_mesh((8,), ("model",))
    with comm.instrument() as events:
        x = jnp.zeros((64, 4))          # per-shard (8, 4): split dim == 8
        jax.jit(shard_map(
            lambda v: comm.all_to_all(v, "model"),
            mesh=mesh, in_specs=P("model"), out_specs=P("model"),
            check_vma=False)).lower(x)
    assert len(events) == 1
    assert events[0].op == "all_to_all"
    assert events[0].axis_size == 8
    assert events[0].bytes_in == 8 * 4 * 4


def arch_train_and_decode(arch):
    mesh = auto_mesh((4, 2), ("data", "model"))
    ctx = make_context(mesh)
    tc = TrainConfig(remat=True, optimizer_state_dtype="int8")
    cfg = dataclasses.replace(configs.get_smoke_config(arch),
                              dtype="float32", moe_capacity_factor=8.0)
    B, Sq = 8, 16
    rng = jax.random.key(0)
    state = init_train_state(rng, cfg, tc, tp_size=ctx.tp_size,
                             dtype=jnp.float32)
    batch = {"tokens": jax.random.randint(rng, (B, Sq), 0, cfg.vocab_size),
             "labels": jax.random.randint(rng, (B, Sq), 0, cfg.vocab_size)}
    if cfg.family == "audio":
        batch["frames"] = jax.random.normal(
            rng, (B, cfg.encoder_seq_len, cfg.d_model)) * 0.02
    loss_ref, _ = lm_loss(state["params"], batch, cfg, None, tc)

    pspecs = S.param_spec_tree(state["params"], cfg, ctx)
    ospecs = S.opt_spec_tree(pspecs, state["opt"])
    st_sh = {"params": jax.tree.map(ctx.sharding, pspecs),
             "opt": {"m": jax.tree.map(ctx.sharding, ospecs["m"]),
                     "v": jax.tree.map(ctx.sharding, ospecs["v"]),
                     "step": ctx.sharding(P())}}
    bspec = jax.tree.map(ctx.sharding,
                         S.batch_specs(cfg, ShapeConfig("t", Sq, B, "train"),
                                       ctx))
    state_d = jax.device_put(state, st_sh)
    batch_d = jax.device_put(batch, bspec)
    loss_d, _ = jax.jit(lambda p, b: lm_loss(p, b, cfg, ctx, tc))(
        state_d["params"], batch_d)
    assert abs(float(loss_d) - float(loss_ref)) < 2e-3, \
        (arch, float(loss_ref), float(loss_d))

    step = jax.jit(make_train_step(cfg, tc, ctx),
                   in_shardings=(st_sh, bspec),
                   out_shardings=(st_sh, None), donate_argnums=(0,))
    new_state, metrics = step(state_d, batch_d)
    assert np.isfinite(float(metrics["loss"]))

    # decode
    params_d = new_state["params"]
    pf_kw = ({"frames": batch_d["frames"]} if cfg.family == "audio" else {})
    h_full, _ = jax.jit(
        lambda p, t: lm.forward(p, t, cfg, ctx, **pf_kw))(
            params_d, batch_d["tokens"])
    cache_t = jax.eval_shape(
        lambda: dec.init_cache(cfg, B, Sq + 4, dtype=jnp.float32))
    cspecs = jax.tree.map(ctx.sharding,
                          S.cache_spec_tree(cache_t, cfg, ctx, B))
    cache, _ = jax.jit(
        lambda p, t: dec.prefill(p, t, cfg, ctx, max_len=Sq + 4, **pf_kw),
        out_shardings=(cspecs, None))(params_d, batch_d["tokens"][:, :-1])
    cache, h_dec = jax.jit(
        lambda p, c, t: dec.decode_step(p, c, t, cfg, ctx),
        out_shardings=(cspecs, None))(params_d, cache,
                                      batch_d["tokens"][:, -1])
    err = float(jnp.abs(h_dec - h_full[:, -1]).max())
    assert err < 5e-3, (arch, err)


def beyond_paper_embedding():
    """bf16 reduce-scatter + hot-row replication on the real 8-dev mesh."""
    from repro.core.embedding_bag import extract_hot_table, pooled_lookup_hot
    mesh = auto_mesh((8,), ("model",))
    rng = np.random.default_rng(3)
    R, T, B, L = 256, 4, 16, 8
    base = EmbeddingBagConfig(num_tables=T, rows_per_table=R, dim=16,
                              sharding="row", rw_impl="a2a",
                              capacity_factor=8.0)
    tables = init_tables(jax.random.key(0), base)
    batch = random_jagged_batch(rng, T, B, L, R, zipf_a=1.3)
    ref = pooled_lookup_local(tables, batch, base)

    # (1) bf16 phase-3 reduce-scatter: traffic halves, bounded error
    cfg_bf16 = dataclasses.replace(base, rs_dtype="bfloat16")
    out = jax.jit(shard_map(
        lambda t, b: pooled_lookup_sharded(t, b, cfg_bf16),
        mesh=mesh, in_specs=(table_pspec(cfg_bf16), P()), out_specs=P(),
        check_vma=False))(tables, batch)
    rel = float(jnp.abs(out - ref).max() / (jnp.abs(ref).max() + 1e-9))
    assert rel < 2e-2, rel

    # (2) hot-row replication: exact, and the a2a path only carries cold
    cfg_hot = dataclasses.replace(base, hot_rows=32)
    hot_tbl = extract_hot_table(tables, cfg_hot)
    out = jax.jit(shard_map(
        lambda t, h, b: pooled_lookup_hot(t, h, b, cfg_hot),
        mesh=mesh,
        in_specs=(table_pspec(cfg_hot), P(None, None, None), P()),
        out_specs=P(), check_vma=False))(tables, hot_tbl, batch)
    assert float(jnp.abs(out - ref).max()) < 1e-4


def dlrm_distributed():
    mesh = auto_mesh((4, 2), ("data", "model"))
    ctx = make_context(mesh)
    cfg = dataclasses.replace(dlrm_cfg_mod.smoke(), rows_per_table=128)
    params = dlrm_mod.init_params(jax.random.key(0), cfg)
    rng = np.random.default_rng(0)
    batch = random_jagged_batch(rng, cfg.num_sparse_features, 8,
                                cfg.pooling, cfg.rows_per_table)
    dense = jnp.asarray(rng.standard_normal((8, cfg.num_dense_features)),
                        jnp.float32)
    ref = dlrm_mod.forward(params, dense, batch, cfg, None)
    out = jax.jit(lambda p, d, b: dlrm_mod.forward(p, d, b, cfg, ctx))(
        params, dense, batch)
    assert float(jnp.abs(out - ref).max()) < 1e-3


def elastic_reshard():
    """Train 2 steps on (4,2), checkpoint, restore onto (2,4): losses match."""
    import tempfile
    from repro import checkpoint as ckpt
    cfg = dataclasses.replace(configs.get_smoke_config("granite-8b"),
                              dtype="float32")
    tc = TrainConfig(remat=False)
    rng = jax.random.key(0)
    batch = {"tokens": jax.random.randint(rng, (8, 16), 0, cfg.vocab_size),
             "labels": jax.random.randint(rng, (8, 16), 0, cfg.vocab_size)}
    state = init_train_state(rng, cfg, tc, tp_size=2, dtype=jnp.float32)
    mesh_a = auto_mesh((4, 2), ("data", "model"))
    ctx_a = make_context(mesh_a)
    with tempfile.TemporaryDirectory() as d:
        ckpt.save(state, d, 0)
        # new topology: tp=2 kept (vocab padding depends on it), dp reshaped
        mesh_b = auto_mesh((2, 2, 2), ("pod", "data", "model"))
        ctx_b = make_context(mesh_b)
        pspecs = S.param_spec_tree(state["params"], cfg, ctx_b)
        sh = {"params": jax.tree.map(ctx_b.sharding, pspecs),
              "opt": jax.tree.map(
                  ctx_b.sharding,
                  S.opt_spec_tree(pspecs, state["opt"]))}
        restored = ckpt.restore(state, d, shardings=sh)
        la, _ = lm_loss(state["params"], batch, cfg, None, tc)
        lb, _ = jax.jit(lambda p, b: lm_loss(p, b, cfg, ctx_b, tc))(
            restored["params"], batch)
        assert abs(float(la) - float(lb)) < 2e-3


check("embedding_shardings", embedding_shardings)
check("onesided_backend_end_to_end", onesided_backend_end_to_end)
check("comm_instrumentation", comm_instrumentation)
for a in ("moonshot-v1-16b-a3b", "deepseek-v3-671b", "hymba-1.5b",
          "yi-34b", "rwkv6-1.6b", "whisper-base"):
    check(f"arch_train_and_decode[{a}]",
          lambda a=a: arch_train_and_decode(a))
check("beyond_paper_embedding", beyond_paper_embedding)
check("dlrm_distributed", dlrm_distributed)
check("elastic_reshard", elastic_reshard)

if failures:
    print("FAILURES:", failures)
    sys.exit(1)
print("ALL DIST CHECKS PASS")
