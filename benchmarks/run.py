"""Benchmark driver — one section per paper artifact.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig1,fig9

Sections:
  fig1      collectives: bulk vs one-sided across message sizes (§3)
  fig6_8    embedding-bag phase times across tables/batch/pooling (§4.4)
  fig9      local-vs-distributed projection (§5.2)
  measured  wall-clock microbenches of the real pipeline on this host
  roofline  per-cell terms from the dry-run artifacts (deliverable g)
"""
from __future__ import annotations

import argparse
import sys
import time


def _section(name):
    print(f"\n{'='*72}\n== {name}\n{'='*72}")


def run_measured():
    """Measured us/call of the actual kernels on this host (CPU).

    Not TPU numbers — these validate that the pipeline executes and give
    the relative phase structure; format: name,us_per_call,derived.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.embedding_bag import (
        EmbeddingBagConfig, init_tables, pooled_lookup_local)
    from repro.core.jagged import random_jagged_batch
    from repro.kernels import ops as kops

    rng = np.random.default_rng(0)
    print("name,us_per_call,derived")
    for T, B, L, R, D in [(8, 128, 8, 1 << 16, 128),
                          (26, 512, 32, 1 << 16, 128)]:
        cfg = EmbeddingBagConfig(num_tables=T, rows_per_table=R, dim=D)
        tables = init_tables(jax.random.key(0), cfg)
        batch = random_jagged_batch(rng, T, B, L, R)
        f = jax.jit(lambda t, b: pooled_lookup_local(t, b, cfg))
        f(tables, batch).block_until_ready()
        t0 = time.perf_counter()
        n = 5
        for _ in range(n):
            f(tables, batch).block_until_ready()
        dt = (time.perf_counter() - t0) / n
        gb = T * B * L * D * 4 / 1e9
        print(f"embedding_bag_local_T{T}_B{B}_L{L},{dt*1e6:.1f},"
              f"{gb/dt:.2f}GB/s_gather")
    # single-table kernel path
    table = jax.random.normal(jax.random.key(1), (1 << 14, 128))
    idx = jnp.asarray(rng.integers(0, 1 << 14, (256, 16)), jnp.int32)
    f = jax.jit(lambda t, i: kops.embedding_bag(t, i, mode="reference"))
    f(table, idx).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        f(table, idx).block_until_ready()
    dt = (time.perf_counter() - t0) / 20
    print(f"embedding_bag_kernel_ref_B256_L16,{dt*1e6:.1f},-")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig1,fig6_8,fig9,measured,roofline")
    args = ap.parse_args(argv)
    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    if want("fig1"):
        _section("Fig 1: collective latency, bulk vs one-sided")
        from benchmarks import collectives
        collectives.main()
    if want("fig6_8"):
        _section("Figs 6-8: embedding-bag phase times (tables/batch/pooling)")
        from benchmarks import embedding_bag_phases
        embedding_bag_phases.main()
    if want("fig9"):
        _section("Fig 9: local vs distributed projection")
        from benchmarks import distributed_projection
        distributed_projection.main()
    if want("beyond"):
        _section("Beyond-paper: bf16 reduce-scatter + hot-row replication")
        from benchmarks import beyond_paper
        beyond_paper.main()
    if want("measured"):
        _section("Measured microbenches (this host)")
        run_measured()
    if want("roofline"):
        _section("Roofline (from dry-run artifacts)")
        from benchmarks import roofline
        roofline.main()


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
