"""Quickstart: the paper's distributed Embedding Bag in 60 lines.

    PYTHONPATH=src python examples/quickstart.py

Builds a row-wise-sharded embedding bag on a (1, N)-device mesh (uses all
local devices), runs the paper's three-phase pipeline (index permute ->
gather/pool -> reduce-scatter), and verifies it against the local oracle.
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.core.embedding_bag import (
    EmbeddingBagConfig,
    init_tables,
    pooled_lookup_local,
    pooled_lookup_sharded,
    table_pspec,
)
from repro.core.jagged import random_jagged_batch
from repro.launch.mesh import auto_mesh


def main():
    n_dev = len(jax.devices())
    mesh = auto_mesh((n_dev,), ("model",))
    print(f"mesh: {n_dev} devices on axis 'model'")

    cfg = EmbeddingBagConfig(
        num_tables=8,             # 8 sparse features
        rows_per_table=1 << 16,   # 65k rows each
        dim=128,                  # paper fixes dim=128
        sharding="row",           # the paper's RW parallelism
        rw_impl="a2a",            # paper-faithful 3-phase pipeline
        capacity_factor=4.0,
    )
    tables = init_tables(jax.random.key(0), cfg)
    print(f"tables: {tables.shape} = "
          f"{tables.size * 4 / 2**20:.0f} MiB, row-sharded {n_dev}-way")

    rng = np.random.default_rng(0)
    batch = random_jagged_batch(
        rng, cfg.num_tables, batch_size=64, pooling=16,
        num_rows=cfg.rows_per_table)

    pooled = jax.jit(shard_map(
        lambda t, b: pooled_lookup_sharded(t, b, cfg),
        mesh=mesh,
        in_specs=(table_pspec(cfg), P()),
        out_specs=P(),
        check_vma=False,
    ))(tables, batch)
    print(f"pooled output: {pooled.shape}  (batch, tables, dim)")

    ref = pooled_lookup_local(tables, batch, cfg)
    err = float(jnp.abs(pooled - ref).max())
    print(f"max |distributed - local oracle| = {err:.2e}")
    assert err < 1e-4
    print("OK: the distributed pipeline reproduces the local pooling.")


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
