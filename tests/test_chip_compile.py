"""Compile the serving path's programs for a described TPU v5e.

The TPU compiler is installed even where no chip is attached: it compiles
for a described ``v5e:2x2`` and refuses what the chip would refuse
(block shapes off the (8, 128) tiling, scratch over the VMEM limit, a
collective kernel without its barrier).  Nothing runs, so these tests
say nothing about results or speed.

The topology is described inside a module fixture, never at import:
only one process may hold the TPU library, and test workers import
every test file.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.analysis.contracts import tpu_kernels
from repro.cache import CacheConfig, tiers
from repro.configs.dlrm import CONFIG, dlrmv2
from repro.core import comm
from repro.core.jagged import JaggedBatch, PackedBatch
from repro.core.parallel import make_context
from repro.kernels.embedding_gather import gather_pool_tbe_flat_pallas
from repro.kernels.onesided_a2a import (onesided_all_to_all,
                                        onesided_ring_permute)
from repro.models import dlrm as dlrm_mod
from repro.serving.engine import make_scorer

T, D, L = CONFIG.num_sparse_features, CONFIG.embedding_dim, CONFIG.pooling
B = 1024                  # serving micro-batch
SLOTS = 65536             # slot-pool rows per table


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("hosts",))


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled) -> bool:
    return tpu_kernels(compiled.as_text())[0] > 0


def _has_onesided_kernel(compiled) -> bool:
    return tpu_kernels(compiled.as_text())[1] > 0


@pytest.mark.parametrize("pooling", [1, L])   # MLPerf one-hot, CONFIG
def test_tbe_kernel_compiles_at_serving_batch(one_chip, pooling):
    args = (_sds((T * SLOTS, D), jnp.float32, one_chip),
            _sds((T,), jnp.int32, one_chip),
            _sds((T, B, pooling), jnp.int32, one_chip),
            _sds((T, B, pooling), jnp.float32, one_chip))
    compiled = gather_pool_tbe_flat_pallas.lower(*args).compile()
    assert _has_kernel(compiled)
    assert compiled.out_info.shape == (T, B, D)


def _scorer_args(cfg, tables_shape, sharding):
    params = jax.eval_shape(
        lambda: dlrm_mod.init_params(jax.random.key(0), cfg))
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, sharding), params)
    params["tables"] = _sds(tables_shape, jnp.float32, sharding)
    dense = _sds((B, cfg.num_dense_features), jnp.float32, sharding)
    batch = JaggedBatch(_sds((T, B, L), jnp.int32, sharding),
                        _sds((T, B), jnp.int32, sharding))
    return params, dense, batch


def test_tiered_forward_compiles_at_config_widths(one_chip):
    cfg = dataclasses.replace(CONFIG, kernel_mode="pallas",
                              cache=CacheConfig(rows=SLOTS))
    args = _scorer_args(cfg, (T * SLOTS, D), one_chip)
    compiled = make_scorer(cfg).lower(*args).compile()
    assert _has_kernel(compiled)
    assert compiled.out_info.shape == (B,)


@pytest.mark.parametrize("mode", ["pallas", "reference"])
def test_uncached_forward_fits_one_chip(one_chip, mode):
    """The whole CONFIG tables (13.3 GB) in HBM, scored by the kernel or
    by the jnp reference: the program must fit a 16 GB chip."""
    cfg = dataclasses.replace(CONFIG, kernel_mode=mode)
    args = _scorer_args(cfg, (T, CONFIG.rows_per_table, D), one_chip)
    compiled = make_scorer(cfg).lower(*args).compile()
    assert _has_kernel(compiled) == (mode == "pallas")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


def test_dcnv2_forward_fits_one_chip(one_chip):
    """DLRM-DCNv2 at MLPerf's widths, its five 40M-row tables cut to 5M
    (29.2M rows, 14.9 GB in HBM), scored through the packed multi-hot
    kernel and the cross net: the program compiles and fits a 16 GB chip."""
    cfg = dataclasses.replace(dlrmv2(5_000_000), kernel_mode="pallas")
    params = jax.eval_shape(
        lambda: dlrm_mod.init_params(jax.random.key(0), cfg))
    params = jax.tree.map(lambda a: _sds(a.shape, a.dtype, one_chip), params)
    assert params["tables"].shape == (29_184_588, D)
    batch = PackedBatch(_sds((B, sum(cfg.pooling)), jnp.int32, one_chip),
                        _sds((B, T), jnp.int32, one_chip))
    compiled = make_scorer(cfg).lower(
        params, _sds((B, cfg.num_dense_features), jnp.float32, one_chip),
        batch).compile()
    assert _has_kernel(compiled)
    assert "gather_pool_tbe_multihot_pallas" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


@pytest.mark.parametrize("impl", ["allgather", "a2a"])
@pytest.mark.parametrize("backend", ["bulk", "onesided"])
def test_row_wise_forward_compiles_on_four_chips(four_chips, impl, backend):
    """The DLRM forward with CONFIG tables row-sharded over 4 chips."""
    mesh = Mesh(four_chips.devices.reshape(1, 4), ("data", "model"))
    cfg = dataclasses.replace(CONFIG, kernel_mode="pallas", rw_impl=impl,
                              rw_backend=backend)
    rep = NamedSharding(mesh, P())
    params, dense, batch = _scorer_args(
        cfg, (T, CONFIG.rows_per_table, D), rep)
    params["tables"] = _sds(params["tables"].shape, jnp.float32,
                            NamedSharding(mesh, P(None, "model", None)))
    compiled = make_scorer(cfg, make_context(mesh)).lower(
        params, dense, batch).compile()
    assert _has_kernel(compiled) == (impl == "allgather"
                                     or backend == "onesided")
    # the gather kernel alone does not count: a one-sided program holds
    # the barrier-synchronised RDMA kernel, a bulk one none
    assert _has_onesided_kernel(compiled) == (backend == "onesided")


def test_donated_scatter_rows_compiles(one_chip):
    M = 1 << 15
    compiled = tiers._scatter_rows.lower(
        _sds((T * SLOTS, D), jnp.float32, one_chip),
        _sds((M,), jnp.int32, one_chip),
        _sds((M, D), jnp.float32, one_chip)).compile()
    # the donated pool is updated in place: output aliases argument 0
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == T * SLOTS * D * 4


def test_onesided_fetch_rows_compiles_on_four_chips(four_chips):
    M, rows = 4096, T * SLOTS

    def fetch(shards, addr, owner):
        return shard_map(
            lambda s, a, o: comm.fetch_rows(s[0], a, o, "hosts",
                                            backend="onesided"),
            mesh=four_chips, in_specs=(P("hosts"), P(), P()), out_specs=P(),
            check_vma=False)(shards, addr, owner)

    rep = NamedSharding(four_chips, P())
    compiled = jax.jit(fetch).lower(
        _sds((4, rows, D), jnp.float32, NamedSharding(four_chips, P("hosts"))),
        _sds((M,), jnp.int32, rep), _sds((M,), jnp.int32, rep)).compile()
    assert _has_onesided_kernel(compiled)


@pytest.mark.parametrize("kernel", ["all_to_all", "ring_permute"])
@pytest.mark.parametrize("shape,dtype", [
    ((16, B, D), jnp.float32),
    # the a2a's send buffers: one (E, capacity) row per peer
    ((16, 3 * B + 5), jnp.int32),
])
def test_onesided_exchange_kernels_compile_on_four_chips(four_chips, kernel,
                                                         shape, dtype):
    def body(x):
        if kernel == "all_to_all":
            return onesided_all_to_all(x, "hosts")
        return onesided_ring_permute(x, "hosts")

    fn = jax.jit(shard_map(body, mesh=four_chips, in_specs=P("hosts"),
                           out_specs=P("hosts"), check_vma=False))
    x = _sds(shape, dtype, NamedSharding(four_chips, P("hosts")))
    assert _has_onesided_kernel(fn.lower(x).compile())
