"""The DLRM serving engine under test, built from a benchmark configuration.

A configuration file (``bench/configs/<name>.json``) holds the
``repro.configs.dlrm.DLRMConfig`` fields at its top level, the tiered
store's ``repro.core.cache_config.CacheConfig`` fields under ``cache``,
the engine's micro-batch ``batch_size``, the ``matmul_precision`` it is
served at, ``chips``, and the ``source``, ``reduced`` and ``assumed`` of
the deployment it stands for.

The weights are the benchmark's own (``dlrm_reference``), drawn on the
device in one program; the engine is ``make_dlrm_engine``, the entry a
user calls, with ``submit``, ``flush`` and ``run_to_completion``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import dlrm_reference as ref

# the fused TBE kernel as it appears on the device trace
TBE_KERNEL = r"gather_pool_tbe_flat_pallas"

_FIELDS = ("num_dense_features", "num_sparse_features", "embedding_dim",
           "rows_per_table", "pooling", "bottom_mlp", "top_mlp",
           "interaction", "sharding", "rw_impl", "rw_backend", "dtype",
           "kernel_mode", "fused")
_WEIGHT_STREAM = 1   # fold_in tag of the MLP weights


def dlrm_config(cfg: dict, warmup_freqs=None):
    from repro.configs.dlrm import DLRMConfig
    from repro.core.cache_config import CacheConfig

    fields = {k: tuple(cfg[k]) if isinstance(cfg[k], list) else cfg[k]
              for k in _FIELDS}
    return DLRMConfig(**fields, cache=CacheConfig(**cfg.get("cache", {}),
                                                  warmup_freqs=warmup_freqs))


def _mesh(devices):
    from repro.launch.mesh import auto_mesh

    return auto_mesh((1, len(devices)), ("data", "model"), devices=devices)


def make_weights(seed: int, cfg: dict, devices):
    """Tables ``(T, R, D)`` and MLPs drawn from ``seed`` in one jitted
    program, in float32, placed as the engine serves them: on one chip,
    or the tables row-sharded over ``devices``."""
    T, R, D = (cfg["num_sparse_features"], cfg["rows_per_table"],
               cfg["embedding_dim"])
    words = ref.seed_words(seed)

    def init(words):
        t = jax.lax.broadcasted_iota(jnp.uint32, (T, R), 0)
        r = jax.lax.broadcasted_iota(jnp.uint32, (T, R), 1)
        key = jax.random.fold_in(
            jax.random.wrap_key_data(words, impl="threefry2x32"),
            _WEIGHT_STREAM)
        return {"tables": ref.rows_from_seed(words, t * jnp.uint32(R) + r, D),
                **ref.mlp_from_seed(key, cfg)}

    if len(devices) == 1:
        out = jax.sharding.SingleDeviceSharding(devices[0])
    else:
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = _mesh(devices)
        rep = NamedSharding(mesh, P())
        shapes = jax.eval_shape(init, words)
        out = {"tables": NamedSharding(mesh, P(None, "model", None)),
               "bottom": jax.tree.map(lambda _: rep, shapes["bottom"]),
               "top": jax.tree.map(lambda _: rep, shapes["top"])}
    return jax.jit(init, out_shardings=out)(jnp.asarray(words))


class System:
    """The engine under test with its weights, for one run.  A tiered
    store's counters start from ``warmup_freqs`` (per row) when given."""

    def __init__(self, seed: int, cfg: dict, devices, warmup_freqs=None):
        from repro.serving.engine import CTRRequest, make_dlrm_engine

        self.cfg, self.devices = ref.equal_tables(cfg), devices
        self.dlrm = dlrm_config(cfg, warmup_freqs)
        params = make_weights(seed, cfg, devices)
        # the reference's copy of the MLPs, taken before the engine runs
        self.mlp = jax.tree.map(np.asarray, {k: params[k]
                                             for k in ("bottom", "top")})
        ctx = None
        if len(devices) > 1:
            from repro.core.parallel import make_context

            ctx = make_context(_mesh(devices))
        if self.dlrm.cache.enabled:
            # the tiered store's cold tier is host memory: one copy of the
            # tables, taken from the device, whose copy is then dropped
            params = {**params, "tables": np.asarray(params["tables"])}
        self.request = CTRRequest
        self.engine = make_dlrm_engine(params, self.dlrm, cfg["batch_size"],
                                       ctx)
        self.pipelined = self.dlrm.cache.pipeline_depth > 1

    def drain(self) -> dict:
        """Score every queued request through the user's call: the
        pipeline's ``run_to_completion``, or ``flush`` until empty."""
        if self.pipelined:
            return self.engine.run_to_completion()
        out = {}
        while self.engine.queue:
            out.update(self.engine.flush())
        return out

    def cache_counters(self):
        """``(hits, lookups, prefetch + scatter seconds, batches)`` of the
        tiered store, or None without one."""
        stats = self.engine.cache_stats()
        if stats is None:
            return None
        return (stats.hits, stats.lookups, stats.prefetch_s + stats.scatter_s,
                stats.batches)

    def close(self) -> None:
        """Drop the engine and its weights, on the device and the host."""
        self.engine = None
