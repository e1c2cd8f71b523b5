"""DLRM-DCNv2 (MLPerf's DLRMv2) under test, built from a benchmark
configuration.

A configuration file holds the ``repro.configs.dlrm.DLRMConfig`` fields
this model reads at its top level, ``rows_per_table`` and ``pooling`` a
list of one int per table, ``interaction`` "dcn" with ``dcn_layers`` and
``dcn_rank``; besides them the engine's micro-batch ``batch_size``, the
``matmul_precision`` it is served at, ``chips`` (one: the tables live in
that chip's HBM), an empty ``cache``, and the ``source``, ``reduced`` and
``assumed`` of the deployment it stands for.

The weights are the benchmark's own (``dlrm_dcnv2_reference``): the flat
``(sum R_t, D)`` table, the MLPs and the cross layers drawn on the device
in one program.  The engine is ``make_dlrm_engine``, the entry a user
calls, with ``submit`` and ``flush``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench.models import dlrm_dcnv2_reference as ref
from bench.traffic import per_table

# the packed multi-hot TBE kernel as it appears on the device trace
TBE_KERNEL = r"gather_pool_tbe_multihot_pallas"

_FIELDS = ("num_dense_features", "num_sparse_features", "embedding_dim",
           "rows_per_table", "pooling", "bottom_mlp", "top_mlp",
           "interaction", "dcn_layers", "dcn_rank", "dtype", "kernel_mode")
_WEIGHT_STREAM = 1   # fold_in tag of the dense weights


def dlrm_config(cfg: dict):
    from repro.configs.dlrm import DLRMConfig

    return DLRMConfig(**{k: tuple(cfg[k]) if isinstance(cfg[k], list)
                         else cfg[k] for k in _FIELDS})


def make_weights(seed: int, cfg: dict, device):
    """The flat table (row ``offset_t + r`` of table ``t``'s row ``r``),
    the MLPs and the cross layers, drawn from ``seed`` in one jitted
    program in float32 on ``device``."""
    rows = int(per_table(cfg, "rows_per_table").sum())
    words = ref.seed_words(seed)

    def init(words):
        key = jax.random.fold_in(
            jax.random.wrap_key_data(words, impl="threefry2x32"),
            _WEIGHT_STREAM)
        flat = jax.lax.iota(jnp.uint32, rows)
        return {"tables": ref.rows_from_seed(words, flat,
                                             cfg["embedding_dim"]),
                **ref.weights_from_seed(key, cfg)}

    return jax.jit(init, out_shardings=jax.sharding.SingleDeviceSharding(
        device))(jnp.asarray(words))


class System:
    """The engine under test with its weights, for one run: one chip,
    every table in its HBM."""

    def __init__(self, seed: int, cfg: dict, devices, warmup_freqs=None):
        from repro.serving.engine import CTRRequest, make_dlrm_engine

        if len(devices) != 1 or cfg.get("cache"):
            raise ValueError("dlrm_dcnv2 serves from one chip with its "
                             "tables in HBM: chips 1 and an empty cache")
        self.cfg, self.devices = cfg, devices
        self.dlrm = dlrm_config(cfg)
        params = make_weights(seed, cfg, devices[0])
        # the reference's copy of the dense weights, taken before serving
        self.mlp = jax.tree.map(np.asarray, {k: params[k]
                                             for k in ("bottom", "top",
                                                       "cross")})
        self.request = CTRRequest
        self.engine = make_dlrm_engine(params, self.dlrm, cfg["batch_size"])

    def drain(self) -> dict:
        """Score every queued request through ``flush`` until empty."""
        out = {}
        while self.engine.queue:
            out.update(self.engine.flush())
        return out

    def cache_counters(self):
        return None

    def close(self) -> None:
        """Drop the engine and its weights, so the check has the chip."""
        self.engine = None
