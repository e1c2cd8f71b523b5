"""DLRM — the paper's own model (Fig. 2 canonical architecture).

Criteo-Terabyte-like defaults: 13 dense features -> bottom MLP [512,256,128];
26 sparse features -> 26 embedding tables (dim 128); dot-product feature
interaction; top MLP [1024,1024,512,256,1]. Table sizes follow the paper's
benchmarking assumption (§4.3): one row count and one pooling factor for
every table, split evenly row-wise.

``rows_per_table`` and ``pooling`` may instead give one int per table
(DLRM-DCNv2 as MLPerf runs it: rows from 3 to 40M, multi-hot sizes from 1
to 100).  Such a config keeps its tables in one flat ``(sum R_t, D)``
array and its lookups packed, only each table's ``L_t`` slots per sample
(``repro.core.jagged.PackedBatch``); it is served on one device with the
tables in HBM (no row-wise split, no tiered store yet).

``interaction`` is ``"dot"`` (pairwise dot products, Naumov et al.) or
``"dcn"``: the DCN-v2 low-rank cross network of Wang et al.
(arXiv:2008.13535) over the concatenated bottom output and pooled
vectors, ``dcn_layers`` layers of rank ``dcn_rank``.

``CONFIG`` is the inference-benchmark scale used in §4.4/§5 (rows kept at
1M so CPU runs stay tractable; the Fig. 9 projection sweeps table_bytes
analytically); ``smoke()`` is the CPU test scale.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Tuple, Union

from repro.core.cache_config import CacheConfig, resolve_cache_aliases
from repro.core.embedding_bag import EmbeddingBagConfig


@dataclasses.dataclass(frozen=True)
class DLRMConfig:
    num_dense_features: int = 13
    num_sparse_features: int = 26        # == num embedding tables
    embedding_dim: int = 128             # paper fixes 128
    # one int for every table, or a tuple of one int per table
    rows_per_table: Union[int, Tuple[int, ...]] = 1_000_000
    pooling: Union[int, Tuple[int, ...]] = 32   # paper §5: pooling factor
    bottom_mlp: Tuple[int, ...] = (512, 256, 128)
    top_mlp: Tuple[int, ...] = (1024, 1024, 512, 256, 1)
    interaction: str = "dot"             # dot | dcn
    dcn_layers: int = 3                  # dcn: low-rank cross layers
    dcn_rank: int = 512                  # dcn: rank of each layer's V, W
    sharding: str = "row"                # paper's RW focus
    rw_impl: str = "allgather"           # allgather | a2a (paper-faithful)
    rw_backend: str = "bulk"             # bulk | onesided
    dtype: str = "float32"
    kernel_mode: str = "auto"            # auto | reference | pallas | interpret
    fused: bool = True                   # table-batched (TBE) kernel path
    # tiered frequency-aware cache + pipelined serving, all knobs in ONE
    # CacheConfig (repro.core.cache_config): slot-pool sizing (uniform
    # ``rows`` / per-table ``rows_per_table``), lfu|lru policy, cold tier
    # ("host" | "remote" + transport), warmup seeding, and pipeline_depth
    # (1 = serialized DLRMEngine; >= 2 selects PipelinedDLRMEngine via
    # make_dlrm_engine).  Always normalized to a CacheConfig instance
    # (never None) after construction.
    cache: Optional[CacheConfig] = None
    # DEPRECATED flat aliases of the CacheConfig fields above.  Passing
    # any of them warns DeprecationWarning and forwards the value into
    # ``cache``; after construction they read as None (their sentinel) —
    # read cfg.cache.* instead.  Removal noted in the README.
    cache_rows: Optional[int] = None
    cache_policy: Optional[str] = None
    cold_tier: Optional[str] = None
    remote_hosts: Optional[int] = None
    remote_backend: Optional[str] = None
    pipeline_depth: Optional[int] = None
    warmup_freqs: object = dataclasses.field(
        default=None, compare=False, repr=False)
    # planner -> engine round trip: a core.sharding_plan.ShardingPlan
    # whose per-table "cached" Placement.cache_rows size HETEROGENEOUS
    # slot pools (ONE flat (sum S_t, D) device pool; capacity and
    # eviction per table).  Placements map to tables by POSITION
    # (Placement.index), never by name — benchmark sweeps duplicate
    # names freely.  Tables the planner did not price as "cached" fall
    # back to the uniform cache.rows scalar (or the pooling floor when
    # cache.rows == 0).  Data, not architecture: excluded from config
    # equality/hash like warmup_freqs.
    sharding_plan: object = dataclasses.field(
        default=None, compare=False, repr=False)

    _CACHE_ALIASES = ("cache_rows", "cache_policy", "cold_tier",
                      "remote_hosts", "remote_backend", "pipeline_depth",
                      "warmup_freqs")

    def __post_init__(self):
        if self.interaction not in ("dot", "dcn"):
            raise ValueError(f"interaction must be 'dot' or 'dcn', got "
                             f"{self.interaction!r}")
        if self.bottom_mlp[-1] != self.embedding_dim:
            raise ValueError(
                f"{self.interaction} interaction needs bottom_mlp[-1] "
                f"({self.bottom_mlp[-1]}) == embedding_dim "
                f"({self.embedding_dim})")
        if self.interaction == "dcn" and min(self.dcn_layers,
                                             self.dcn_rank) < 1:
            raise ValueError(f"dcn needs dcn_layers and dcn_rank >= 1, got "
                             f"{self.dcn_layers}, {self.dcn_rank}")
        for key in ("rows_per_table", "pooling"):
            v = getattr(self, key)
            if isinstance(v, int):
                continue
            v = tuple(int(x) for x in v)
            if len(v) != self.num_sparse_features or min(v) < 1:
                raise ValueError(
                    f"{key} must be an int or {self.num_sparse_features} "
                    f"positive ints, one per table; got {v}")
            object.__setattr__(self, key, v)
        cc = resolve_cache_aliases(self, self._CACHE_ALIASES)
        object.__setattr__(self, "cache", cc)
        for alias in self._CACHE_ALIASES:
            object.__setattr__(self, alias, None)

    def __hash__(self):
        # the cross net's fields join the hash only under "dcn", so a dot
        # config hashes as it did before they existed
        skip = () if self.interaction == "dcn" else ("dcn_layers",
                                                     "dcn_rank")
        return hash(tuple(getattr(self, f.name)
                          for f in dataclasses.fields(self)
                          if f.compare and f.name not in skip))

    @property
    def per_table(self) -> bool:
        """Rows or pooling given per table: flat tables, packed lookups."""
        return not (isinstance(self.rows_per_table, int)
                    and isinstance(self.pooling, int))

    def table_rows(self) -> Tuple[int, ...]:
        """Each table's row count ``R_t``."""
        return _per_table(self.rows_per_table, self.num_sparse_features)

    def table_pooling(self) -> Tuple[int, ...]:
        """Each table's pooling factor ``L_t``."""
        return _per_table(self.pooling, self.num_sparse_features)

    def cache_rows_vector(self):
        """Per-table slot counts the tiered store should use, or None
        when no plan is attached (uniform ``cache.rows`` path)."""
        if self.sharding_plan is None:
            return None
        fallback = self.cache.rows if self.cache.rows > 0 else self.pooling
        return tuple(self.sharding_plan.cache_rows_vector(
            self.num_sparse_features, default=fallback))

    def embedding_config(self) -> EmbeddingBagConfig:
        cache = self.cache
        slots = self.cache_rows_vector()
        if slots is not None:
            cache = dataclasses.replace(cache, rows_per_table=slots)
        return EmbeddingBagConfig(
            num_tables=self.num_sparse_features,
            rows_per_table=(self.table_rows() if self.per_table
                            else self.rows_per_table),
            pooling=self.table_pooling() if self.per_table else None,
            dim=self.embedding_dim,
            sharding=self.sharding,
            rw_impl=self.rw_impl,
            rw_backend=self.rw_backend,
            dtype=self.dtype,
            kernel_mode=self.kernel_mode,
            fused=self.fused,
            cache=cache,
        )

    @property
    def interaction_dim(self) -> int:
        """Output width of the feature-interaction layer."""
        n = self.num_sparse_features + 1          # + bottom-MLP vector
        if self.interaction == "dot":
            return self.bottom_mlp[-1] + n * (n - 1) // 2
        return n * self.embedding_dim             # dcn: the cross net's x0


def _per_table(v, num_tables: int) -> Tuple[int, ...]:
    return (v,) * num_tables if isinstance(v, int) else v


CONFIG = DLRMConfig()


def smoke() -> DLRMConfig:
    return DLRMConfig(
        num_dense_features=4,
        num_sparse_features=8,
        embedding_dim=16,
        rows_per_table=128,
        pooling=4,
        bottom_mlp=(32, 16),
        top_mlp=(64, 32, 1),
    )


# DLRM-DCNv2 as MLPerf runs it (Training recommendation_v2/torchrec_dlrm,
# Inference dlrm_v2; Criteo 1TB multi-hot): each table's rows, capped at
# 40M, and its fixed multi-hot size (214 lookups per sample)
DLRMV2_ROWS = (40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63,
               40000000, 3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14,
               40000000, 40000000, 40000000, 590152, 12973, 108, 36)
DLRMV2_POOLING = (3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
                  100, 27, 10, 3, 1, 1)


def dlrmv2(rows_cap: int = 40_000_000) -> DLRMConfig:
    """DLRM-DCNv2 at MLPerf's widths (bottom MLP 13-512-256-128, 3 cross
    layers of rank 512 over 27 x 128 inputs, top MLP 3456-1024-1024-512-
    256-1), every table's rows capped at ``rows_cap``: the five 40M-row
    tables take 104.5 GB in f32, more than one chip holds."""
    return DLRMConfig(
        rows_per_table=tuple(min(r, rows_cap) for r in DLRMV2_ROWS),
        pooling=DLRMV2_POOLING,
        interaction="dcn",
        dcn_layers=3,
        dcn_rank=512,
    )
