"""DistributedEmbeddingBag — the paper's contribution as a composable module.

Implements the row-wise-parallel (RW) embedding bag of §4.2 as a three-phase
pipeline inside ``shard_map``, plus the column-wise (CW), table-wise (TW)
and replicated (DP) strategies of §4.1, all behind one config.

Two RW implementations are provided:

  * ``rw_impl="a2a"`` — the PAPER-FAITHFUL pipeline:
      phase 1  index permute: bucket every lookup id by owner shard
               (``dest = id // rows_per_shard``) and ``all_to_all`` the
               fixed-capacity buckets (the paper's "permute kernel"),
      phase 2  local gather + segment-sum pooling on the owner,
      phase 3  ``reduce_scatter`` of partial pooled vectors back to the
               requesting rank (optionally emulated as all-to-all + local
               sum, exactly like the paper's NVSHMEM 2.9 workaround).
    Fixed-shape buckets require a capacity factor; overflow lookups are
    dropped and counted (standard TPU practice, same as MoE capacity).

  * ``rw_impl="allgather"`` — the TPU-NATIVE variant (beyond-paper
    optimization, exact): replicate the (small) index payload with an
    all-gather... in our 2-D mesh the batch is already replicated along the
    model axis, so phase 1 costs ZERO bytes; every shard pools the rows it
    owns (out-of-shard ids masked to weight 0 — one kernel serves both
    paths), and phase 3 is a single reduce-scatter/psum. Index traffic is
    traded for (E-1)/E wasted gather *lookups* which are masked, not
    fetched, by the scalar-prefetch kernel.

The mesh contract: this module is called INSIDE ``shard_map`` with the
batch sharded over the data axes and REPLICATED over ``model_axis``; tables
are sharded over ``model_axis`` according to ``cfg.sharding``.

Kernel execution is table-batched by default (``cfg.fused``): each shard
issues ONE fused TBE ``pallas_call`` covering all of its tables
(kernels/embedding_gather.py) instead of T single-table launches —
the paper's #tables sweep (§5) is a launch-count sweep under the unfused
baseline and flat under TBE.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from jax.lax import axis_size

from repro.core import comm
from repro.core.cache_config import CacheConfig, resolve_cache_aliases
from repro.core.jagged import JaggedBatch, PackedBatch
from repro.kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class EmbeddingBagConfig:
    num_tables: int
    # one row count for every table, stacked (T, R, D); or a tuple of one
    # per table, kept as one flat (sum R_t, D) array, table t's rows from
    # row_offsets()[t]
    rows_per_table: Union[int, Tuple[int, ...]]
    dim: int
    combiner: str = "sum"            # sum | mean
    dtype: str = "float32"
    sharding: str = "row"            # row | column | table | replicated
    rw_impl: str = "allgather"       # allgather | a2a (paper-faithful)
    rw_backend: str = "bulk"         # bulk (NCCL-analogue) | onesided (NVSHMEM)
    capacity_factor: float = 2.0     # a2a bucket capacity multiplier
    emulate_rs_with_a2a: bool = False  # paper's NVSHMEM reduce-scatter workaround
    kernel_mode: str = "auto"        # auto | reference | pallas | interpret
    # fused: run the table-batched (TBE) kernel — ONE pallas_call for all T
    # tables per shard. False calls the single-table kernel per table (T
    # launches); the A/B baseline tests/test_tbe.py checks the fused one
    # against.
    fused: bool = True
    # pooling: each table's fixed multi-hot size L_t, for lookups packed
    # as ``PackedBatch`` (only each table's L_t slots per sample); None
    # for the padded (T, B, L) ``JaggedBatch`` layout
    pooling: Optional[Tuple[int, ...]] = None
    # --- beyond-paper levers (benchmarks/beyond_paper.py) ---
    # rs_dtype: cast partial pooled vectors to this dtype before the
    # phase-3 reduce-scatter/all-reduce — halves output traffic at bf16
    # (bounded error: one rounding per shard contribution).
    rs_dtype: str = "float32"        # float32 | bfloat16
    # hot_rows: rows [0, hot_rows) are treated as replicated-hot (zipf
    # traffic: low ids = hottest). Their lookups are served from a local
    # replica and are EXCLUDED from the a2a/reduce-scatter pipeline —
    # see pooled_lookup_hot.
    hot_rows: int = 0
    # --- tiered frequency-aware cache (repro/cache/) ---
    # cache: ALL cache-serving knobs in one CacheConfig — slot pool sizing
    # (uniform rows / per-table rows_per_table), LFU/LRU policy, cold tier
    # and remote transport, warmup seeding.  Unlike the static hot_rows
    # split, residency is DYNAMIC: an id->slot indirection table plus
    # LFU/LRU admission-eviction driven by batch frequency counters — see
    # pooled_lookup_cached / repro.cache.  Always normalized to a
    # CacheConfig instance (never None) after construction.
    cache: Optional[CacheConfig] = None
    # DEPRECATED flat aliases of the CacheConfig fields above.  Passing
    # any of them warns DeprecationWarning and forwards the value into
    # ``cache``; after construction they read as None (their sentinel) —
    # read cfg.cache.* instead.  Removal noted in the README.
    cache_rows: Optional[int] = None
    cache_policy: Optional[str] = None
    cache_rows_per_table: Optional[Tuple[int, ...]] = None
    cold_tier: Optional[str] = None
    remote_hosts: Optional[int] = None
    remote_backend: Optional[str] = None
    warmup_freqs: Optional[object] = dataclasses.field(
        default=None, compare=False, repr=False)

    _CACHE_ALIASES = ("cache_rows", "cache_policy", "cache_rows_per_table",
                      "cold_tier", "remote_hosts", "remote_backend",
                      "warmup_freqs")

    def __post_init__(self):
        cc = resolve_cache_aliases(self, self._CACHE_ALIASES)
        object.__setattr__(self, "cache", cc)
        for alias in self._CACHE_ALIASES:
            object.__setattr__(self, alias, None)

    @property
    def per_table(self) -> bool:
        """Rows given per table: one flat ``(sum R_t, D)`` array."""
        return not isinstance(self.rows_per_table, int)

    @property
    def num_rows(self) -> int:
        """Rows of all tables together."""
        if self.per_table:
            return sum(self.rows_per_table)
        return self.num_tables * self.rows_per_table

    def row_offsets(self) -> np.ndarray:
        """``(T,)`` int64: the first row of each table in the flat array,
        the rows of tables ``0 .. t - 1`` before table ``t``."""
        rows = np.broadcast_to(np.asarray(self.rows_per_table, np.int64),
                               (self.num_tables,))
        return np.cumsum(rows) - rows

    @property
    def table_bytes(self) -> int:
        return self.num_rows * self.dim * jnp.dtype(self.dtype).itemsize


# ---------------------------------------------------------------------------
# Parameters
# ---------------------------------------------------------------------------

def init_tables(rng: jax.Array, cfg: EmbeddingBagConfig) -> jax.Array:
    """(T, R, D) stacked tables, or the flat (sum R_t, D) array of tables
    whose rows differ; scale 1/sqrt(D) like TorchRec defaults."""
    shape = ((cfg.num_rows, cfg.dim) if cfg.per_table
             else (cfg.num_tables, cfg.rows_per_table, cfg.dim))
    return _normal_tables(rng, shape, jnp.dtype(cfg.dtype))


@partial(jax.jit, static_argnums=(1, 2))
def _normal_tables(rng, shape, dtype):
    # one jitted program: the draw, the scale and the cast fuse into the
    # output buffer, so CONFIG-scale tables (13.3 GB) never need a second
    # full-size temporary on the device
    return (jax.random.normal(rng, shape, jnp.float32)
            * shape[-1] ** -0.5).astype(dtype)


def _equal_tables(cfg: EmbeddingBagConfig) -> None:
    """Refuse tables whose rows differ on the sharded paths."""
    if cfg.per_table:
        raise NotImplementedError(
            f"sharding {cfg.sharding!r} of per-table rows_per_table: the "
            f"row-wise split of uneven tables is not implemented (ROADMAP "
            f"Reach 1); such tables are served from one device")


def table_pspec(cfg: EmbeddingBagConfig, model_axis: str = "model"):
    """PartitionSpec for the stacked (T, R, D) table under cfg.sharding."""
    from jax.sharding import PartitionSpec as P

    _equal_tables(cfg)
    return {
        "row": P(None, model_axis, None),
        "column": P(None, None, model_axis),
        "table": P(model_axis, None, None),
        "replicated": P(None, None, None),
    }[cfg.sharding]


# ---------------------------------------------------------------------------
# Local (single-device / fully-replicated) path — the oracle
# ---------------------------------------------------------------------------

def pooled_lookup_local(
    tables: jax.Array, batch: Union[JaggedBatch, PackedBatch],
    cfg: EmbeddingBagConfig
) -> jax.Array:
    """Tables x JaggedBatch (or PackedBatch) -> (B, T, D), no
    communication.

    ``tables`` is either the full stacked ``(T, R, D)`` array (ids are
    row ids), or a FLAT ``(N, D)`` array: the tables whose rows differ
    (``cfg.per_table``; offsets ``cfg.row_offsets()``), or the tiered
    cache's ``(sum S_t, D)`` slot pool (ids are pool-slot ids out of
    ``CachedEmbeddingBag.prefetch``; offsets from ``cfg.cache``, the SAME
    geometry the SlotPoolManager sized the pool with, so the two can
    never disagree).  A ``PackedBatch`` (multi-hot sizes ``cfg.pooling``)
    reads the flat tables through the packed multi-hot kernel.

    All T tables go through ONE table-batched kernel call when
    ``cfg.fused`` (the default); ``fused=False`` restores the per-table
    baseline (3-D tables only — a ragged flat pool is always fused).
    """
    if isinstance(batch, PackedBatch):
        out = kops.embedding_bag_packed(
            tables,
            jnp.asarray(cfg.row_offsets(), jnp.int32),
            batch.ids,
            batch.lengths,
            cfg.pooling,
            mode=cfg.kernel_mode,
        )                                                    # (T, B, D)
        return out.transpose(1, 0, 2)
    if tables.ndim == 2:
        offsets = (cfg.cache.slot_offsets(
            cfg.num_tables, cfg.rows_per_table)[:-1] if cfg.cache.enabled
            else cfg.row_offsets())
        out = kops.embedding_bag_batched_flat(
            tables,
            jnp.asarray(offsets, jnp.int32),
            batch.indices,
            batch.lengths,
            batch.weights,
            combiner=cfg.combiner,
            mode=cfg.kernel_mode,
        )                                                    # (T, B, D)
        return out.transpose(1, 0, 2)
    out = kops.embedding_bag_batched(
        tables,
        batch.indices,
        batch.lengths,
        batch.weights,
        combiner=cfg.combiner,
        mode=cfg.kernel_mode,
        fused=cfg.fused,
    )                                                        # (T, B, D)
    return out.transpose(1, 0, 2)


def _comm(cfg: EmbeddingBagConfig) -> dict:
    """Collective options of the RW pipeline: the configured backend,
    and for the one-sided backend whether its kernels run interpreted
    (they follow ``cfg.kernel_mode``)."""
    return dict(backend=cfg.rw_backend,
                interpret=cfg.rw_backend == "onesided"
                and kops.onesided_interpret(cfg.kernel_mode))


# ---------------------------------------------------------------------------
# Row-wise parallel: allgather variant (TPU-native, exact)
# ---------------------------------------------------------------------------

def _rw_allgather(
    table_shard: jax.Array,    # (T, R/E, D)
    batch: JaggedBatch,        # replicated along model_axis
    cfg: EmbeddingBagConfig,
    model_axis: str,
    scatter_batch: bool,
) -> jax.Array:
    E = axis_size(model_axis)
    rank = jax.lax.axis_index(model_axis)
    rows_per_shard = cfg.rows_per_table // E
    offset = rank * rows_per_shard

    # one fused TBE call pools every table's owned rows on this shard
    partial_out = kops.embedding_bag_rw_partial_batched(
        table_shard,
        offset,
        batch.indices,
        batch.lengths,
        batch.weights,
        mode=cfg.kernel_mode,
        fused=cfg.fused,
    ).transpose(1, 0, 2)                                     # (B, T, D)

    out_dtype = partial_out.dtype
    if cfg.rs_dtype != "float32":
        partial_out = partial_out.astype(cfg.rs_dtype)
    if scatter_batch:
        # Phase 3 as a true reduce-scatter over the batch dim: rank r ends
        # with the pooled rows for its 1/E batch subslice (sequence-parallel
        # style — the paper's "send back to the requesting GPU").
        B = partial_out.shape[0]
        stacked = partial_out.reshape(E, B // E, *partial_out.shape[1:])
        return comm.reduce_scatter(
            stacked,
            model_axis,
            scatter_axis=0,
            emulate_with_a2a=cfg.emulate_rs_with_a2a,
            **_comm(cfg),
        ).astype(out_dtype)
    return comm.all_reduce(partial_out, model_axis,
                           **_comm(cfg)).astype(out_dtype)


# ---------------------------------------------------------------------------
# Row-wise parallel: a2a variant (paper-faithful §4.2/§4.3)
# ---------------------------------------------------------------------------

def _bucket_by_owner(
    flat_idx: jax.Array,       # (N,) global row ids
    flat_w: jax.Array,         # (N,) effective weights (0 = masked)
    flat_seg: jax.Array,       # (N,) output segment id (b*T + t)
    num_shards: int,
    capacity: int,
    rows_per_shard: int,
):
    """Phase-1 bucketing: fixed-capacity per-destination send buffers.

    Returns (send_idx, send_w, send_seg, dropped) with shapes (E, C).
    Overflow beyond capacity is dropped (weight forced to 0) and counted.
    """
    N = flat_idx.shape[0]
    dest = jnp.clip(flat_idx // rows_per_shard, 0, num_shards - 1)
    # stable within-destination position via cumulative one-hot counts
    onehot = jax.nn.one_hot(dest, num_shards, dtype=jnp.int32)        # (N, E)
    pos = jnp.take_along_axis(
        jnp.cumsum(onehot, axis=0) - 1, dest[:, None], axis=1
    )[:, 0]                                                            # (N,)
    live = flat_w != 0.0
    keep = live & (pos < capacity)
    dropped = jnp.sum(live & (pos >= capacity))
    slot = jnp.where(keep, dest * capacity + pos, num_shards * capacity)
    size = num_shards * capacity
    send_idx = jnp.zeros((size + 1,), flat_idx.dtype).at[slot].set(
        flat_idx, mode="drop"
    )[:size]
    send_w = jnp.zeros((size + 1,), flat_w.dtype).at[slot].set(
        flat_w, mode="drop"
    )[:size]
    send_seg = jnp.full((size + 1,), -1, flat_seg.dtype).at[slot].set(
        flat_seg, mode="drop"
    )[:size]
    return (
        send_idx.reshape(num_shards, capacity),
        send_w.reshape(num_shards, capacity),
        send_seg.reshape(num_shards, capacity),
        dropped,
    )


def _rw_a2a(
    table_shard: jax.Array,    # (T, R/E, D)
    batch: JaggedBatch,
    cfg: EmbeddingBagConfig,
    model_axis: str,
) -> Tuple[jax.Array, jax.Array]:
    """Paper-faithful RW pipeline. Returns ((B, T, D) pooled, dropped count).

    Each rank processes only its own 1/E slice of the (model-axis
    replicated) batch — matching the paper's setup where every GPU owns a
    distinct mini-batch — then phases 1-3 reassemble full pooled outputs
    for that slice; a final all-gather restores model-axis replication.
    """
    E = axis_size(model_axis)
    rank = jax.lax.axis_index(model_axis)
    rows_per_shard = cfg.rows_per_table // E
    T = cfg.num_tables
    B = batch.indices.shape[1]
    Bl = B // E
    L = batch.max_pooling

    # This rank's mini-batch slice (the paper's per-GPU batch).
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, rank * Bl, Bl, axis=1)
    idx = sl(batch.indices)                                   # (T, Bl, L)
    eff_w = sl(batch.effective_weights())                     # (T, Bl, L)

    # segment id = b * T + t for pooled-output scatter
    seg = (
        jnp.arange(Bl)[None, :, None] * T + jnp.arange(T)[:, None, None]
    ) * jnp.ones((1, 1, L), jnp.int32)
    flat_idx = idx.transpose(1, 0, 2).reshape(-1)             # (Bl*T*L,)
    flat_w = eff_w.transpose(1, 0, 2).reshape(-1)
    flat_seg = seg.transpose(1, 0, 2).reshape(-1).astype(jnp.int32)
    # global table offset folded into the id so one shard array serves all
    # tables: shard-local address = (t, id % rows_per_shard)
    flat_tab = (
        (jnp.arange(T)[:, None, None] * jnp.ones((1, Bl, L), jnp.int32))
        .transpose(1, 0, 2)
        .reshape(-1)
    )

    N = Bl * T * L
    capacity = max(1, int(N / E * cfg.capacity_factor))
    capacity = min(capacity, N)

    # ---- phase 1: index permute (all-to-all) -------------------------------
    packed = flat_idx * T + flat_tab          # pack (row, table) into one id
    send_p, send_w, send_seg, dropped = _bucket_by_owner(
        packed, flat_w, flat_seg, E, capacity,
        rows_per_shard * T,  # packed ids of one shard span rows_per_shard*T
    )
    recv_p = comm.all_to_all(send_p, model_axis, **_comm(cfg))
    recv_w = comm.all_to_all(send_w, model_axis, **_comm(cfg))
    recv_seg = comm.all_to_all(send_seg, model_axis, **_comm(cfg))

    # ---- phase 2: local gather + pool (segment-sum) ------------------------
    recv_row = recv_p // T - rank * rows_per_shard            # local row id
    recv_tab = recv_p % T
    valid = (recv_w != 0.0) & (recv_row >= 0) & (recv_row < rows_per_shard)
    safe_row = jnp.where(valid, recv_row, 0)
    safe_tab = jnp.where(valid, recv_tab, 0)
    # gather in the flattened (T * rows_per_shard, D) row space — the same
    # address math as the fused TBE kernel (one gather, not a 2-D index)
    flat_addr = (safe_tab * rows_per_shard + safe_row).reshape(-1)
    rows = table_shard.reshape(-1, table_shard.shape[-1])[flat_addr]  # (E*C, D)
    contrib = rows.astype(jnp.float32) * (
        recv_w.reshape(-1) * valid.reshape(-1).astype(jnp.float32)
    )[:, None]
    seg_ids = jnp.where(valid, recv_seg, Bl * T).reshape(-1)
    # partials grouped by origin rank: (E, Bl*T, D)
    origin = (
        jnp.arange(E)[:, None] * jnp.ones((1, capacity), jnp.int32)
    ).reshape(-1)
    partial = jax.ops.segment_sum(
        contrib,
        origin * (Bl * T + 1) + seg_ids,
        num_segments=E * (Bl * T + 1),
    ).reshape(E, Bl * T + 1, -1)[:, : Bl * T, :]

    # ---- phase 3: reduce-scatter back to the requesting rank ---------------
    if cfg.rs_dtype != "float32":
        partial = partial.astype(cfg.rs_dtype)
    pooled = comm.reduce_scatter(
        partial,
        model_axis,
        scatter_axis=0,
        emulate_with_a2a=cfg.emulate_rs_with_a2a,
        **_comm(cfg),
    ).astype(jnp.float32)                                      # (Bl*T, D)
    pooled = pooled.reshape(Bl, T, -1).astype(table_shard.dtype)

    if cfg.combiner == "mean":
        denom = jnp.maximum(
            eff_w.sum(axis=2).transpose(1, 0)[:, :, None], 1.0
        )
        pooled = pooled / denom

    # restore model-axis replication of the full batch (tiled all-gather)
    out = comm.all_gather(
        pooled, model_axis, axis=0, tiled=True, **_comm(cfg)
    )                                                          # (B, T, D)
    return out, dropped


# ---------------------------------------------------------------------------
# Column-wise / table-wise / replicated
# ---------------------------------------------------------------------------

def _cw(table_shard, batch, cfg, model_axis, keep_sharded):
    # shard: (T, R, D/E); batch replicated -> local pool of a column slice
    out = pooled_lookup_local(table_shard, batch, cfg)        # (B, T, D/E)
    if keep_sharded:
        return out
    return comm.all_gather(out, model_axis, axis=2, tiled=True)


def _tw(table_shard, batch, cfg, model_axis, keep_sharded):
    # shard: (T/E, R, D); batch replicated -> pool owned tables only
    E = axis_size(model_axis)
    rank = jax.lax.axis_index(model_axis)
    Tl = cfg.num_tables // E
    sl = lambda x: jax.lax.dynamic_slice_in_dim(x, rank * Tl, Tl, axis=0)
    local_batch = JaggedBatch(
        sl(batch.indices),
        sl(batch.lengths),
        None if batch.weights is None else sl(batch.weights),
    )
    sub_cfg = dataclasses.replace(cfg, num_tables=Tl)
    out = pooled_lookup_local(table_shard, local_batch, sub_cfg)  # (B, T/E, D)
    if keep_sharded:
        return out
    return comm.all_gather(out, model_axis, axis=1, tiled=True)


# ---------------------------------------------------------------------------
# Public sharded entry point (call inside shard_map)
# ---------------------------------------------------------------------------

def pooled_lookup_sharded(
    table_shard: jax.Array,
    batch: JaggedBatch,
    cfg: EmbeddingBagConfig,
    *,
    model_axis: str = "model",
    scatter_batch: bool = False,
    keep_sharded: bool = False,
) -> jax.Array:
    """Distributed pooled lookup. Dispatches on ``cfg.sharding``.

    Returns (B, T, D) pooled embeddings (or the sharded variant when
    ``scatter_batch``/``keep_sharded`` is set — see each strategy).
    """
    if cfg.sharding == "replicated":
        return pooled_lookup_local(table_shard, batch, cfg)
    _equal_tables(cfg)
    if cfg.sharding == "row":
        if cfg.rw_impl == "a2a":
            out, _ = _rw_a2a(table_shard, batch, cfg, model_axis)
            return out
        return _rw_allgather(table_shard, batch, cfg, model_axis, scatter_batch)
    if cfg.sharding == "column":
        return _cw(table_shard, batch, cfg, model_axis, keep_sharded)
    if cfg.sharding == "table":
        return _tw(table_shard, batch, cfg, model_axis, keep_sharded)
    raise ValueError(f"unknown sharding {cfg.sharding!r}")


def pooled_lookup_rw_a2a_with_stats(
    table_shard, batch, cfg, *, model_axis: str = "model"
):
    """Paper-faithful RW pipeline, also returning the dropped-lookup count."""
    return _rw_a2a(table_shard, batch, cfg, model_axis)


# ---------------------------------------------------------------------------
# Beyond-paper: hot-row replication (zipf-aware traffic elision)
# ---------------------------------------------------------------------------

def extract_hot_table(tables: jax.Array, cfg: EmbeddingBagConfig) -> jax.Array:
    """(T, R, D) full tables -> (T, hot_rows, D) replica of the hot rows.

    CTR traffic is zipfian; with ids ordered by popularity the first
    ``hot_rows`` rows absorb most lookups (e.g. zipf a=1.2: the top 1% of
    rows take ~75% of lookups). Serving deployments materialize this
    replica once at model-load time (FlexShard/RecShard-style).
    """
    return tables[:, : cfg.hot_rows]


def pooled_lookup_hot(
    table_shard: jax.Array,     # row-sharded (T, R/E, D)
    hot_table: jax.Array,       # replicated (T, hot_rows, D)
    batch: JaggedBatch,
    cfg: EmbeddingBagConfig,
    *,
    model_axis: str = "model",
) -> jax.Array:
    """RW pooled lookup with replicated-hot short-circuit.

    Lookups with id < cfg.hot_rows are served from the local replica and
    carry ZERO weight into the distributed pipeline — under the a2a impl
    they never enter the send buckets (``_bucket_by_owner`` drops
    weightless slots), so phase-1 traffic shrinks by the hot-hit rate.
    Exact: hot + cold partitions sum to the plain pooled lookup.

    Combiners: both partitions are pooled with ``sum`` (partition sums are
    additive, per-partition means are not); ``mean`` divides the combined
    sum by the full batch's denominators, matching the oracle exactly.
    """
    if cfg.combiner not in ("sum", "mean"):
        raise NotImplementedError(
            f"pooled_lookup_hot: combiner {cfg.combiner!r} "
            f"(EmbeddingBagConfig.combiner) is not supported — the hot/cold "
            f"split needs an additive pooling to recombine partitions")
    sum_cfg = dataclasses.replace(cfg, combiner="sum")
    hot = cfg.hot_rows
    eff = batch.effective_weights()                          # (T, B, L)
    is_hot = (batch.indices < hot).astype(jnp.float32)
    w_hot = eff * is_hot
    w_cold = eff * (1.0 - is_hot)

    safe = jnp.clip(batch.indices, 0, hot - 1)
    hot_out = kops.embedding_bag_batched(
        hot_table, safe, None, w_hot, mode=cfg.kernel_mode, fused=cfg.fused
    ).transpose(1, 0, 2)                                      # (B, T, D)

    cold_batch = JaggedBatch(batch.indices, batch.lengths, w_cold)
    cold_out = pooled_lookup_sharded(table_shard, cold_batch, sum_cfg,
                                     model_axis=model_axis)
    out = hot_out.astype(jnp.float32) + cold_out.astype(jnp.float32)
    if cfg.combiner == "mean":
        denom = jnp.maximum(eff.sum(axis=2), 1.0)             # (T, B)
        out = out / denom.transpose(1, 0)[:, :, None]
    return out.astype(table_shard.dtype)


# ---------------------------------------------------------------------------
# Tiered frequency-aware cache serving path (repro/cache/)
# ---------------------------------------------------------------------------

def make_cache(tables, cfg: EmbeddingBagConfig):
    """Build the dynamic tiered cache for ``cfg`` (``cfg.cache.enabled``).

    The returned :class:`repro.cache.CachedEmbeddingBag` serves lookups
    from a flat HBM slot pool sized by ``cfg.cache`` (uniform ``rows`` or
    heterogeneous ``rows_per_table``) over the cold tier it names — the
    full ``tables`` in local host memory, or row-shards on
    ``cache.remote_hosts`` peer ranks fetched through ``comm.fetch_rows``
    — the dynamic successor of the static ``hot_rows`` replica split
    above.  All cache knobs travel inside the one ``CacheConfig``; no
    per-knob kwarg plumbing.
    """
    from repro.cache import CachedEmbeddingBag   # deferred: cache -> core

    if cfg.per_table:
        raise NotImplementedError(
            "the tiered store over tables whose rows differ needs "
            "per-table tiered metadata, not implemented (ROADMAP Reach 1)")
    return CachedEmbeddingBag(tables, cfg)


def pooled_lookup_cached(cache, batch: JaggedBatch) -> jax.Array:
    """(cache, JaggedBatch) -> (B, T, D): prefetch misses, then ONE fused
    TBE launch over the slot pool.  Drop-in for ``pooled_lookup_local``
    when the cold tiers live off-device; exact (bitwise) once prefetched.
    """
    return cache.lookup(batch)


# ---------------------------------------------------------------------------
# Kernel contracts (audited by repro.analysis)
# ---------------------------------------------------------------------------

from repro.analysis.contracts import KernelContract  # noqa: E402

KERNEL_CONTRACTS = {
    "pooled_lookup_local": KernelContract(
        name="core.embedding_bag.pooled_lookup_local",
        note="replicated-table lookup (2-D flat pool or 3-D stacked) "
             "stays ONE fused TBE launch regardless of layout"),
}
