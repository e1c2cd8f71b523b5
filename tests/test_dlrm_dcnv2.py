"""DLRM-DCNv2: tables whose rows and multi-hot sizes differ, the packed
multi-hot TBE kernel (interpret mode), the low-rank cross network, and
the serving engine over them, each against a plain ``jax.numpy`` model."""
import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cache import CacheConfig
from repro.configs import dlrm as dlrm_cfg
from repro.configs.dlrm import CONFIG, DLRMConfig
from repro.core import embedding_bag as eb
from repro.core.jagged import PackedBatch, packed_slots
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.embedding_gather import (gather_pool_tbe_flat_pallas,
                                            gather_pool_tbe_multihot_pallas)
from repro.models import dlrm as dlrm_mod
from repro.serving.engine import CTRRequest, make_dlrm_engine

HI = jax.lax.Precision.HIGHEST

# DLRMv2's structure at a size the Pallas interpreter runs in seconds
ROWS, POOLING = (300, 3, 1000, 40), (3, 1, 12, 2)
TINY = DLRMConfig(num_dense_features=4, num_sparse_features=4,
                  embedding_dim=16, rows_per_table=ROWS, pooling=POOLING,
                  bottom_mlp=(32, 16), top_mlp=(64, 32, 1),
                  interaction="dcn", dcn_layers=3, dcn_rank=8,
                  kernel_mode="interpret")


def _offsets(rows):
    return np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int32)


def _packed_ids(rng, rows, pooling, B):
    return np.concatenate([rng.integers(0, r, (B, L))
                           for r, L in zip(rows, pooling)], 1).astype(np.int32)


def _gather_sum(flat, rows, pooling, ids, lengths):
    """The plain pooled lookup: each table's live slots, gathered and
    summed one by one in float64."""
    off = _offsets(rows)
    flat = np.asarray(flat, np.float64)
    out = np.zeros((len(rows), ids.shape[0], flat.shape[1]))
    start = 0
    for t, L in enumerate(pooling):
        for b in range(ids.shape[0]):
            for l in range(lengths[b, t]):
                out[t, b] += flat[off[t] + ids[b, start + l]]
        start += L
    return out


# ---------------------------------------------------------------------------
# The packed multi-hot kernel
# ---------------------------------------------------------------------------

_MULTIHOT_CASES = [
    ((300, 3, 1000, 40, 7), (1, 2, 12, 27, 100), 5, 128),
    ((50, 9), (100, 1), 9, 8),
    ((4, 4000, 11), (2, 12, 1), 33, 16),
]


@pytest.mark.parametrize("rows,pooling,B,D", _MULTIHOT_CASES,
                         ids=[f"S{sum(c[1])}-B{c[2]}" for c in _MULTIHOT_CASES])
def test_multihot_kernel_matches_a_plain_gather_sum(rows, pooling, B, D):
    """Uneven R_t and L_t, some samples shorter than L_t (their slots past
    the length hold ids out of range, masked), B off the tile."""
    rng = np.random.default_rng(sum(pooling) + B)
    flat = rng.standard_normal((sum(rows), D)).astype(np.float32)
    ids = _packed_ids(rng, rows, pooling, B)
    lengths = np.stack([rng.integers(0, L + 1, B) for L in pooling], 1)
    table, pos = packed_slots(pooling)
    ids[pos[None, :] >= lengths[:, table]] = 10 ** 6       # masked slots
    lengths = lengths.astype(np.int32)
    want = _gather_sum(flat, rows, pooling, ids, lengths)
    for mode in ("interpret", "reference"):
        got = kops.embedding_bag_packed(
            jnp.asarray(flat), jnp.asarray(_offsets(rows)), jnp.asarray(ids),
            jnp.asarray(lengths), pooling, mode=mode)
        assert got.shape == (len(rows), B, D)
        np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5,
                                   atol=1e-5)


def _uniform_case():
    rng = np.random.default_rng(2024)
    rows = [40, 7, 300, 3]
    flat = rng.standard_normal((sum(rows), 16)).astype(np.float32)
    idx = np.stack([rng.integers(0, r, (21, 5)) for r in rows]).astype(
        np.int32)
    w = (rng.random((4, 21, 5)) < 0.7).astype(np.float32)
    return jnp.asarray(flat), jnp.asarray(_offsets(rows)), idx, w


def test_uniform_kernel_stays_bitwise_and_multihot_agrees():
    """The one-L kernel the dlrm cells run reads what it read before the
    multi-hot kernel existed (its output's sha256 at the parent commit),
    and the multi-hot kernel with every L_t equal gives the same bits."""
    flat, off, idx, w = _uniform_case()
    uniform = np.asarray(gather_pool_tbe_flat_pallas(
        flat, off, jnp.asarray(idx), jnp.asarray(w), interpret=True))
    assert hashlib.sha256(uniform.tobytes()).hexdigest()[:16] \
        == "a5b65f7d07e1394c"
    T, B, L = idx.shape
    packed = gather_pool_tbe_multihot_pallas(
        flat, off, jnp.asarray(idx.transpose(1, 0, 2).reshape(B, T * L)),
        jnp.asarray(w.transpose(1, 0, 2).reshape(B, T * L)),
        pooling=(L,) * T, interpret=True)
    np.testing.assert_array_equal(np.asarray(packed), uniform)


def test_multihot_kernel_has_no_backward():
    flat, off = jnp.ones((10, 8)), jnp.asarray([0, 4], jnp.int32)
    ids, lengths = jnp.zeros((2, 3), jnp.int32), jnp.ones((2, 2), jnp.int32)
    loss = lambda f: kops.embedding_bag_packed(
        f, off, ids, lengths, (1, 2), mode="interpret").sum()
    with pytest.raises(NotImplementedError, match="no backward"):
        jax.grad(loss)(flat)


# ---------------------------------------------------------------------------
# Config and tables
# ---------------------------------------------------------------------------

# the fields DLRMConfig hashed before the cross net's were added
_DOT_FIELDS = ("num_dense_features", "num_sparse_features", "embedding_dim",
               "rows_per_table", "pooling", "bottom_mlp", "top_mlp",
               "interaction", "sharding", "rw_impl", "rw_backend", "dtype",
               "kernel_mode", "fused", "cache", "cache_rows", "cache_policy",
               "cold_tier", "remote_hosts", "remote_backend",
               "pipeline_depth")


def test_config_fields_and_dot_configs_keep_their_hash():
    for cfg in (CONFIG, dlrm_cfg.smoke()):
        assert hash(cfg) == hash(tuple(getattr(cfg, f) for f in _DOT_FIELDS))
        assert not cfg.per_table
    assert CONFIG.interaction_dim == 128 + 27 * 26 // 2
    assert TINY.per_table and TINY.interaction_dim == 5 * 16
    assert hash(TINY) != hash(dataclasses.replace(TINY, dcn_rank=4))
    v2 = dlrm_cfg.dlrmv2(5_000_000)
    assert v2.interaction_dim == 3456 and sum(v2.pooling) == 214
    assert v2.embedding_config().table_bytes == 29_184_588 * 128 * 4
    cfg = DLRMConfig(num_sparse_features=2, rows_per_table=[5, 6], pooling=2)
    assert cfg.rows_per_table == (5, 6) and cfg.table_pooling() == (2, 2)
    with pytest.raises(ValueError, match="interaction"):
        DLRMConfig(interaction="cat")
    with pytest.raises(ValueError, match="pooling"):
        DLRMConfig(pooling=(1, 2))


def test_per_table_tables_are_one_flat_array():
    ecfg = TINY.embedding_config()
    assert ecfg.per_table and ecfg.pooling == POOLING
    assert list(ecfg.row_offsets()) == [0, 300, 303, 1303]
    assert ecfg.table_bytes == sum(ROWS) * 16 * 4
    params = dlrm_mod.init_params(jax.random.key(0), TINY)
    assert params["tables"].shape == (sum(ROWS), 16)
    assert [c["v"].shape for c in params["cross"]] == [(80, 8)] * 3
    # padded (T, B, L) lookups over the flat tables read the same rows
    # as the packed ones: offsets from the config, there being no cache
    rng = np.random.default_rng(3)
    ids = _packed_ids(rng, ROWS, POOLING, 6)
    lengths = np.stack([rng.integers(0, L + 1, 6) for L in POOLING], 1)
    packed = eb.pooled_lookup_local(
        params["tables"], PackedBatch(jnp.asarray(ids),
                                      jnp.asarray(lengths, jnp.int32)), ecfg)
    table, pos = packed_slots(POOLING)
    padded = np.zeros((4, 6, max(POOLING)), np.int32)
    padded[table, :, pos] = ids.T
    jagged = eb.pooled_lookup_local(params["tables"], eb.JaggedBatch(
        jnp.asarray(padded), jnp.asarray(lengths.T, jnp.int32)), ecfg)
    np.testing.assert_allclose(np.asarray(packed), np.asarray(jagged),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# The model served through the engine, against a plain forward
# ---------------------------------------------------------------------------

def plain_dcnv2(params, cfg, dense, ids, lengths):
    """DLRM-DCNv2 in plain f32 ``jax.numpy``: sum-pooled bags over the
    flat table, bottom MLP (ReLU after every layer), x0 = [bottom output,
    pooled bags in table order], ``x_{l+1} = x0 * (W (V x_l) + b) + x_l``,
    top MLP (ReLU between layers), sigmoid.  ``ids (n, T, max L_t)``."""
    tables = jnp.asarray(params["tables"])
    off = _offsets(cfg.rows_per_table)
    slot = jnp.arange(ids.shape[2])
    pooled = [jnp.sum(jnp.where((slot < lengths[:, t, None])[..., None],
                                tables[off[t] + ids[:, t]], 0.0), axis=1)
              for t in range(len(off))]
    x = jnp.asarray(dense)
    for layer in params["bottom"]:
        x = jax.nn.relu(jnp.dot(x, layer["w"], precision=HI) + layer["b"])
    x0 = jnp.concatenate([x] + pooled, axis=1)
    x = x0
    for c in params["cross"]:
        low = jnp.dot(x, c["v"], precision=HI)
        x = x0 * (jnp.dot(low, c["w"], precision=HI) + c["b"]) + x
    for i, layer in enumerate(params["top"]):
        x = jnp.dot(x, layer["w"], precision=HI) + layer["b"]
        if i < len(params["top"]) - 1:
            x = jax.nn.relu(x)
    return jax.nn.sigmoid(x[:, 0])


def _requests(cfg, n, seed=0):
    rng = np.random.default_rng(seed)
    L = max(cfg.pooling)
    out = []
    for rid in range(n):
        ids = np.full((len(ROWS), L), -1, np.int32)      # padding past L_t
        for t, (r, lt) in enumerate(zip(cfg.rows_per_table, cfg.pooling)):
            ids[t, :lt] = rng.integers(0, r, lt)
        lengths = np.array([rng.integers(1, lt + 1) for lt in cfg.pooling],
                           np.int32)
        out.append(CTRRequest(rid, rng.standard_normal(
            cfg.num_dense_features).astype(np.float32), ids, lengths))
    return out


def _seeded_params(cfg):
    """init_params with every bias drawn too, so each one is read."""
    params = dlrm_mod.init_params(jax.random.key(7), cfg)
    keys = iter(jax.random.split(jax.random.key(8), 64))
    return jax.tree_util.tree_map_with_path(
        lambda path, a: (0.1 * jax.random.normal(next(keys), a.shape)
                         if path[-1].key == "b" else a), params)


@pytest.mark.parametrize("mode", ["interpret", "reference"])
def test_dcnv2_engine_matches_a_plain_forward(mode):
    with jax.default_matmul_precision("highest"):
        cfg = dataclasses.replace(TINY, kernel_mode=mode)
        params = _seeded_params(cfg)
        eng = make_dlrm_engine(params, cfg, batch_size=8)
        reqs = _requests(cfg, 13)                    # a full and a part batch
        for r in reqs:
            eng.submit(r)
        got = eng.run_to_completion()
        want = plain_dcnv2(params, cfg, np.stack([r.dense for r in reqs]),
                           np.stack([r.indices for r in reqs]),
                           np.stack([r.lengths for r in reqs]))
    assert sorted(got) == list(range(13))
    np.testing.assert_allclose([got[r] for r in range(13)], np.asarray(want),
                               rtol=0, atol=2e-6)
    assert np.ptp(np.asarray(want)) > 0.01           # the scores differ


def test_one_cross_layer_left_out_is_seen():
    """The comparison above can tell a cross layer apart."""
    params = _seeded_params(TINY)
    reqs = _requests(TINY, 4)
    args = (np.stack([r.dense for r in reqs]),
            np.stack([r.indices for r in reqs]),
            np.stack([r.lengths for r in reqs]))
    full = plain_dcnv2(params, TINY, *args)
    short = plain_dcnv2({**params, "cross": params["cross"][:2]}, TINY, *args)
    assert np.max(np.abs(np.asarray(full) - np.asarray(short))) > 1e-4


@pytest.mark.parametrize("fault", ["id past its table", "length past L_t"])
def test_submit_refuses_one_tables_bound(fault):
    eng = make_dlrm_engine(dlrm_mod.init_params(jax.random.key(0), TINY),
                           TINY, batch_size=4)
    req = _requests(TINY, 1)[0]
    ids, lengths = req.indices.copy(), req.lengths.copy()
    ids[2, 0] = ROWS[1]             # in range for table 2 (1000 rows)
    lengths[1] = 1
    eng.submit(dataclasses.replace(req, indices=ids, lengths=lengths))
    if fault == "id past its table":
        ids[1, 0] = ROWS[1]         # table 1 has 3 rows
        bad = dataclasses.replace(req, indices=ids, lengths=lengths)
        match = "indices"
    else:
        lengths[1] = 2              # table 1 pools 1, the widest 12
        bad = dataclasses.replace(req, indices=ids, lengths=lengths)
        match = "lengths"
    with pytest.raises(ValueError, match=match):
        eng.submit(bad)
    assert len(eng.queue) == 1


def test_row_wise_and_tiered_paths_refuse_per_table_configs():
    from repro.core.parallel import make_context
    from repro.launch.mesh import auto_mesh

    params = dlrm_mod.init_params(jax.random.key(0), TINY)
    ctx = make_context(auto_mesh((1, 1), ("data", "model"),
                                 devices=jax.devices()[:1]))
    dense = jnp.zeros((4, 4))
    batch = PackedBatch(jnp.zeros((4, sum(POOLING)), jnp.int32),
                        jnp.ones((4, 4), jnp.int32))
    with pytest.raises(NotImplementedError,
                       match="row-wise split of uneven tables"):
        dlrm_mod.forward(params, dense, batch, TINY, ctx)
    with pytest.raises(NotImplementedError,
                       match="row-wise split of uneven tables"):
        eb.pooled_lookup_sharded(params["tables"], batch,
                                 TINY.embedding_config())
    tiered = dataclasses.replace(TINY, cache=CacheConfig(rows=64))
    with pytest.raises(NotImplementedError,
                       match="per-table tiered metadata"):
        make_dlrm_engine(params, tiered, batch_size=4)
    with pytest.raises(NotImplementedError,
                       match="per-table tiered metadata"):
        eb.make_cache(np.asarray(params["tables"]), tiered.embedding_config())
