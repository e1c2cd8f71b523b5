"""CPU tests of configurations whose tables differ: ``rows_per_table`` and
``pooling`` given per table, in the traffic generator, the check's
plumbing and the TBE byte count; and that every current cell still draws
and counts what it drew and counted with one int for every table.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import hashlib
import json
import types

import numpy as np
import pytest

from test_bench import ROOT, TINY, tiny_cell  # noqa: E402 (sets sys.path)

from bench import harness, traffic  # noqa: E402
from bench.models import dlrm, dlrm_reference as ref  # noqa: E402

MIXES = ("uniform.offline", "zipf.offline", "zipf.server")
CONFIGS = ("dlrm-criteo-hbm", "dlrm-criteo-tiered", "dlrm-criteo-rw4")

# DLRMv2 (DCNv2) on Criteo 1TB multi-hot, as MLPerf runs it: rows per
# table capped at 40M, and each table's multi-hot size
DLRMV2_ROWS = [40000000, 39060, 17295, 7424, 20265, 3, 7122, 1543, 63,
               40000000, 3067956, 405282, 10, 2209, 11938, 155, 4, 976, 14,
               40000000, 40000000, 40000000, 590152, 12973, 108, 36]
DLRMV2_POOLING = [3, 2, 1, 2, 6, 1, 1, 1, 1, 7, 3, 8, 1, 6, 9, 5, 1, 1, 1, 12,
                  100, 27, 10, 3, 1, 1]


def config(name: str) -> dict:
    cfg = json.loads((ROOT / "bench/configs/dlrm-criteo-hbm.json")
                     .read_text())
    if name == "tiny":
        return dict(cfg, **TINY)
    return json.loads((ROOT / "bench/configs" / f"{name}.json").read_text())


def mix(name: str) -> dict:
    return json.loads((ROOT / "bench/traffic" / f"{name}.json").read_text())


def digest(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# The current cells draw and count what they did
# ---------------------------------------------------------------------------

# sha256 of ids, dense and lengths of the first CHUNK samples of seed
# 2**31 + 29, as the generator drew them with one int for every table
# (traffic.py at 62b6b07; the CPU backend); tiered's shapes are hbm's
_UNIFORM_TINY = ["9ea7a97f8d8f803e", "a5286d7cece7d6af", "e55f704330dd9c3c"]
_ZIPF_TINY = ["ee6bd1fb38b95184", "a5286d7cece7d6af", "e55f704330dd9c3c"]
_UNIFORM_1M = ["c4fb1c4edbd53a48", "30bf03b2df36026c", "a917c9847816d61a"]
_ZIPF_1M = ["dff78d17278fe6a6", "30bf03b2df36026c", "a917c9847816d61a"]
_UNIFORM_4M = ["32c840811a758c15", "30bf03b2df36026c", "a917c9847816d61a"]
_ZIPF_4M = ["83dd894c8fd06390", "30bf03b2df36026c", "a917c9847816d61a"]
DIGESTS = {
    ("tiny", "uniform.offline"): _UNIFORM_TINY,
    ("tiny", "zipf.offline"): _ZIPF_TINY,
    ("tiny", "zipf.server"): _ZIPF_TINY,
    **{(c, "uniform.offline"): _UNIFORM_1M for c in CONFIGS[:2]},
    **{(c, m): _ZIPF_1M for c in CONFIGS[:2] for m in MIXES[1:]},
    ("dlrm-criteo-rw4", "uniform.offline"): _UNIFORM_4M,
    ("dlrm-criteo-rw4", "zipf.offline"): _ZIPF_4M,
    ("dlrm-criteo-rw4", "zipf.server"): _ZIPF_4M,
}


@pytest.mark.parametrize("cfg_name,mix_name", sorted(DIGESTS))
def test_current_cells_draw_the_same_samples(cfg_name, mix_name):
    s = traffic.samples(2 ** 31 + 29, config(cfg_name), mix(mix_name),
                        traffic.CHUNK)
    assert [digest(s.ids), digest(s.dense), digest(s.lengths)] \
        == DIGESTS[cfg_name, mix_name]


@pytest.mark.parametrize("mix_name,rows,want", [
    ("zipf.offline", 1_000_000, "5b1505070fa11202"),
    ("zipf.offline", 256, "ba4d830e68517e58"),
    ("uniform.offline", 1_000_000, "3f56c0b93ad84797")])
def test_log_counts_of_an_int_are_unchanged(mix_name, rows, want):
    m = dict(mix(mix_name), cache_log_lookups=10 ** 9)
    counts = traffic.log_counts(m, rows)
    assert counts.shape == (rows,) and counts.dtype == np.int64
    assert digest(counts) == want


def _old_tbe_bytes(win, pool, cfg, chips):
    """The byte count as it was for one int of rows and of pooling."""
    T, R, D, L = (cfg["num_sparse_features"], cfg["rows_per_table"],
                  cfg["embedding_dim"], cfg["pooling"])
    seen = np.zeros(T * R, bool)
    offsets = (np.arange(T, dtype=np.int64) * R)[None, :, None]
    total = 0.0
    for rids in win.batches:
        rids = np.asarray(list(rids), np.int64)
        flat = (pool.ids[rids % len(pool)] + offsets).ravel()
        seen[flat] = True
        distinct = np.flatnonzero(seen)
        seen[distinct] = False
        per_chip = np.bincount((distinct % R) // -(-R // chips),
                               minlength=chips)
        for n in per_chip:
            total += ref.tbe_least_bytes(int(n), T * len(rids) * L,
                                         len(rids), T, D)
    return total


def _window(batches) -> harness.Window:
    return harness.Window("offline", 1.0, 0, 0, 0.0, batches, {})


def _batches(n_pool: int, sizes, seed=0):
    """Micro-batches of the given sizes over request ids that wrap the
    pool, as a server's uneven flushes do."""
    rng = np.random.default_rng(seed)
    first = int(rng.integers(0, n_pool))
    out = []
    for s in sizes:
        out.append(range(first, first + s))
        first += s
    return out


@pytest.mark.parametrize("cfg_name,mix_name,chips", [
    ("tiny", "uniform.offline", 1), ("tiny", "zipf.offline", 4),
    ("dlrm-criteo-hbm", "uniform.offline", 1),
    ("dlrm-criteo-tiered", "zipf.offline", 1),
    ("dlrm-criteo-rw4", "uniform.offline", 4)])
def test_tbe_bytes_of_equal_tables_are_unchanged(cfg_name, mix_name, chips):
    cfg = config(cfg_name)
    pool = traffic.samples(7, cfg, mix(mix_name), traffic.CHUNK)
    win = _window(_batches(len(pool), [1024, 1024, 300, 1024]))
    assert harness._tbe_bytes(win, pool, cfg, chips, ref) \
        == _old_tbe_bytes(win, pool, cfg, chips)


# ---------------------------------------------------------------------------
# Tables that differ
# ---------------------------------------------------------------------------

def _lists(cfg: dict) -> dict:
    T = cfg["num_sparse_features"]
    return dict(cfg, rows_per_table=[cfg["rows_per_table"]] * T,
                pooling=[cfg["pooling"]] * T)


@pytest.mark.parametrize("mix_name", ["uniform.offline", "zipf.offline"])
def test_a_list_of_equal_entries_draws_what_the_int_draws(mix_name):
    cfg = config("tiny")
    a = traffic.samples(2 ** 31 + 3, cfg, mix(mix_name), 9000)
    b = traffic.samples(2 ** 31 + 3, _lists(cfg), mix(mix_name), 9000)
    for x, y in ((a.ids, b.ids), (a.dense, b.dense), (a.lengths, b.lengths)):
        assert x.dtype == y.dtype and np.array_equal(x, y)
    win = _window(_batches(len(a), [32, 17, 32]))
    for chips in (1, 4):
        assert harness._tbe_bytes(win, a, cfg, chips, ref) \
            == harness._tbe_bytes(win, b, _lists(cfg), chips, ref)
    m = dict(mix(mix_name), cache_log_lookups=10 ** 6)
    counts = traffic.log_counts(m, _lists(cfg)["rows_per_table"])
    assert len(counts) == cfg["num_sparse_features"]
    assert all(np.array_equal(c, traffic.log_counts(m, 256)) for c in counts)


def small_dlrmv2(rows_cap: int = 500) -> dict:
    """DLRMv2's 26 tables and multi-hot sizes, rows capped at ``rows_cap``
    (so its smallest tables, of 3, 4 and 10 rows, stay)."""
    return dict(config("tiny"), num_sparse_features=26,
                rows_per_table=[min(r, rows_cap) for r in DLRMV2_ROWS],
                pooling=DLRMV2_POOLING)


def _assert_in_range(s, cfg):
    R = np.asarray(cfg["rows_per_table"])
    L = np.asarray(cfg["pooling"])
    assert s.ids.shape[1:] == (len(R), L.max()) and s.ids.dtype == np.int32
    assert np.array_equal(s.lengths, L) and s.lengths.dtype == np.int32
    live = np.arange(L.max())[None, :] < L[:, None]           # (T, L)
    assert np.all(s.ids >= 0)
    assert np.all(s.ids < np.where(live, R[:, None], 1)[None])  # 0 past L_t
    return live


@pytest.mark.parametrize("dist", ["uniform", "zipf"])
def test_per_table_ids_stay_in_their_tables(dist):
    cfg = small_dlrmv2()
    m = {"ids": {"dist": dist, "a": 1.05}}
    s = traffic.samples(2 ** 31 + 11, cfg, m, traffic.CHUNK)
    live = _assert_in_range(s, cfg)
    R = np.asarray(cfg["rows_per_table"])
    for t in range(len(R)):
        got = s.ids[:, t, live[t]].ravel()
        # every row of a small table is drawn; the tables' draws differ
        if R[t] <= 10:
            assert np.array_equal(np.unique(got), np.arange(R[t]))
        share0 = np.mean(got == 0)
        if dist == "uniform":
            assert share0 == pytest.approx(1 / R[t], abs=0.02)
        else:
            pmf = np.arange(1, R[t] + 1, dtype=np.float64) ** -1.05
            assert share0 == pytest.approx(pmf[0] / pmf.sum(), abs=0.02)
    wide = [t for t in range(len(R)) if R[t] == 500]
    assert not np.array_equal(s.ids[:, wide[0]], s.ids[:, wide[1]])


def _brute_tbe_bytes(win, pool, cfg, chips):
    """Each micro-batch's distinct (table, row) pairs, counted one by one
    on the chip that holds them, plus every live slot and the output."""
    T, D = cfg["num_sparse_features"], cfg["embedding_dim"]
    R, L = cfg["rows_per_table"], cfg["pooling"]
    total = 0.0
    for rids in win.batches:
        rows = pool.ids[np.asarray(list(rids)) % len(pool)]
        held = [set() for _ in range(chips)]
        for t in range(T):
            share = -(-R[t] // chips)
            for r in np.unique(rows[:, t, :L[t]]).tolist():
                held[r // share].add((t, r))
        for h in held:
            total += ref.tbe_least_bytes(len(h), len(rids) * sum(L),
                                         len(rids), T, D)
    return total


@pytest.mark.parametrize("dist", ["uniform", "zipf"])
@pytest.mark.parametrize("chips", [1, 4])
def test_tbe_bytes_match_a_brute_force_count(dist, chips):
    cfg = small_dlrmv2()
    pool = traffic.samples(5, cfg, {"ids": {"dist": dist, "a": 1.05}}, 2000)
    win = _window(_batches(len(pool), [512, 77, 512, 1]))
    assert harness._tbe_bytes(win, pool, cfg, chips, ref) \
        == _brute_tbe_bytes(win, pool, cfg, chips)


def test_dlrmv2_published_shapes_pass_through():
    """Uniform ids at DLRMv2's own row counts (zipf there builds a 40M-row
    alias table, ~30 s: PERF.md gives its timing)."""
    cfg = dict(config("tiny"), num_sparse_features=26,
               rows_per_table=DLRMV2_ROWS, pooling=DLRMV2_POOLING,
               embedding_dim=128)
    assert sum(DLRMV2_ROWS) == 204_184_588 and sum(DLRMV2_POOLING) == 214
    s = traffic.samples(2 ** 31 + 41, cfg, mix("uniform.offline"), 2048)
    _assert_in_range(s, cfg)
    assert s.ids[:, 0].max() > 39_000_000     # ids reach the 40M tables' end
    win = _window(_batches(len(s), [1024, 1024]))
    assert harness._tbe_bytes(win, s, cfg, 4, ref) \
        == _brute_tbe_bytes(win, s, cfg, 4)
    counts = traffic.log_counts(dict(mix("uniform.offline"),
                                     cache_log_lookups=10 ** 9), DLRMV2_ROWS)
    assert [c.size for c in counts] == DLRMV2_ROWS
    assert counts[0] is counts[9]             # one array for one size


@pytest.mark.parametrize("value", [[256, 256, 256], [0, 1, 2, 3], 0])
def test_per_table_refuses_a_wrong_list(value):
    with pytest.raises(ValueError, match="rows_per_table"):
        traffic.per_table(dict(TINY, rows_per_table=value), "rows_per_table")


# ---------------------------------------------------------------------------
# The check's plumbing passes each table's pooling through
# ---------------------------------------------------------------------------

def test_submit_check_and_control_pass_per_table_lengths(monkeypatch):
    cfg = small_dlrmv2()
    pool = traffic.samples(9, cfg, mix("uniform.offline"), 300)
    L = np.asarray(DLRMV2_POOLING)

    sent = []
    eng = types.SimpleNamespace(submit=sent.append)
    system = types.SimpleNamespace(engine=eng,
                                   request=lambda *a: types.SimpleNamespace(
                                       rid=a[0], dense=a[1], ids=a[2],
                                       lengths=a[3]))
    harness.submit(system, pool, 295, 10)
    assert [r.rid for r in sent] == list(range(295, 305))
    assert all(np.array_equal(r.lengths, L) for r in sent)
    assert np.array_equal(sent[-1].ids, pool.ids[304 % 300])

    got = {}

    def scores(seed, cfg, mlp, dense, ids, lengths, **kw):
        got.update(dense=dense, ids=ids, lengths=lengths)
        return np.full(len(dense), 0.5, np.float32)

    win = _window([])
    win.answers = {r: 0.5 for r in range(300, 500)}
    reference = types.SimpleNamespace(reference_scores=scores)
    assert harness._check(win, pool, cfg, 3, None, reference) == 0.0
    assert got["lengths"].shape == (200, 26)
    assert np.all(got["lengths"] == L[None])
    assert np.array_equal(got["ids"], pool.ids[np.arange(300, 500) % 300])

    from bench import control

    cell = tiny_cell("hbm.uniform.offline")
    cell.config.update(cfg)
    monkeypatch.setattr(ref, "reference_scores", scores)
    monkeypatch.setattr(harness, "CHECK_SAMPLES", 64)
    assert control.control_gap(cell, 3) == 0.0
    assert got["lengths"].shape == (64, 26)
    assert np.all(got["lengths"] == L[None])


@pytest.mark.parametrize("key", ["rows_per_table", "pooling"])
def test_dlrm_refuses_a_per_table_config(key):
    """The dlrm model has one row count and one pooling for all tables:
    a list is refused, naming its key, and not broadcast."""
    import jax

    cfg = dict(config("tiny"))
    cfg[key] = [cfg[key]] * cfg["num_sparse_features"]
    with pytest.raises(ValueError, match=key):
        dlrm.System(1, cfg, jax.devices()[:1])
    with pytest.raises(ValueError, match=key):
        ref.flops_per_sample(cfg)
    with pytest.raises(ValueError, match=key):
        ref.reference_scores(1, cfg, None, np.zeros((1, 4), np.float32),
                             np.zeros((1, 4, 4), np.int32),
                             np.full((1, 4), 4, np.int32))
