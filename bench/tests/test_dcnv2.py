"""CPU tests of the DLRM-DCNv2 cell: a tiny run is correct, broken answers
are not, the reference draws the rows the adapter's flat table holds, and
the control at three bfloat16 passes reads above the cell's limit.

    JAX_PLATFORMS=cpu python3 -m pytest -q bench/tests
"""
from __future__ import annotations

import json

import numpy as np
import pytest

from test_bench import ROOT, tiny_run  # noqa: E402 (sets sys.path)

from bench import harness, traffic  # noqa: E402
from bench.models import dlrm_dcnv2, dlrm_dcnv2_reference as ref  # noqa: E402

CELL = "dcnv2.uniform.offline"

# DLRMv2's structure at a size the Pallas interpreter runs in seconds
TINY = {"num_dense_features": 4, "num_sparse_features": 4,
        "embedding_dim": 16, "rows_per_table": [300, 3, 1000, 40],
        "pooling": [3, 1, 12, 2], "bottom_mlp": [32, 16],
        "top_mlp": [64, 32, 1], "dcn_rank": 8, "kernel_mode": "interpret",
        "batch_size": 32}
TINY_MIX = {"distinct_samples": 512, "warmup_batches": 2,
            "batches_per_call": 2}


def tiny_cell() -> harness.Cell:
    cell = harness.load_cell(ROOT, CELL)
    cell.config.update(TINY)
    cell.mix.update(TINY_MIX)
    cell.per_layer = []
    return cell


def test_the_cell_is_the_configuration_it_claims():
    cfg = harness.load_cell(ROOT, CELL).config
    assert cfg["model"] == "dlrm_dcnv2" and cfg["chips"] == 1
    cap = [min(r, 5_000_000) for r in json.loads(
        (ROOT / "bench/configs/dlrmv2-dcn-hbm.json").read_text())[
            "rows_per_table"]]
    assert cfg["rows_per_table"] == cap and sum(cap) == 29_184_588
    assert sum(cfg["pooling"]) == 214 and max(cfg["pooling"]) == 100
    # MLPs 10,827,264 + cross 3 x (4 x 3456 x 512 + 3 x 3456) + pooling
    assert ref.dense_flops_per_sample(cfg) == 10_827_264 + 21_264_768
    assert ref.flops_per_sample(cfg) == 32_092_032 + 2 * 214 * 128


def test_tiny_run_is_correct():
    result, compared, info = tiny_run(tiny_cell())
    assert result["correct"], compared
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {"samples_per_s", "setup_s"}
    assert info["compiles_in_window"] == 0


class _HalfLeftOut:
    def __init__(self, system):
        self._system = system

    def __getattr__(self, name):
        return getattr(self._system, name)

    def drain(self):
        out = self._system.drain()
        return {r: out[r] for r in sorted(out)[: len(out) // 2]}


@pytest.mark.parametrize("fault", ["answer altered", "half left out",
                                   "cross layer left out"])
def test_broken_answers_are_not_correct(monkeypatch, fault):
    import jax

    from repro.models import dlrm as dlrm_mod
    from repro.serving import engine

    if fault == "answer altered":
        real = engine.make_scorer

        def make(cfg, ctx=None):
            fwd = real(cfg, ctx)
            return jax.jit(lambda p, d, b: fwd(p, d, b).at[0].add(1e-3))

        monkeypatch.setattr(engine, "make_scorer", make)
    elif fault == "half left out":
        real_system = dlrm_dcnv2.System
        monkeypatch.setattr(dlrm_dcnv2, "System",
                            lambda *a: _HalfLeftOut(real_system(*a)))
    else:
        real_cross = dlrm_mod.dcn_cross
        monkeypatch.setattr(dlrm_mod, "dcn_cross",
                            lambda x0, layers: real_cross(x0, layers[:-1]))
    result, compared, _ = tiny_run(tiny_cell())
    assert not result["correct"]
    if fault == "half left out":
        assert result["failed"] == result["attempted"] // 2
    else:
        assert compared["max_abs_dpctr"]["value"] > 1e-4


def test_reference_draws_the_adapters_rows():
    """Table ``t``'s id ``r`` reads flat row ``offset_t + r`` on both
    sides, bitwise, for every table's live slots."""
    import jax
    import jax.numpy as jnp

    cfg = dict(tiny_cell().config)
    seed = 2 ** 31 + 77
    params = dlrm_dcnv2.make_weights(seed, cfg, jax.devices()[0])
    table = np.asarray(params["tables"])
    R = np.asarray(cfg["rows_per_table"])
    assert table.shape == (R.sum(), cfg["embedding_dim"])
    pool = traffic.samples(seed, cfg, {"ids": {"dist": "uniform"}}, 64)
    got = np.asarray(ref.live_rows(
        jnp.asarray(ref.seed_words(seed)), pool.ids, tuple(R.tolist()),
        tuple(cfg["pooling"]), cfg["embedding_dim"]))
    offsets = np.concatenate([[0], np.cumsum(R)[:-1]])
    want = np.concatenate(
        [table[offsets[t] + pool.ids[:, t, :L]]
         for t, L in enumerate(cfg["pooling"])], axis=1)
    assert got.shape == (64, 18, cfg["embedding_dim"])
    np.testing.assert_array_equal(got, want)


def control_gap(cfg: dict, mix: dict, seed: int, n: int) -> float:
    """The widest pCTR gap over ``n`` samples between the reference at
    "highest" and at three bfloat16 passes ("high")."""
    import jax

    pool = traffic.samples(seed, cfg, mix, n)
    key = jax.random.fold_in(jax.random.wrap_key_data(
        jax.numpy.asarray(ref.seed_words(seed)), impl="threefry2x32"), 1)
    weights = jax.tree.map(np.asarray, ref.weights_from_seed(key, cfg))
    lengths = np.ascontiguousarray(
        np.broadcast_to(pool.lengths, (n, pool.lengths.size)))
    got = {p: ref.reference_scores(seed, cfg, weights, pool.dense, pool.ids,
                                   lengths, precision=p).astype(np.float64)
           for p in ("highest", "high")}
    return float(np.max(np.abs(got["high"] - got["highest"])))


def test_control_fails_the_limit():
    """At the cell's widths (rows capped at 4,096, 1,024 samples) the
    reference at three bf16 passes reads above the cell's limit."""
    cell = harness.load_cell(ROOT, CELL)
    cfg = dict(cell.config, rows_per_table=[
        min(r, 4096) for r in cell.config["rows_per_table"]])
    gaps = [control_gap(cfg, cell.mix, s, 1024) for s in (1, 2)]
    assert min(gaps) > cell.check["max_abs_dpctr"], gaps


def test_dense_mfu_reads_the_dense_ops():
    from test_bench import _events

    from bench import trace

    reader = harness.reader("dense_mfu")
    cell = harness.load_cell(ROOT, CELL)
    win = harness.Window("offline", 2.0, 2048, 2048, 0.04,
                         [range(0, 1024), range(1024, 2048)], {})
    events = [e._replace(name=e.name.replace("gather_pool_tbe_flat_pallas",
                                             dlrm_dcnv2.TBE_KERNEL))
              for e in _events()]
    run = harness.Run(cell, win, 2, {"flops": 1e12, "hbm_bytes_per_s": 1e9},
                      None, trace.summarize(events, chips=2), None, 0)
    # per chip 2000 ns of TBE, 500 + 1000 ns of other ops
    dense_s = 2 * 1500e-9
    assert reader(run) == pytest.approx(
        100 * ref.dense_flops_per_sample(cell.config) * 2048
        / (dense_s * 1e12))
    # a model whose reference has no dense count reads nothing
    assert reader(harness.Run(harness.load_cell(ROOT, "hbm.uniform.offline"),
                              win, 2, run.peaks, None, run.trace, None,
                              0)) is None
