"""Fused table-batched embedding bag (TBE): oracle sweeps, RW variant,
custom_vjp gradient, and the single-launch guarantee (interpret mode)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import audit, count_pallas_calls
from repro.core.embedding_bag import (
    EmbeddingBagConfig,
    init_tables,
    pooled_lookup_local,
)
from repro.core.jagged import random_jagged_batch
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.embedding_gather import gather_pool_tbe_flat_pallas


def _mk(T, R=64, D=32, B=6, L=5, seed=0, weighted=False):
    rng = np.random.default_rng(seed)
    tables = jnp.asarray(rng.standard_normal((T, R, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, R, (T, B, L)), jnp.int32)
    lens = jnp.asarray(rng.integers(0, L + 1, (T, B)), jnp.int32)
    w = (jnp.asarray(rng.standard_normal((T, B, L)), jnp.float32)
         if weighted else None)
    return tables, idx, lens, w


def _exact(rng, shape):
    """f32 values whose weighted sums over up to 32 terms are exact, so
    any pooling order gives the same bits: multiples of 1/4 in [-4, 4]."""
    return jnp.asarray(rng.integers(-16, 17, shape) / 4, jnp.float32)


# (T, B, L, D, rows per table): B is never a multiple of the tile (padded
# samples), the tables are ragged, and L covers the one-hot case, a
# few-hot one and the CONFIG pooling factor.
_FLAT_CASES = [
    (1, 9, 1, 128, [40]),
    (3, 20, 4, 128, [5, 17, 9]),
    (3, 130, 1, 8, [30, 2, 50]),
    (26, 12, 32, 16, [7, 3, 11] * 8 + [5, 9]),
]


@pytest.mark.parametrize("T,B,L,D,rows", _FLAT_CASES,
                         ids=[f"T{c[0]}-B{c[1]}-L{c[2]}" for c in _FLAT_CASES])
def test_tbe_flat_kernel_bitwise(T, B, L, D, rows):
    """The kernel over a ragged flat row space, bitwise to the flat oracle:
    each slot reads row ``clip(row_offsets[t] + id, 0, N - 1)`` (ids run
    past their table and below 0) and weight-0 slots add nothing."""
    rng = np.random.default_rng(T * 1000 + B + L)
    N = sum(rows)
    off = np.concatenate([[0], np.cumsum(rows)[:-1]]).astype(np.int32)
    flat = _exact(rng, (N, D))
    idx = rng.integers(-3, max(rows) + 3, (T, B, L)).astype(np.int32)
    w = np.array(_exact(rng, (T, B, L)))
    w[rng.random((T, B, L)) < 0.25] = 0.0            # masked slots
    clamped = np.clip(off[:, None, None] + idx, 0, N - 1) - off[:, None, None]
    got = gather_pool_tbe_flat_pallas(flat, jnp.asarray(off), jnp.asarray(idx),
                                      jnp.asarray(w), interpret=True)
    want = kref.embedding_bag_batched_flat_ref(
        flat, jnp.asarray(off), jnp.asarray(clamped), None, jnp.asarray(w))
    assert got.shape == (T, B, D)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("T", [1, 4, 16])
@pytest.mark.parametrize("weighted", [False, True])
def test_tbe_matches_oracle(T, weighted):
    tables, idx, lens, w = _mk(T, weighted=weighted)
    ref = kops.embedding_bag_batched(tables, idx, lens, w, mode="reference")
    out = kops.embedding_bag_batched(tables, idx, lens, w, mode="interpret",
                                     fused=True)
    assert out.shape == (T, 6, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("T", [1, 4])
def test_tbe_matches_unfused(T):
    tables, idx, lens, _ = _mk(T, seed=T)
    fused = kops.embedding_bag_batched(tables, idx, lens, mode="interpret",
                                       fused=True)
    unfused = kops.embedding_bag_batched(tables, idx, lens, mode="interpret",
                                         fused=False)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(unfused),
                               atol=1e-6, rtol=1e-6)


def test_tbe_mean_combiner():
    tables, idx, lens, w = _mk(4, weighted=True, seed=3)
    w = jnp.abs(w) + 0.1          # mean needs positive weights
    ref = kops.embedding_bag_batched(tables, idx, lens, w, combiner="mean",
                                     mode="reference")
    out = kops.embedding_bag_batched(tables, idx, lens, w, combiner="mean",
                                     mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("T", [1, 3, 4, 16, 26])
def test_tbe_rw_premasked_shards_reconstruct(T):
    """RW variant: per-shard fused partials equal the masked oracle bit for
    bit (exact values, L=32) and sum to the full pool."""
    R, E = 64, 4
    rng = np.random.default_rng(T + 10)
    tables = _exact(rng, (T, R, 16))
    idx = jnp.asarray(rng.integers(0, R, (T, 12, 32)), jnp.int32)
    lens = jnp.asarray(rng.integers(0, 33, (T, 12)), jnp.int32)
    full = kops.embedding_bag_batched(tables, idx, lens, mode="reference")
    Rs = R // E
    acc = jnp.zeros_like(full)
    for e in range(E):
        shard = tables[:, e * Rs:(e + 1) * Rs]
        part = kops.embedding_bag_rw_partial_batched(
            shard, e * Rs, idx, lens, mode="interpret", fused=True)
        ref = kops.embedding_bag_rw_partial_batched(
            shard, e * Rs, idx, lens, mode="reference")
        np.testing.assert_array_equal(np.asarray(part), np.asarray(ref))
        acc = acc + part
    np.testing.assert_array_equal(np.asarray(acc), np.asarray(full))


def test_tbe_grad_matches_reference():
    """custom_vjp: d/dtables and d/dweights of the fused path == oracle."""
    tables, idx, lens, w = _mk(4, seed=7, weighted=True)

    def loss(mode):
        def f(t, ww):
            out = kops.embedding_bag_batched(t, idx, lens, ww, mode=mode)
            return jnp.sum(out ** 2)
        return jax.grad(f, argnums=(0, 1))(tables, w)

    g_ref = loss("reference")
    g_tbe = loss("interpret")
    for a, b in zip(g_tbe, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_tbe_single_pallas_call():
    """The fused path must execute ALL tables in ONE pallas_call —
    audited against the module's attached KernelContract (launch count,
    no collectives, no callbacks, no dtype upcasts in one pass)."""
    tables, idx, lens, _ = _mk(8)
    eff_w = jnp.ones(idx.shape, jnp.float32)

    audit(lambda t, i, w: kops.embedding_bag_batched(
              t, i, None, w, mode="interpret", fused=True),
          (tables, idx, eff_w),
          kops.KERNEL_CONTRACTS["tbe_fused"]).raise_if_failed()

    audit(lambda t, i: kops.embedding_bag_rw_partial_batched(
              t, 0, i, mode="interpret", fused=True),
          (tables[:, :8], idx),
          kops.KERNEL_CONTRACTS["rw_partial_fused"]).raise_if_failed()


def test_unfused_path_launches_one_kernel_per_table():
    """The per-table baseline the fused kernel is measured against pays
    one pallas_call per table (the fused one is pinned to one above)."""
    for T in (1, 4):
        tables, idx, _, _ = _mk(T)
        eff_w = jnp.ones(idx.shape, jnp.float32)
        n = count_pallas_calls(
            lambda t, i, w: kops.embedding_bag_batched(
                t, i, None, w, mode="interpret", fused=False),
            tables, idx, eff_w)
        assert n == T, (T, n)


def test_pooled_lookup_local_fused_switch():
    """cfg.fused toggles the kernel layout, not the numbers."""
    rng = np.random.default_rng(5)
    base = EmbeddingBagConfig(num_tables=4, rows_per_table=64, dim=32,
                              kernel_mode="interpret")
    tables = init_tables(jax.random.key(0), base)
    batch = random_jagged_batch(rng, 4, 6, 5, 64, fixed_pooling=False)
    ref_cfg = dataclasses.replace(base, kernel_mode="reference")
    want = pooled_lookup_local(tables, batch, ref_cfg)
    for fused in (True, False):
        got = pooled_lookup_local(
            tables, batch, dataclasses.replace(base, fused=fused))
        assert got.shape == want.shape == (6, 4, 32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


def test_tbe_nonaligned_dim_and_L1():
    """Non-128-multiple D (DLRM smoke) and the L=1 LM-vocab degenerate."""
    for (R, D, B, L) in [(100, 96, 5, 3), (64, 128, 4, 1)]:
        tables, idx, lens, _ = _mk(3, R=R, D=D, B=B, L=L, seed=R)
        ref = kops.embedding_bag_batched(tables, idx, lens, mode="reference")
        out = kops.embedding_bag_batched(tables, idx, lens, mode="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
