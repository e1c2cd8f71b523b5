"""Per-kernel allclose sweeps against the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.flash_attention import flash_attention
from repro.models.layers import chunked_attention, full_attention


# ---------------------------------------------------------------------------
# Embedding gather+pool kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("R,D,B,L", [
    (32, 16, 8, 4),       # tiny
    (64, 128, 4, 1),      # L=1 (the LM vocab case)
    (128, 256, 16, 8),    # MXU-aligned dim
    (100, 96, 5, 3),      # non-128-multiple dim
    (64, 128, 130, 32),   # CONFIG pooling, two tiles, padded samples
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_gather_pool_sweep(R, D, B, L, dtype):
    rng = np.random.default_rng(R + D)
    table = jnp.asarray(rng.standard_normal((R, D)), dtype)
    idx = jnp.asarray(rng.integers(0, R, (B, L)), jnp.int32)
    lens = jnp.asarray(rng.integers(0, L + 1, (B,)), jnp.int32)
    ref = kops.embedding_bag(table, idx, lens, mode="reference")
    out = kops.embedding_bag(table, idx, lens, mode="interpret")
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_gather_pool_weighted_and_mean():
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.standard_normal((40, 32)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 40, (6, 5)), jnp.int32)
    lens = jnp.asarray(rng.integers(1, 6, (6,)), jnp.int32)
    w = jnp.asarray(rng.standard_normal((6, 5)), jnp.float32)
    for combiner in ("sum", "mean"):
        ref = kops.embedding_bag(table, idx, lens, w, combiner=combiner,
                                 mode="reference")
        out = kops.embedding_bag(table, idx, lens, w, combiner=combiner,
                                 mode="interpret")
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)


def test_rw_partial_masking():
    """Out-of-shard ids contribute zero; shards sum to the full pool."""
    rng = np.random.default_rng(1)
    R, D, B, L, E = 64, 16, 8, 4, 4
    table = jnp.asarray(rng.standard_normal((R, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, R, (B, L)), jnp.int32)
    full = kops.embedding_bag(table, idx, mode="reference")
    acc = jnp.zeros_like(full)
    for e in range(E):
        shard = table[e * (R // E):(e + 1) * (R // E)]
        for mode in ("reference", "interpret"):
            part = kops.embedding_bag_rw_partial(
                shard, e * (R // E), idx, mode=mode)
        acc = acc + part
    np.testing.assert_allclose(np.asarray(acc), np.asarray(full),
                               atol=1e-5, rtol=1e-5)


def test_gather_pool_grad_matches_reference():
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 32, (4, 3)), jnp.int32)
    lens = jnp.asarray([3, 2, 1, 0], jnp.int32)

    def loss(mode):
        def f(t):
            out = kops.embedding_bag(t, idx, lens, mode=mode)
            return jnp.sum(out ** 2)
        return jax.grad(f)(table)

    g_ref = loss("reference")
    g_pal = loss("interpret")
    np.testing.assert_allclose(np.asarray(g_pal), np.asarray(g_ref),
                               atol=1e-4, rtol=1e-4)


def test_onehot_formulation_matches():
    rng = np.random.default_rng(3)
    table = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 16, (5, 3)), jnp.int32)
    lens = jnp.asarray(rng.integers(0, 4, (5,)), jnp.int32)
    a = kref.embedding_bag_ref(table, idx, lens)
    b = kref.embedding_onehot_ref(table, idx, lens)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 40), st.integers(1, 40), st.integers(1, 6),
       st.integers(1, 6), st.randoms())
def test_gather_pool_property(R, D, B, L, pyrng):
    rng = np.random.default_rng(pyrng.randint(0, 2**31))
    table = jnp.asarray(rng.standard_normal((R, D)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, R, (B, L)), jnp.int32)
    lens = jnp.asarray(rng.integers(0, L + 1, (B,)), jnp.int32)
    ref = kops.embedding_bag(table, idx, lens, mode="reference")
    out = kops.embedding_bag(table, idx, lens, mode="interpret")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)
    # invariant: all-padding rows pool to exactly zero
    zero_rows = np.asarray(lens) == 0
    assert np.all(np.asarray(out)[zero_rows] == 0.0)


# ---------------------------------------------------------------------------
# Flash attention kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,S,H,KH,hd,causal,window", [
    (2, 128, 4, 2, 32, True, None),
    (1, 256, 4, 4, 64, True, 64),
    (2, 96, 2, 1, 16, False, None),    # non-block-multiple S
    (1, 64, 8, 2, 128, True, None),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(B, S, H, KH, hd, causal, window, dtype):
    rng = np.random.default_rng(S + hd)
    q = jnp.asarray(rng.standard_normal((B, S, H, hd)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, KH, hd)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, KH, hd)), dtype)
    ref = full_attention(q, k, v, causal=causal, window=window)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          q_block=32, kv_block=32, interpret=True)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


def test_chunked_attention_oracle_matches_full():
    """The kernel's jnp oracle itself must match naive attention."""
    rng = np.random.default_rng(0)
    for S, win in [(130, None), (256, 48)]:
        q = jnp.asarray(rng.standard_normal((2, S, 4, 32)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((2, S, 2, 32)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((2, S, 2, 32)), jnp.float32)
        a = full_attention(q, k, v, causal=True, window=win)
        b = chunked_attention(q, k, v, causal=True, window=win,
                              q_block=64, kv_block=64)
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-5, rtol=2e-5)
