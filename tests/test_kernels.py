"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode) +
the structural ρ-relaxation property of relaxed_topk."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.ops import flash_attention, relaxed_topk
from repro.kernels.ref import attention_ref, exact_topk_ref, relaxed_topk_ref


# ---------------------------------------------------------------------------
# relaxed_topk
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [512, 1000, 4096])
@pytest.mark.parametrize("p,c", [(16, 16), (64, 16), (128, 8)])
def test_relaxed_topk_matches_ref(n, p, c):
    x = jax.random.normal(jax.random.PRNGKey(n + p + c), (n,))
    v, i = relaxed_topk(x, p, c=c, block_size=512)
    vr, ir = relaxed_topk_ref(x, p, c=c, block_size=512)
    np.testing.assert_allclose(np.asarray(v), np.asarray(vr), rtol=1e-6)
    valid = np.asarray(i) >= 0
    np.testing.assert_array_equal(np.asarray(i)[valid], np.asarray(ir)[valid])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_relaxed_topk_exact_when_c_eq_p(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2048,)).astype(dtype)
    v, i = relaxed_topk(x, 32, c=32, block_size=256)
    ve, ie = exact_topk_ref(x, 32)
    np.testing.assert_allclose(
        np.sort(np.asarray(v)), np.sort(np.asarray(ve)), rtol=1e-2
    )


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 1000), p=st.integers(4, 64), c=st.integers(1, 64))
def test_relaxed_topk_rho_property(seed, p, c):
    """Structural ρ-relaxation: at most max(0, p - c) of the exact top p are
    missed, and an item better than the worst selected one is passed over
    only when its own block already gave up min(c, block) better items.

    (Counting every unselected item above the worst selected one is not
    bounded by p - c: with c = 1, the runner-up of a strong block can beat
    the winners of several weak blocks.)"""
    n, block = 2048, 256
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(seed), (n,)))
    v, i = relaxed_topk(jnp.asarray(x), p, c=c, block_size=block)
    idx = np.asarray(i)
    chosen = np.zeros(n, bool)
    chosen[idx[idx >= 0]] = True
    exact = np.argsort(-x, kind="stable")[:p]
    missed = int(np.sum(~chosen[exact]))
    assert missed <= max(0, p - c), (missed, p, c)
    worst = float(np.asarray(v)[idx >= 0].min())
    for j in np.flatnonzero((x > worst) & ~chosen):
        blk = slice(j // block * block, (j // block + 1) * block)
        assert np.sum(chosen[blk] & (x[blk] > x[j])) >= min(c, block), j


def test_relaxed_topk_p_larger_than_n():
    x = jax.random.normal(jax.random.PRNGKey(1), (100,))
    v, i = relaxed_topk(x, 128, c=128, block_size=128)
    assert v.shape == (128,) and i.shape == (128,)
    assert np.all(np.asarray(v)[100:] == -np.inf) or np.isfinite(
        np.asarray(v)[:100]).all()


# ---------------------------------------------------------------------------
# flash_attention
# ---------------------------------------------------------------------------

SWEEP = [
    # (b, h, hkv, sq, skv, d, causal, window)
    (1, 2, 2, 128, 128, 64, True, None),
    (2, 4, 2, 256, 256, 64, True, None),     # GQA
    (1, 4, 1, 128, 128, 32, True, None),     # MQA
    (2, 2, 2, 128, 128, 64, False, None),    # encoder
    (1, 2, 1, 256, 256, 64, True, 64),       # sliding window
    (1, 2, 2, 100, 100, 64, True, None),     # non-multiple padding
]


@pytest.mark.parametrize("b,h,hkv,sq,skv,d,causal,window", SWEEP)
def test_flash_matches_dense(b, h, hkv, sq, skv, d, causal, window):
    ks = jax.random.split(jax.random.PRNGKey(b * sq + h), 3)
    q = jax.random.normal(ks[0], (b, h, sq, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, hkv, skv, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, hkv, skv, d), jnp.float32)
    o = flash_attention(q, k, v, causal=causal, window=window,
                        block_q=64, block_kv=64)
    ref = attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.bfloat16, 2e-2)])
def test_flash_bf16(dtype, tol):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64)).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64)).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64)).astype(dtype)
    o = flash_attention(q, k, v, causal=True).astype(jnp.float32)
    ref = attention_ref(q, k, v, causal=True).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=tol, atol=tol)


def test_flash_block_shape_independence():
    """Result must not depend on tiling (the relaxation lives in relaxed_topk,
    not here)."""
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 2, 256, 64))
    k = jax.random.normal(ks[1], (1, 2, 256, 64))
    v = jax.random.normal(ks[2], (1, 2, 256, 64))
    o1 = flash_attention(q, k, v, block_q=64, block_kv=64)
    o2 = flash_attention(q, k, v, block_q=128, block_kv=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=1e-5, atol=1e-5)


# blockwise XLA attention used by the models must agree with both
def test_blockwise_xla_matches_dense():
    from repro.models.attention import blockwise_attention
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (2, 4, 192, 64))
    k = jax.random.normal(ks[1], (2, 2, 192, 64))
    v = jax.random.normal(ks[2], (2, 2, 192, 64))
    o = blockwise_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
