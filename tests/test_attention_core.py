"""Core attention invariants: block algorithm == naive; decode == train;
prefill == train; polynomial attention behavior (S2.1)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (block_causal_linear_attention, init_polysketch_cache,
                        init_sketch, noncausal_linear_attention,
                        poly_attention_full, polysketch_decode_step,
                        polysketch_fold, polysketch_prefill, qk_layernorm)
from repro.core.sketches import sketch_half


def _setup(seed=0, n=64, h=16, r=8, p=4, blk=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = qk_layernorm(jax.random.normal(ks[0], (n, h)), None, None)
    k = qk_layernorm(jax.random.normal(ks[1], (n, h)), None, None)
    v = jax.random.normal(ks[2], (n, h))
    sp, _ = init_sketch(ks[3], h, r, p, learned=False)
    scale = 1.0 / h
    rt = np.sqrt(scale)
    qm = sketch_half(sp, q * rt, p, False)
    km = sketch_half(sp, k * rt, p, False)
    return q, k, v, qm, km, scale


def _naive(qm, km, q, k, v, p, scale, blk, local):
    n = q.shape[0]
    sk = np.array((qm @ km.T)) ** 2
    ex = (np.array(q @ k.T) * scale) ** p if local else sk
    w = np.zeros((n, n), np.float64)
    for i in range(n):
        for j in range(i + 1):
            w[i, j] = ex[i, j] if i // blk == j // blk else sk[i, j]
    return (w @ np.array(v, np.float64)) / (1 + w.sum(1))[:, None]


@pytest.mark.parametrize("local", [True, False])
@pytest.mark.parametrize("blk", [8, 16, 64])
def test_block_algorithm_matches_naive(local, blk):
    q, k, v, qm, km, scale = _setup()
    out = block_causal_linear_attention(
        qm[None, None], km[None, None], v[None, None], q[None, None],
        k[None, None], degree=4, scale=scale, block_size=blk,
        local_exact=local)
    want = _naive(qm, km, q, k, v, 4, scale, blk, local)
    np.testing.assert_allclose(np.array(out[0, 0]), want, atol=1e-4)


@pytest.mark.parametrize("local", [True, False])
def test_decode_matches_train_exactly(local):
    """The paper's training block semantics == our streaming decode."""
    q, k, v, qm, km, scale = _setup(n=48, blk=16)
    blk = 16
    train_out = np.array(block_causal_linear_attention(
        qm[None, None], km[None, None], v[None, None], q[None, None],
        k[None, None], degree=4, scale=scale, block_size=blk,
        local_exact=local)[0, 0])
    cache = init_polysketch_cache(1, 1, 16, 8, blk)
    outs = []
    for t in range(48):
        o, cache = polysketch_decode_step(
            cache, qm[None, t:t + 1], km[None, t:t + 1], q[None, t:t + 1],
            k[None, t:t + 1], v[None, t:t + 1], degree=4, scale=scale,
            local_exact=local)
        cache = polysketch_fold(cache)
        outs.append(np.array(o[0, 0]))
    np.testing.assert_allclose(np.stack(outs), train_out, atol=1e-4)


@pytest.mark.parametrize("s0", [16, 24, 40, 48])
def test_prefill_then_decode_matches_full(s0):
    """prefill(s0) + decode(rest) == full training forward."""
    n, blk = 64, 16
    q, k, v, qm, km, scale = _setup(n=n, blk=blk)
    full = np.array(block_causal_linear_attention(
        qm[None, None], km[None, None], v[None, None], q[None, None],
        k[None, None], degree=4, scale=scale, block_size=blk)[0, 0])
    cache = init_polysketch_cache(1, 1, 16, 8, blk)
    out0, cache = polysketch_prefill(
        cache, qm[None, None, :s0], km[None, None, :s0], q[None, None, :s0],
        k[None, None, :s0], v[None, None, :s0], degree=4, scale=scale)
    np.testing.assert_allclose(np.array(out0[0, 0]), full[:s0], atol=1e-4)
    outs = []
    for t in range(s0, n):
        o, cache = polysketch_decode_step(
            cache, qm[None, t:t + 1], km[None, t:t + 1], q[None, t:t + 1],
            k[None, t:t + 1], v[None, t:t + 1], degree=4, scale=scale)
        cache = polysketch_fold(cache)
        outs.append(np.array(o[0, 0]))
    np.testing.assert_allclose(np.stack(outs), full[s0:], atol=1e-4)


def test_noncausal_linear_attention():
    q, k, v, qm, km, scale = _setup()
    out = np.array(noncausal_linear_attention(qm, km, v))
    w = np.array((qm @ km.T)) ** 2
    want = (w @ np.array(v)) / (1 + w.sum(1))[:, None]
    np.testing.assert_allclose(out, want, atol=1e-4)


def test_poly_attention_interpolates_to_argmax():
    """S2.1: as p grows, polynomial attention concentrates on the argmax key."""
    rng = np.random.default_rng(0)
    q = jnp.array(rng.normal(size=(1, 4, 8)), jnp.float32)
    k = jnp.array(rng.normal(size=(1, 16, 8)), jnp.float32)
    v = jnp.eye(16)[None].astype(jnp.float32)  # one-hot value per key
    sims = np.array(jnp.einsum("bsh,bth->bst", q, k))[0]
    argmax = np.abs(sims).argmax(1)  # even powers act on |<q,k>|
    # beta (the paper's smoothness scale) keeps x^p in range; A is invariant
    out = poly_attention_full(q, k, v, degree=32, causal=False,
                              scale=float(1.0 / np.abs(sims).max()))
    picked = np.array(out[0]).argmax(1)
    assert (picked == argmax).mean() >= 0.75


def test_poly_attention_gqa_and_mask():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 3, 10, 8))
    k = jax.random.normal(ks[1], (2, 3, 10, 8))
    v = jax.random.normal(ks[2], (2, 3, 10, 8))
    out = np.array(poly_attention_full(q, k, v, degree=4, causal=True))
    # causal: first position attends only to itself
    w00 = (float(jnp.einsum("h,h->", q[0, 0, 0], k[0, 0, 0])) / 8) ** 4
    want0 = w00 / (1 + w00) * np.array(v[0, 0, 0])
    np.testing.assert_allclose(out[0, 0, 0], want0, atol=1e-5)
