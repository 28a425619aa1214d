"""Per-kernel allclose sweeps against the pure-jnp oracles in kernels/ref.py
(shape x dtype grid, interpret mode on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(key, shape, jnp.float32)
    return x.astype(dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("n,m,k,blk", [(64, 8, 16, 16), (128, 32, 8, 32),
                                       (96, 16, 16, 32), (256, 64, 64, 64)])
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_lt_mult_sweep(n, m, k, blk, dtype, impl):
    ks = jax.random.split(jax.random.PRNGKey(n + m), 3)
    a = _rand(ks[0], (2, n, m), dtype)
    b = _rand(ks[1], (2, n, m), dtype)
    c = _rand(ks[2], (2, n, k), dtype)
    out = ops.lt_mult(a, b, c, block_size=blk, impl=impl)
    want = ref.lt_mult_ref(a, b, c)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-3
    np.testing.assert_allclose(np.array(out, np.float32),
                               np.array(want, np.float32),
                               atol=tol * n, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("degree", [2, 4, 8])
@pytest.mark.parametrize("local_exact", [True, False])
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_polysketch_causal_sweep(degree, local_exact, dtype, impl):
    B, Hq, Hkv, S, hd, r, blk = 2, 4, 2, 96, 16, 8, 32
    ks = jax.random.split(jax.random.PRNGKey(degree), 5)
    qm = _rand(ks[0], (B, Hq, S, r), dtype) * 0.5
    km = _rand(ks[1], (B, Hkv, S, r), dtype) * 0.5
    q = _rand(ks[2], (B, Hq, S, hd), dtype)
    k = _rand(ks[3], (B, Hkv, S, hd), dtype)
    v = _rand(ks[4], (B, Hkv, S, hd), dtype)
    scale = 1.0 / hd
    out = ops.polysketch_attention(qm, km, q, k, v, degree=degree,
                                   scale=scale, local_exact=local_exact,
                                   block_size=blk, impl=impl)
    g = Hq // Hkv
    want = ref.polysketch_causal_ref(
        qm, jnp.repeat(km, g, 1), q, jnp.repeat(k, g, 1),
        jnp.repeat(v, g, 1), degree=degree, scale=scale, block_size=blk,
        local_exact=local_exact)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.array(out, np.float32),
                               np.array(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("degree", [4, 8])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_poly_flash_sweep(degree, causal, dtype, impl):
    B, H, S, hd = 2, 2, 128, 16
    ks = jax.random.split(jax.random.PRNGKey(degree + causal), 3)
    q = _rand(ks[0], (B, H, S, hd), dtype)
    k = _rand(ks[1], (B, H, S, hd), dtype)
    v = _rand(ks[2], (B, H, S, hd), dtype)
    out = ops.poly_attention(q, k, v, degree=degree, scale=1.0 / hd,
                             causal=causal, block_q=32, block_kv=32,
                             impl=impl)
    want = ref.poly_flash_ref(q, k, v, degree=degree, scale=1.0 / hd,
                              causal=causal)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.array(out, np.float32),
                               np.array(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("causal", [True, False])
def test_poly_flash_unaligned_seq_padding(causal):
    """The Pallas path pads q/k/v to whole blocks with zero keys."""
    B, H, S, hd = 1, 2, 77, 16
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (_rand(kk, (B, H, S, hd), jnp.float32) for kk in ks)
    out = ops.poly_attention(q, k, v, degree=4, scale=1.0 / hd,
                             causal=causal, block_q=32, block_kv=32,
                             impl="interpret")
    want = ref.poly_flash_ref(q, k, v, degree=4, scale=1.0 / hd,
                              causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)


# Seeded stand-in for the former hypothesis property test: a fixed sweep
# over (n, blk, seed) drawn from the same strategy space.
@pytest.mark.parametrize("n,blk", [(32, 16), (32, 32), (64, 16), (64, 32),
                                   (96, 16), (96, 32)])
@pytest.mark.parametrize("seed", [0, 271, 828])
def test_lt_mult_property(n, blk, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    a = jax.random.normal(ks[0], (1, n, 8))
    b = jax.random.normal(ks[1], (1, n, 8))
    c = jax.random.normal(ks[2], (1, n, 4))
    out = ops.lt_mult(a, b, c, block_size=blk, impl="interpret")
    want = ref.lt_mult_ref(a, b, c)
    np.testing.assert_allclose(np.array(out), np.array(want),
                               atol=1e-3, rtol=1e-3)


def test_polysketch_unaligned_seq_padding():
    """Pallas path pads to a block multiple with zero keys."""
    B, H, S, hd, r = 1, 2, 77, 16, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    qm, km = (jax.random.normal(k, (B, H, S, r)) for k in ks[:2])
    q, k, v = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks[2:])
    out = ops.polysketch_attention(qm, km, q, k, v, degree=4, scale=1.0 / hd,
                                   block_size=32, impl="interpret")
    want = ref.polysketch_causal_ref(qm, km, q, k, v, degree=4,
                                     scale=1.0 / hd, block_size=32)
    np.testing.assert_allclose(np.array(out), np.array(want), atol=1e-4)


@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)])
@pytest.mark.parametrize("impl", ["xla", "interpret"])
def test_polysketch_resume_from_state_matches_full(impl, hq, hkv):
    """Splitting a sequence at a block boundary and resuming the second part
    with z0 = the first part's returned state reproduces the one-shot run —
    on both the jnp block path and the Pallas kernel."""
    B, S, hd, r, blk, cut = 2, 96, 16, 8, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    qm = _rand(ks[0], (B, hq, S, r), jnp.float32) * 0.5
    km = _rand(ks[1], (B, hkv, S, r), jnp.float32) * 0.5
    q = _rand(ks[2], (B, hq, S, hd), jnp.float32)
    k = _rand(ks[3], (B, hkv, S, hd), jnp.float32)
    v = _rand(ks[4], (B, hkv, S, hd), jnp.float32)
    kw = dict(degree=4, scale=1.0 / hd, block_size=blk, impl=impl)
    out_full, z_full = ops.polysketch_attention(qm, km, q, k, v,
                                                return_state=True, **kw)
    c = lambda x: x[..., :cut, :]
    s = lambda x: x[..., cut:, :]
    o1, z1 = ops.polysketch_attention(c(qm), c(km), c(q), c(k), c(v),
                                      return_state=True, **kw)
    o2, z2 = ops.polysketch_attention(s(qm), s(km), s(q), s(k), s(v),
                                      z0=z1, return_state=True, **kw)
    got = jnp.concatenate([o1, o2], axis=-2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(out_full),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(z2), np.asarray(z_full),
                               atol=1e-4, rtol=1e-5)


def test_kernel_grid_state_reset_between_heads():
    """Scratch prefix state must reset at t==0 for every (batch, head)."""
    B, H, S, hd, r = 1, 3, 64, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    qm, km = (jax.random.normal(k, (B, H, S, r)) for k in ks[:2])
    q, k, v = (jax.random.normal(kk, (B, H, S, hd)) for kk in ks[2:])
    out = ops.polysketch_attention(qm, km, q, k, v, degree=4, scale=1.0 / hd,
                                   block_size=16, impl="interpret")
    # head 2 computed alone must match head 2 computed in the batch
    out_solo = ops.polysketch_attention(
        qm[:, 2:], km[:, 2:], q[:, 2:], k[:, 2:], v[:, 2:], degree=4,
        scale=1.0 / hd, block_size=16, impl="interpret")
    np.testing.assert_allclose(np.array(out[:, 2:]), np.array(out_solo),
                               atol=1e-5)


@pytest.mark.parametrize("local_exact", [True, False])
def test_polysketch_row_tiles_match_reference(local_exact):
    """Blocks wider than the kernel's 256-row tile are walked tile by tile
    (the published block is 1024); outputs and the returned state must
    match the oracle and the XLA block path."""
    B, H, S, hd, r, blk = 1, 1, 1024, 8, 4, 512
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    qm, km = (_rand(k, (B, H, S, r), jnp.float32) * 0.5 for k in ks[:2])
    q, k, v = (_rand(kk, (B, H, S, hd), jnp.float32) for kk in ks[2:])
    kw = dict(degree=4, scale=1.0 / hd, block_size=blk,
              local_exact=local_exact, return_state=True)
    out, z = ops.polysketch_attention(qm, km, q, k, v, impl="interpret",
                                      **kw)
    _, z_xla = ops.polysketch_attention(qm, km, q, k, v, impl="xla", **kw)
    want = ref.polysketch_causal_ref(qm, km, q, k, v, degree=4,
                                     scale=1.0 / hd, block_size=blk,
                                     local_exact=local_exact)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(z), np.asarray(z_xla),
                               atol=1e-4, rtol=1e-5)


def test_kernel_path_follows_the_platform(monkeypatch):
    """impl=None is the platform's own path; interpret only on request."""
    assert ops.resolve_impl(None) == "xla"  # the CPU test backend
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ops.resolve_impl(None) == "pallas"
    assert ops.resolve_impl("interpret") == "interpret"
    with pytest.raises(ValueError):
        ops.resolve_impl("mosaic")
