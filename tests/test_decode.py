"""core/decode.py block-boundary edge cases: prefill at s % blk == 0,
s < blk, and GQA all agree with the training block algorithm; a fold at
exactly fill == blk - 1 matches the training path; sketch_param_count
matches the real parameter tree; slot-stack cache helpers round-trip."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (block_causal_linear_attention, init_polysketch_cache,
                        init_sketch, polysketch_decode_step, polysketch_fold,
                        polysketch_prefill, qk_layernorm,
                        sketch_param_count)
from repro.core.decode import (broadcast_slot_caches, init_kv_cache,
                               init_ring_cache, kv_ring_decode_step,
                               kv_ring_prefill, ring_grid, slot_gather,
                               slot_scatter)
from repro.core.sketches import sketch_half
from repro.utils import param_count

BLK = 16


def _mh_setup(seed=0, bsz=2, hq=2, hkv=2, n=48, h=16, r=8, p=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = qk_layernorm(jax.random.normal(ks[0], (bsz, hq, n, h)), None, None)
    k = qk_layernorm(jax.random.normal(ks[1], (bsz, hkv, n, h)), None, None)
    v = jax.random.normal(ks[2], (bsz, hkv, n, h))
    sp, _ = init_sketch(ks[3], h, r, p, learned=False)
    scale = 1.0 / h
    rt = np.sqrt(scale)
    qm = sketch_half(sp, q * rt, p, False)
    km = sketch_half(sp, k * rt, p, False)
    return q, k, v, qm, km, scale


def _train_ref(qm, km, v, q, k, scale):
    """Full-sequence training block algorithm with GQA heads repeated."""
    g = q.shape[1] // k.shape[1]
    rep = lambda x: jnp.repeat(x, g, axis=1) if g > 1 else x
    return np.asarray(block_causal_linear_attention(
        qm, rep(km), rep(v), q, rep(k), degree=4, scale=scale,
        block_size=BLK))


@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2), (4, 1)])
@pytest.mark.parametrize("s0", [7,            # s < blk: all-partial buffer
                                BLK - 1,      # next decode step folds
                                BLK,          # s % blk == 0: empty buffer
                                2 * BLK])     # multi-block, empty buffer
def test_prefill_boundary_then_decode_matches_train(s0, hq, hkv):
    """prefill(s0) output == training[:s0] and the cache it leaves behind
    continues decoding to the exact training outputs — across both block
    boundaries following s0 (including the fold at fill == blk - 1)."""
    n, h, r = 48, 16, 8
    q, k, v, qm, km, scale = _mh_setup(seed=s0 + hq, hq=hq, hkv=hkv, n=n,
                                       h=h, r=r)
    full = _train_ref(qm, km, v, q, k, scale)

    cache = init_polysketch_cache(q.shape[0], hkv, h, r, BLK)
    out0, cache = polysketch_prefill(
        cache, qm[:, :, :s0], km[:, :, :s0], q[:, :, :s0], k[:, :, :s0],
        v[:, :, :s0], degree=4, scale=scale)
    np.testing.assert_allclose(np.asarray(out0), full[:, :, :s0], atol=1e-4)
    assert int(cache.pos) == s0

    outs = []
    for t in range(s0, n):
        o, cache = polysketch_decode_step(
            cache, qm[:, :, t], km[:, :, t], q[:, :, t], k[:, :, t],
            v[:, :, t], degree=4, scale=scale)
        cache = polysketch_fold(cache)
        outs.append(np.asarray(o))
    np.testing.assert_allclose(np.stack(outs, axis=2),
                               full[:, :, s0:], atol=1e-4)
    assert int(cache.pos) == n


@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)])
@pytest.mark.parametrize("suffix", [BLK, BLK + 5, 3])
def test_prefill_resume_bit_equals_cold_prefill(suffix, hq, hkv):
    """A prefill resumed from a block-aligned cache (z + pos, empty buffers)
    is bit-identical to the cold prefill of the concatenated sequence —
    outputs AND final cache (the prefix-cache snapshot contract)."""
    n0 = 2 * BLK
    n = n0 + suffix
    q, k, v, qm, km, scale = _mh_setup(seed=suffix + hq, hq=hq, hkv=hkv, n=n)
    bsz = q.shape[0]

    cold = init_polysketch_cache(bsz, hkv, 16, 8, BLK)
    out_cold, cold = polysketch_prefill(
        cache=cold, qm=qm, km=km, q=q, k=k, v=v, degree=4, scale=scale)

    c1 = init_polysketch_cache(bsz, hkv, 16, 8, BLK)
    _, c1 = polysketch_prefill(
        cache=c1, qm=qm[:, :, :n0], km=km[:, :, :n0], q=q[:, :, :n0],
        k=k[:, :, :n0], v=v[:, :, :n0], degree=4, scale=scale)
    # snapshot = z + pos only; buffers are empty at the block boundary
    resumed = init_polysketch_cache(bsz, hkv, 16, 8, BLK)._replace(
        z=c1.z, pos=c1.pos)
    out_res, resumed = polysketch_prefill(
        cache=resumed, qm=qm[:, :, n0:], km=km[:, :, n0:], q=q[:, :, n0:],
        k=k[:, :, n0:], v=v[:, :, n0:], degree=4, scale=scale)

    assert jnp.array_equal(out_res, out_cold[:, :, n0:])
    for got, want in zip(resumed, cold):
        assert jnp.array_equal(got, want)


def test_kv_ring_wraparound_matches_windowed_reference():
    """After pos > window the ring rotates; outputs must keep matching a
    sliding-window softmax over the last W tokens computed from scratch."""
    W, steps, hq, hkv, h = 8, 21, 4, 2, 16
    g = hq // hkv
    ks = jax.random.split(jax.random.PRNGKey(6), 3)
    qs = jax.random.normal(ks[0], (steps, 1, hq, h))
    kks = jax.random.normal(ks[1], (steps, 1, hkv, h))
    vs = jax.random.normal(ks[2], (steps, 1, hkv, h))
    scale = 1.0 / np.sqrt(h)

    cache = init_kv_cache(1, hkv, h, W)
    for t in range(steps):
        out, cache = kv_ring_decode_step(cache, qs[t], kks[t], vs[t])
        lo = max(0, t - W + 1)
        kw = jnp.repeat(kks[lo:t + 1], g, axis=2)      # (w, 1, hq, h)
        vw = jnp.repeat(vs[lo:t + 1], g, axis=2)
        logits = jnp.einsum("bnh,sbnh->bns", qs[t], kw) * scale
        ref = jnp.einsum("bns,sbnh->bnh", jax.nn.softmax(logits, -1), vw)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, err_msg=f"step {t}")
    assert int(cache.pos) == steps


@pytest.mark.parametrize("hq,hkv", [(2, 2), (4, 2)])
def test_kv_ring_prefill_matches_decode_loop(hq, hkv):
    """The fixed-lattice ring prefill agrees with the token-by-token ring
    decode (same sliding window, same ring layout), including GQA and
    prompts that wrap the ring several times."""
    W, S, h = 8, 21, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (1, hq, S, h))
    k = jax.random.normal(ks[1], (1, hkv, S, h))
    v = jax.random.normal(ks[2], (1, hkv, S, h))
    cache = init_ring_cache(1, hkv, h, W)
    refs = []
    for t in range(S):
        out, cache = kv_ring_decode_step(cache, q[:, :, t], k[:, :, t],
                                         v[:, :, t])
        refs.append(out)
    ref = jnp.stack(refs, axis=2)
    grid = ring_grid(BLK, W)
    out, rc = kv_ring_prefill(init_ring_cache(1, hkv, h, W), q, k, v,
                              grid=grid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
    assert int(rc.pos) == S
    np.testing.assert_allclose(np.asarray(rc.k), np.asarray(cache.k),
                               atol=1e-6)


def test_kv_ring_prefill_resume_bit_exact():
    """Resuming the ring prefill at any lattice-aligned cut is BIT-equal to
    the cold prefill of the full segment — outputs, ring contents, pos
    (the snapshot/resume contract kv_ring's token granularity rests on)."""
    W, S, hq, hkv, h = 8, 37, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q = jax.random.normal(ks[0], (1, hq, S, h))
    k = jax.random.normal(ks[1], (1, hkv, S, h))
    v = jax.random.normal(ks[2], (1, hkv, S, h))
    grid = ring_grid(BLK, W)
    out_cold, cold = kv_ring_prefill(init_ring_cache(1, hkv, h, W), q, k, v,
                                     grid=grid)
    for cut in (grid, 2 * grid, 4 * grid):
        _, c1 = kv_ring_prefill(init_ring_cache(1, hkv, h, W),
                                q[:, :, :cut], k[:, :, :cut], v[:, :, :cut],
                                grid=grid)
        out_res, c2 = kv_ring_prefill(c1, q[:, :, cut:], k[:, :, cut:],
                                      v[:, :, cut:], grid=grid)
        assert bool(jnp.array_equal(out_res, out_cold[:, :, cut:])), cut
        for a, b in zip(c2, cold):
            assert bool(jnp.array_equal(a, b)), cut


def test_ring_grid_divides_block_and_fits_window():
    assert ring_grid(16, 32) == 16     # block fits: lattice == block
    assert ring_grid(16, 8) == 8       # largest divisor of 16 <= 8
    assert ring_grid(48, 32) == 24
    assert ring_grid(16, 5) == 4
    assert ring_grid(7, 2) == 1        # degenerate: token lattice


def test_fold_at_block_edge_updates_prefix_state():
    """The decode step at fill == blk - 1 completes the block, and the
    fold after it folds the block into z; the next step then attends to it
    only through the sketch."""
    n = 2 * BLK
    q, k, v, qm, km, scale = _mh_setup(seed=9, n=n)
    cache = init_polysketch_cache(2, 2, 16, 8, BLK)
    _, cache = polysketch_prefill(
        cache, qm[:, :, :BLK - 1], km[:, :, :BLK - 1], q[:, :, :BLK - 1],
        k[:, :, :BLK - 1], v[:, :, :BLK - 1], degree=4, scale=scale)
    assert float(jnp.abs(cache.z).max()) == 0.0  # nothing folded yet
    _, cache = polysketch_decode_step(
        cache, qm[:, :, BLK - 1], km[:, :, BLK - 1], q[:, :, BLK - 1],
        k[:, :, BLK - 1], v[:, :, BLK - 1], degree=4, scale=scale)
    assert float(jnp.abs(cache.z).max()) == 0.0  # the step itself folds not
    cache = polysketch_fold(cache)
    assert float(jnp.abs(cache.z).max()) > 0.0   # block folded exactly here


@pytest.mark.parametrize("degree", [2, 4, 8])
@pytest.mark.parametrize("learned", [False, True])
def test_sketch_param_count_matches_init(degree, learned):
    h, r = 16, 8
    params, _ = init_sketch(jax.random.PRNGKey(0), h, r, degree, learned)
    assert sketch_param_count(h, r, degree, learned) == param_count(params)


def test_slot_cache_helpers_roundtrip():
    """broadcast -> scatter -> gather preserves per-slot cache contents,
    including the per-slot scalar pos; the slot axis takes the batch
    axis's place."""
    cache = init_polysketch_cache(1, 2, 16, 8, BLK)
    slot_caches = broadcast_slot_caches(cache, 3)
    assert slot_caches.pos.shape == (3,)
    assert slot_caches.kbuf.shape == (3, 2, BLK, 16)

    filled = cache._replace(
        kbuf=jnp.ones_like(cache.kbuf), pos=jnp.asarray(5, jnp.int32))
    slot_caches = slot_scatter(slot_caches, filled, jnp.asarray(1, jnp.int32))
    # target slot holds the new state ...
    got = slot_gather(slot_caches, jnp.asarray(1, jnp.int32))
    np.testing.assert_array_equal(np.asarray(got.kbuf),
                                  np.asarray(filled.kbuf))
    assert int(got.pos) == 5
    # ... and neighbours were untouched
    other = slot_gather(slot_caches, jnp.asarray(0, jnp.int32))
    assert float(jnp.abs(other.kbuf).max()) == 0.0
    assert int(other.pos) == 0
