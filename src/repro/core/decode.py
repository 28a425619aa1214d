"""Decode-time attention states.

The headline inference property of PolySketchFormer: the decode state is
O(1) in context length (an r^2 x (h+1) prefix matrix per kv-head plus one
partial block buffer), vs an O(n) KV cache for softmax attention.

The polysketch decode step is *bit-equivalent in semantics* to the training
block algorithm (linear_attention.block_causal_linear_attention): a token
attends exactly (degree-p polynomial weights) to tokens in its own block so
far, and through the sketched prefix state to all earlier, completed blocks.
When the buffer fills, the whole block is folded into the prefix state.

All caches here are per-layer pytrees; the model stacks them over layers.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import self_kron


class PolysketchCache(NamedTuple):
    z: jax.Array      # (B, Hkv, r^2, h+1) f32 prefix state over folded blocks
    kbuf: jax.Array   # (B, Hkv, b, h)     raw keys, current partial block
    vbuf: jax.Array   # (B, Hkv, b, h)
    mbuf: jax.Array   # (B, Hkv, b, r)     sketched keys, current partial block
    pos: jax.Array    # ()                 int32 tokens consumed so far


def init_polysketch_cache(batch, n_kv_heads, head_dim, r, block_size,
                          dtype=jnp.float32) -> PolysketchCache:
    b = block_size
    return PolysketchCache(
        z=jnp.zeros((batch, n_kv_heads, r * r, head_dim + 1), jnp.float32),
        kbuf=jnp.zeros((batch, n_kv_heads, b, head_dim), dtype),
        vbuf=jnp.zeros((batch, n_kv_heads, b, head_dim), dtype),
        mbuf=jnp.zeros((batch, n_kv_heads, b, r), jnp.float32),
        pos=jnp.zeros((), jnp.int32),
    )


def polysketch_decode_step(cache: PolysketchCache, qm, km, q, k, v, *,
                           degree: int, scale: float,
                           local_exact: bool = True, fold_due=None):
    """One decode step.

    qm: (B, Hq, r)  sketched query (input pre-scaled by sqrt(scale))
    km: (B, Hkv, r) sketched key
    q:  (B, Hq, h)  post-LN query;  k, v: (B, Hkv, h)
    fold_due: optional unbatched scalar bool gating the fold: true when
      some cache of the batch this step is vmapped over completes its
      block (`polysketch_fold_due`); None folds on this cache's own
      position alone.
    Returns (out (B, Hq, h), new_cache).
    """
    bsz, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    blk = cache.kbuf.shape[2]
    fill = jnp.mod(cache.pos, blk)  # slot for the incoming token

    f32 = jnp.float32
    kbuf = jax.lax.dynamic_update_index_in_dim(cache.kbuf, k.astype(cache.kbuf.dtype), fill, axis=2)
    vbuf = jax.lax.dynamic_update_index_in_dim(cache.vbuf, v.astype(cache.vbuf.dtype), fill, axis=2)
    mbuf = jax.lax.dynamic_update_index_in_dim(cache.mbuf, km.astype(f32), fill, axis=2)

    # --- local (within current partial block) attention weights ---
    qg = q.reshape(bsz, hkv, g, hd).astype(f32)
    qmg = qm.reshape(bsz, hkv, g, -1).astype(f32)
    if local_exact:
        w = (jnp.einsum("bngh,bnsh->bngs", qg, kbuf.astype(f32)) * scale) ** degree
    else:
        w = jnp.einsum("bngr,bnsr->bngs", qmg, mbuf) ** 2
    valid = (jnp.arange(blk) <= fill)[None, None, None, :]
    w = jnp.where(valid, w, 0.0)

    def values_and_ones():                                     # (B,Hkv,blk,h+1)
        ones = jnp.ones((*vbuf.shape[:-1], 1), f32)
        return jnp.concatenate([vbuf.astype(f32), ones], axis=-1)

    local = jnp.einsum("bngs,bnsd->bngd", w, values_and_ones())

    # --- sketched prefix (folded blocks) ---
    qf = self_kron(qmg)                                        # (B,Hkv,g,r^2)
    cross = jnp.einsum("bngf,bnfd->bngd", qf, cache.z)

    acc = local + cross
    out = (acc[..., :hd] / (1.0 + acc[..., hd:])).reshape(bsz, hq, hd)

    # --- fold the block into the prefix state when it completes ---
    def fold(z):
        # [v, 1] is built again inside the branch: taken from outside it
        # would be an operand of the cond, written out on every step
        kf = self_kron(mbuf)                                   # (B,Hkv,blk,r^2)
        return z + jnp.einsum("bnsf,bnsd->bnfd", kf, values_and_ones())

    # the scope holds the cond itself. Vmapped over serve slots, a cond on
    # this cache's own `fill` is batched and lowers to a select that folds
    # every slot on every step; an unbatched `fold_due` keeps it a real
    # branch, and the due step selects per slot exactly as that select did
    completes = fill == blk - 1
    pred = completes if fold_due is None else fold_due
    with jax.named_scope("fold"):
        z = jax.lax.cond(pred, lambda z: jnp.where(completes, fold(z), z),
                         lambda z: z, cache.z)
    new_cache = PolysketchCache(z=z, kbuf=kbuf, vbuf=vbuf, mbuf=mbuf,
                                pos=cache.pos + 1)
    return out.astype(v.dtype), new_cache


def polysketch_fold_due(cache: PolysketchCache):
    """Scalar bool: the next decode step completes a block, and folds it,
    at some position of `cache.pos` (any leading stacking: slots,
    layers)."""
    blk = cache.kbuf.shape[-2]
    return jnp.any(jnp.mod(cache.pos, blk) == blk - 1)


class RecurrentCache(NamedTuple):
    """Constant-size recurrent decode state (SSM / RG-LRU mixers).

    Unlike PolysketchCache there is no partial-block buffer: `h` is the
    exact state after every token consumed so far, so a snapshot is valid
    at ANY position (token granularity) — but only bit-reproducible at the
    lt_block_size chunk grid the prefill scan runs on (see models/ssm.py).
    Position is tracked by the caller (the serve engine's per-slot pos);
    the node itself is position-free.
    """
    h: jax.Array     # (B, ...) f32 recurrent state (SSD: (B,H,N,P); RG-LRU: (B,W))
    conv: jax.Array  # (B, K-1, C) trailing conv inputs


class KVCache(NamedTuple):
    k: jax.Array    # (B, Hkv, S_max, h)
    v: jax.Array    # (B, Hkv, S_max, h)
    pos: jax.Array  # ()


class RingKVCache(NamedTuple):
    """Sliding-window ring KV state (local_attn mixers).

    Same field layout as KVCache but a distinct *type*: the ring holds the
    last min(pos, W) tokens' post-RoPE k/v at slot = absolute_pos % W, so
    the whole node is a constant-size O(W) suffix-window snapshot — unlike
    the append-only KVCache whose buffers grow with max_len and admit no
    constant-size snapshot. core.state dispatches snapshot ops by node
    type, which is why the ring gets its own.
    """
    k: jax.Array    # (B, Hkv, W, h)
    v: jax.Array    # (B, Hkv, W, h)
    pos: jax.Array  # ()


def init_kv_cache(batch, n_kv_heads, head_dim, max_len, dtype=jnp.float32) -> KVCache:
    shape = (batch, n_kv_heads, max_len, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   pos=jnp.zeros((), jnp.int32))


def init_ring_cache(batch, n_kv_heads, head_dim, window,
                    dtype=jnp.float32) -> RingKVCache:
    shape = (batch, n_kv_heads, window, head_dim)
    return RingKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                       pos=jnp.zeros((), jnp.int32))


def kv_ring_decode_step(cache: KVCache, q, k, v, *, scale: float | None = None):
    """Sliding-window softmax decode with a ring buffer of size W=max_len.

    The cache stores post-RoPE keys, so ring rotation does not disturb
    relative positions. q: (B, Hq, h); k, v: (B, Hkv, h).
    """
    bsz, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    w = cache.k.shape[2]
    slot = jnp.mod(cache.pos, w)
    kc = jax.lax.dynamic_update_index_in_dim(cache.k, k.astype(cache.k.dtype), slot, axis=2)
    vc = jax.lax.dynamic_update_index_in_dim(cache.v, v.astype(cache.v.dtype), slot, axis=2)
    qg = q.reshape(bsz, hkv, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bngh,bnsh->bngs", qg, kc.astype(jnp.float32)) * scale
    valid = jnp.arange(w) <= cache.pos  # until the ring is full
    logits = jnp.where(valid[None, None, None, :], logits, jnp.finfo(jnp.float32).min)
    wts = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngs,bnsh->bngh", wts, vc.astype(jnp.float32))
    return (out.reshape(bsz, hq, hd).astype(v.dtype),
            type(cache)(kc, vc, cache.pos + 1))


def ring_grid(block_size: int, window: int) -> int:
    """Sub-block lattice for kv_ring prefill: the largest divisor of the
    resume grid (lt_block_size) that fits the ring. Divisibility keeps any
    block-aligned resume on the same lattice as a cold prefill (the
    bit-exactness contract); <= window keeps one sub-block's ring writes on
    distinct slots."""
    g = min(block_size, window)
    while block_size % g:
        g -= 1
    return g


def kv_ring_prefill(cache: RingKVCache, q, k, v, *, grid: int,
                    scale: float | None = None):
    """Sliding-window softmax prefill resuming from a ring cache.

    q: (B, Hq, S, h); k, v: (B, Hkv, S, h) — post-RoPE, at absolute
    positions cache.pos .. cache.pos + S - 1. Returns (out (B, Hq, S, h),
    new RingKVCache covering cache.pos + S tokens).

    The segment is processed on a fixed `grid`-token sub-block lattice
    anchored at absolute position 0 (`grid` from ring_grid; cache.pos must
    be a lattice multiple): each scan step attends its sub-block's queries
    over [ring (W), sub-block (grid)] with fixed shapes and masks derived
    only from the sub-block's base position, then writes the sub-block's
    k/v into ring slots (base + i) % W. Every sub-block's arithmetic is
    therefore independent of where the call started, so a prefill resumed
    from a snapshot at any lattice-aligned cut is bit-identical to the
    cold prefill of the full concatenated prompt — the DecodeState
    snapshot contract that unlocks prefix reuse for ring-window models.
    """
    bsz, hq, s, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    w = cache.k.shape[2]
    if grid > w:
        raise ValueError(f"grid({grid}) must be <= ring window({w})")
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    f32 = jnp.float32
    nb = -(-s // grid)
    pad = nb * grid - s

    def blocks(x):  # (B, H*, S, h) -> (nb, B, H*, grid, h)
        if pad:
            x = jnp.concatenate(
                [x, jnp.zeros((*x.shape[:2], pad, x.shape[-1]), x.dtype)],
                axis=2)
        return jnp.moveaxis(
            x.reshape(*x.shape[:2], nb, grid, x.shape[-1]), 2, 0)

    qb = blocks(q)                                    # (nb, B, Hq, grid, h)
    kb, vb = blocks(k), blocks(v)
    bases = cache.pos + jnp.arange(nb, dtype=jnp.int32) * grid
    nval = jnp.minimum(jnp.asarray(s, jnp.int32) - jnp.arange(nb) * grid,
                       grid)

    i = jnp.arange(grid)                              # query local index
    j2 = jnp.arange(grid)                             # sub-block key index
    t = jnp.arange(w)                                 # ring slot index

    def step(carry, xs):
        rk, rv = carry
        qj, kj, vj, base, nv = xs
        p = base + i                                  # absolute query pos
        # latest absolute position written to ring slot t (negative if the
        # slot was never written): the unique value in [base - W, base - 1]
        # congruent to t mod W
        abs_t = base - w + jnp.mod(t - base, w)
        ring_ok = (abs_t[None, :] >= 0) & (abs_t[None, :] > p[:, None] - w)
        loc_ok = ((j2[None, :] <= i[:, None])
                  & (j2[None, :] > i[:, None] - w)
                  & (j2[None, :] < nv))
        mask = jnp.concatenate([ring_ok, loc_ok], axis=1)  # (grid, W+grid)
        kcat = jnp.concatenate([rk, kj.astype(rk.dtype)], axis=2).astype(f32)
        vcat = jnp.concatenate([rv, vj.astype(rv.dtype)], axis=2).astype(f32)
        qg = qj.reshape(bsz, hkv, g, grid, hd).astype(f32)
        logits = jnp.einsum("bngqh,bnkh->bngqk", qg, kcat) * scale
        logits = jnp.where(mask[None, None, None], logits,
                           jnp.finfo(f32).min)
        wts = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bngqk,bnkh->bngqh", wts, vcat)
        # masked ring write: padded tail positions must not clobber slots
        # still holding live (older) tokens
        slots = jnp.mod(p, w)                         # distinct (grid <= W)
        wm = (i < nv)[None, None, :, None]
        rk = rk.at[:, :, slots].set(jnp.where(wm, kj.astype(rk.dtype),
                                              rk[:, :, slots]))
        rv = rv.at[:, :, slots].set(jnp.where(wm, vj.astype(rv.dtype),
                                              rv[:, :, slots]))
        return (rk, rv), out

    (rk, rv), outs = jax.lax.scan(step, (cache.k, cache.v),
                                  (qb, kb, vb, bases, nval))
    out = jnp.moveaxis(outs, 0, 3)                    # (B,Hkv,g,nb,grid,h)
    out = out.reshape(bsz, hq, nb * grid, hd)[:, :, :s]
    return (out.astype(v.dtype),
            RingKVCache(rk, rv, cache.pos + jnp.asarray(s, jnp.int32)))


def poly_kv_decode_step(cache: KVCache, q, k, v, *, degree: int, scale: float):
    """Exact polynomial attention decode with a full KV cache (quadratic
    baseline; the paper's inference win is that polysketch does NOT need
    this)."""
    bsz, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    kc = jax.lax.dynamic_update_index_in_dim(cache.k, k.astype(cache.k.dtype), cache.pos, axis=2)
    vc = jax.lax.dynamic_update_index_in_dim(cache.v, v.astype(cache.v.dtype), cache.pos, axis=2)
    qg = q.reshape(bsz, hkv, g, hd).astype(jnp.float32)
    wts = (jnp.einsum("bngh,bnsh->bngs", qg, kc.astype(jnp.float32)) * scale) ** degree
    mask = jnp.arange(kc.shape[2]) <= cache.pos
    wts = jnp.where(mask[None, None, None, :], wts, 0.0)
    den = 1.0 + jnp.sum(wts, axis=-1, keepdims=True)
    out = jnp.einsum("bngs,bnsh->bngh", wts / den, vc.astype(jnp.float32))
    return out.reshape(bsz, hq, hd).astype(v.dtype), KVCache(kc, vc, cache.pos + 1)


def polysketch_prefill(cache: PolysketchCache, qm, km, q, k, v, *,
                       degree: int, scale: float, local_exact: bool = True):
    """Fill a PolysketchCache from a prompt segment (B, H*, S, .) in one shot.

    Folds all complete blocks into z; the remainder lands in the buffer.
    Returns (outputs (B, Hq, S, h), cache) where outputs match the training
    block algorithm exactly.

    Resume contract: `cache` may carry a nonzero *block-aligned* state
    (pos % blk == 0, empty buffers) — e.g. a prefix-cache snapshot — and
    the segment's tokens then attend through cache.z as if the folded
    tokens had been part of this call. Both z and the outputs accumulate
    block-by-block (the scan carry), so a prefill resumed from a snapshot
    is bit-identical to a cold prefill of the full concatenated prompt.
    """
    from repro.core.linear_attention import block_causal_linear_attention
    bsz, hkv, s, hd = k.shape
    hq = q.shape[1]
    blk = cache.kbuf.shape[2]
    g = hq // hkv
    rep = lambda x: jnp.repeat(x, g, axis=1) if g > 1 else x
    km_r, k_r, v_r = rep(km), rep(k), rep(v)
    f32 = jnp.float32
    n_full = (s // blk) * blk
    z = cache.z.astype(f32)
    outs = []
    if n_full:
        out_f, z_r = block_causal_linear_attention(
            qm[:, :, :n_full], km_r[:, :, :n_full], v_r[:, :, :n_full],
            q[:, :, :n_full], k_r[:, :, :n_full], degree=degree, scale=scale,
            block_size=blk, local_exact=local_exact, z0=rep(z),
            return_state=True)
        outs.append(out_f)
        # all g query-head copies of a kv head folded identical blocks from
        # an identical z0, so any copy is the per-kv-head state
        z = z_r.reshape(bsz, hkv, g, *z_r.shape[2:])[:, :, 0]
    if s > n_full:
        # partial tail block: attends locally + through z, but is NOT folded
        # (it lives in the buffer until decode completes the block)
        outs.append(block_causal_linear_attention(
            qm[:, :, n_full:], km_r[:, :, n_full:], v_r[:, :, n_full:],
            q[:, :, n_full:], k_r[:, :, n_full:], degree=degree, scale=scale,
            block_size=s - n_full, local_exact=local_exact, z0=rep(z)))
    out = outs[0] if len(outs) == 1 else jnp.concatenate(outs, axis=2)
    kbuf = jax.lax.dynamic_update_slice_in_dim(
        cache.kbuf, k[:, :, n_full:].astype(cache.kbuf.dtype), 0, axis=2)
    vbuf = jax.lax.dynamic_update_slice_in_dim(
        cache.vbuf, v[:, :, n_full:].astype(cache.vbuf.dtype), 0, axis=2)
    mbuf = jax.lax.dynamic_update_slice_in_dim(
        cache.mbuf, km[:, :, n_full:].astype(f32), 0, axis=2)
    return out, PolysketchCache(z=z, kbuf=kbuf, vbuf=vbuf, mbuf=mbuf,
                                pos=cache.pos + s)


def broadcast_slot_caches(cache, slots: int):
    """Replicate a batch-1 decode cache into a slot-stacked pytree.

    Every leaf gains a leading slot axis: arrays (1, ...) -> (slots, 1, ...)
    and the scalar `pos` becomes a (slots,) vector, so each serve slot
    carries an independent position. Works for any of the cache pytrees in
    this module (and the model-level dict-of-layers cache that stacks them).
    """
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (slots,) + x.shape).copy(), cache)


def slot_scatter(slot_caches, cache, slot):
    """Write one slot's batch-1 cache into the slot-stacked pytree.

    `slot` may be a traced int32 scalar, so a single jitted scatter serves
    every slot index without retracing. Leaves of `cache` must match the
    slot-stacked leaves with the leading slot axis removed.
    """
    return jax.tree_util.tree_map(
        lambda full, one: jax.lax.dynamic_update_index_in_dim(
            full, one.astype(full.dtype), slot, axis=0),
        slot_caches, cache)


def slot_gather(slot_caches, slot):
    """Read one slot's batch-1 cache back out of the slot-stacked pytree."""
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, slot, axis=0,
                                               keepdims=False), slot_caches)


def kv_decode_step(cache: KVCache, q, k, v, *, scale: float | None = None,
                   window: int | None = None):
    """One softmax decode step with a (optionally sliding-window) KV cache.

    q: (B, Hq, h); k, v: (B, Hkv, h). Returns (out (B, Hq, h), new_cache).
    """
    bsz, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    kc = jax.lax.dynamic_update_index_in_dim(cache.k, k.astype(cache.k.dtype), cache.pos, axis=2)
    vc = jax.lax.dynamic_update_index_in_dim(cache.v, v.astype(cache.v.dtype), cache.pos, axis=2)
    qg = q.reshape(bsz, hkv, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bngh,bnsh->bngs", qg, kc.astype(jnp.float32)) * scale
    idx = jnp.arange(kc.shape[2])
    mask = idx <= cache.pos
    if window is not None:
        mask = mask & (idx > cache.pos - window)
    logits = jnp.where(mask[None, None, None, :], logits, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngs,bnsh->bngh", w, vc.astype(jnp.float32))
    return out.reshape(bsz, hq, hd).astype(v.dtype), KVCache(kc, vc, cache.pos + 1)
