"""Decode-time attention states.

The headline inference property of PolySketchFormer: the decode state is
O(1) in context length (an r^2 x (h+1) prefix matrix per kv-head plus one
partial block buffer), vs an O(n) KV cache for softmax attention.

The polysketch decode step is *bit-equivalent in semantics* to the training
block algorithm (linear_attention.block_causal_linear_attention): a token
attends exactly (degree-p polynomial weights) to tokens in its own block so
far, and through the sketched prefix state to all earlier, completed blocks.
When a step fills the buffer, `polysketch_fold` folds the whole block into
the prefix state before the next step.

All caches here are per-layer pytrees; the model stacks them over layers.
A decode step takes either one layer's node or the whole layer-stacked
node with the `layer` to step: it then writes the token's rows into the
stack in place and reads that layer read-only, so the model's layer loop
carries the stack instead of slicing it out and writing it back whole.
A node's `pos` is one position shared by the batch, or one per batch row
(the serve engine's slots, each at its own depth).
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.utils import self_kron


def layer_node(node, layer):
    """One layer of a layer-stacked node (the node itself when `layer` is
    None)."""
    if layer is None:
        return node
    return jax.tree_util.tree_map(
        lambda x: jax.lax.dynamic_index_in_dim(x, layer, 0, keepdims=False),
        node)


def put_layer_node(stack, layer, node):
    """Write one layer's node into a layer-stacked node (the whole-node
    write of kinds whose every leaf changes with each token)."""
    if layer is None:
        return node
    return jax.tree_util.tree_map(
        lambda full, one: jax.lax.dynamic_update_index_in_dim(
            full, one.astype(full.dtype), layer, 0), stack, node)


def _row_pos(node, layer, bsz):
    """(B,) position of every batch row of `node` at `layer`."""
    return jnp.broadcast_to(layer_node(node.pos, layer), (bsz,))


def _write_rows(node, layer, row, **rows):
    """Write one token's rows in place: buffer `name` (B, Hkv, S, .) of
    `node` takes rows[name] (B, Hkv, .) at sequence row row[b] of batch row
    b, at `layer` of a layer-stacked node. One dynamic update per batch
    row: an out-of-range row clamps as it always did, and the updates
    take the buffer's layout as it is (one scatter of every row makes the
    TPU's compiler relay the whole stack around it)."""
    at = () if layer is None else (layer,)
    lead = (1,) * len(at)

    def write(buf, val):
        val = val.astype(buf.dtype)
        for b in range(row.shape[0]):
            buf = jax.lax.dynamic_update_slice(
                buf, val[b].reshape(lead + (1, val.shape[1], 1, val.shape[2])),
                (*at, b, 0, row[b], 0))
        return buf
    return node._replace(**{name: write(getattr(node, name), val)
                            for name, val in rows.items()})


def _advance(node, layer):
    """`node` with its position at `layer` one token further."""
    if layer is None:
        return node._replace(pos=node.pos + 1)
    pos = layer_node(node.pos, layer) + 1
    return node._replace(
        pos=jax.lax.dynamic_update_index_in_dim(node.pos, pos, layer, 0))


class PolysketchCache(NamedTuple):
    z: jax.Array      # (B, Hkv, r^2, h+1) f32 prefix state over folded blocks
    kbuf: jax.Array   # (B, Hkv, b, h)     raw keys, current partial block
    vbuf: jax.Array   # (B, Hkv, b, h+1)   raw values and a column of ones
    mbuf: jax.Array   # (B, Hkv, b, r)     sketched keys, current partial block
    pos: jax.Array    # () or (B,)         int32 tokens consumed so far


def init_polysketch_cache(batch, n_kv_heads, head_dim, r, block_size,
                          dtype=jnp.float32) -> PolysketchCache:
    b = block_size
    return PolysketchCache(
        z=jnp.zeros((batch, n_kv_heads, r * r, head_dim + 1), jnp.float32),
        kbuf=jnp.zeros((batch, n_kv_heads, b, head_dim), dtype),
        vbuf=jnp.concatenate([jnp.zeros((batch, n_kv_heads, b, head_dim), dtype),
                              jnp.ones((batch, n_kv_heads, b, 1), dtype)], -1),
        mbuf=jnp.zeros((batch, n_kv_heads, b, r), jnp.float32),
        pos=jnp.zeros((), jnp.int32),
    )


def polysketch_decode_step(cache: PolysketchCache, qm, km, q, k, v, *,
                           degree: int, scale: float,
                           local_exact: bool = True, layer=None):
    """One decode step: write the token into the buffers, attend, advance.

    qm: (B, Hq, r)  sketched query (input pre-scaled by sqrt(scale))
    km: (B, Hkv, r) sketched key
    q:  (B, Hq, h)  post-LN query;  k, v: (B, Hkv, h)
    layer: None steps `cache` itself; else `cache` is the layer-stacked
      node (leaves (L, B, ...)) and the step writes that layer in place.
    A block the step completes stays in the buffers until
    `polysketch_fold` folds it into z, before the next step.
    Returns (out (B, Hq, h), new_cache).
    """
    bsz, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    blk = cache.kbuf.shape[-2]
    fill = jnp.mod(_row_pos(cache, layer, bsz), blk)  # row of the token
    f32 = jnp.float32
    # write the token's rows, then read this layer's buffers
    cache = _write_rows(cache, layer, fill, kbuf=k, vbuf=v, mbuf=km.astype(f32))
    kbuf, vbuf, mbuf = (layer_node(x, layer)
                        for x in (cache.kbuf, cache.vbuf, cache.mbuf))

    # --- local (within current partial block) attention weights ---
    qg = q.reshape(bsz, hkv, g, hd).astype(f32)
    qmg = qm.reshape(bsz, hkv, g, -1).astype(f32)
    if local_exact:
        w = (jnp.einsum("bngh,bnsh->bngs", qg, kbuf.astype(f32)) * scale) ** degree
    else:
        w = jnp.einsum("bngr,bnsr->bngs", qmg, mbuf) ** 2
    valid = (jnp.arange(blk)[None, :] <= fill[:, None])[:, None, None, :]
    w = jnp.where(valid, w, 0.0)
    local = jnp.einsum("bngs,bnsd->bngd", w, vbuf.astype(f32))

    # --- sketched prefix (folded blocks) ---
    qf = self_kron(qmg)                                        # (B,Hkv,g,r^2)
    cross = jnp.einsum("bngf,bnfd->bngd", qf, layer_node(cache.z, layer))

    acc = local + cross
    out = (acc[..., :hd] / (1.0 + acc[..., hd:])).reshape(bsz, hq, hd)
    return out.astype(v.dtype), _advance(cache, layer)


def polysketch_fold(cache: PolysketchCache, fold_due=None):
    """Fold the blocks the last decode step completed into the prefix state.

    A batch row whose buffer the step filled now sits on the block grid;
    its block folds into z, and its buffer rows are overwritten by the
    next block. `cache` may be layer-stacked (leaves (L, B, ...)): every
    layer folds in turn inside one branch. `fold_due`: optional unbatched
    scalar bool gating the fold, `polysketch_fold_due` of the whole state
    before the step (some row of a batch the step is vmapped over
    completes); None folds on this node's own positions alone.
    """
    blk = cache.kbuf.shape[-2]
    stacked = cache.z.ndim == 5

    def fold_layer(z, mbuf, vbuf, pos):
        # the due rows fold; the others keep their z
        done = jnp.broadcast_to(jnp.mod(pos, blk) == 0, z.shape[:1])
        kf = self_kron(mbuf)                                   # (B,Hkv,blk,r^2)
        new = z + jnp.einsum("bnsf,bnsd->bnfd", kf, vbuf.astype(jnp.float32))
        return jnp.where(done[:, None, None, None], new, z)

    def fold(z):
        if not stacked:
            return fold_layer(z, cache.mbuf, cache.vbuf, cache.pos)
        return jax.lax.fori_loop(0, z.shape[0], lambda i, z: put_layer_node(
            z, i, fold_layer(*(layer_node(x, i) for x in
                               (z, cache.mbuf, cache.vbuf, cache.pos)))), z)

    # the scope holds the cond itself. An unbatched `fold_due` keeps it a
    # real branch, taken on the few steps where some row completes a block
    pred = (jnp.any(jnp.mod(cache.pos, blk) == 0) if fold_due is None
            else fold_due)
    with jax.named_scope("fold"):
        return cache._replace(z=jax.lax.cond(pred, fold, lambda z: z,
                                             cache.z))


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
    pos: jax.Array  # () or (B,)


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
    pos: jax.Array  # () or (B,)


def init_kv_cache(batch, n_kv_heads, head_dim, max_len, dtype=jnp.float32) -> KVCache:
    shape = (batch, n_kv_heads, max_len, head_dim)
    return KVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                   pos=jnp.zeros((), jnp.int32))


def init_ring_cache(batch, n_kv_heads, head_dim, window,
                    dtype=jnp.float32) -> RingKVCache:
    shape = (batch, n_kv_heads, window, head_dim)
    return RingKVCache(k=jnp.zeros(shape, dtype), v=jnp.zeros(shape, dtype),
                       pos=jnp.zeros((), jnp.int32))


def kv_ring_decode_step(cache: RingKVCache, q, k, v, *,
                        scale: float | None = None, layer=None):
    """Sliding-window softmax decode with a ring buffer of size W=max_len.

    The cache stores post-RoPE keys, so ring rotation does not disturb
    relative positions. q: (B, Hq, h); k, v: (B, Hkv, h); `layer` as in
    polysketch_decode_step.
    """
    bsz, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    w = cache.k.shape[-2]
    pos = _row_pos(cache, layer, bsz)
    cache = _write_rows(cache, layer, jnp.mod(pos, w), k=k, v=v)
    kc, vc = layer_node(cache.k, layer), layer_node(cache.v, layer)
    qg = q.reshape(bsz, hkv, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bngh,bnsh->bngs", qg, kc.astype(jnp.float32)) * scale
    valid = jnp.arange(w)[None, :] <= pos[:, None]  # until the ring is full
    logits = jnp.where(valid[:, None, None, :], logits, jnp.finfo(jnp.float32).min)
    wts = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngs,bnsh->bngh", wts, vc.astype(jnp.float32))
    return out.reshape(bsz, hq, hd).astype(v.dtype), _advance(cache, layer)


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


def poly_kv_decode_step(cache: KVCache, q, k, v, *, degree: int, scale: float,
                        layer=None):
    """Exact polynomial attention decode with a full KV cache (quadratic
    baseline; the paper's inference win is that polysketch does NOT need
    this). `layer` as in polysketch_decode_step."""
    bsz, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    pos = _row_pos(cache, layer, bsz)
    cache = _write_rows(cache, layer, pos, k=k, v=v)
    kc, vc = layer_node(cache.k, layer), layer_node(cache.v, layer)
    qg = q.reshape(bsz, hkv, g, hd).astype(jnp.float32)
    wts = (jnp.einsum("bngh,bnsh->bngs", qg, kc.astype(jnp.float32)) * scale) ** degree
    mask = jnp.arange(kc.shape[2])[None, :] <= pos[:, None]
    wts = jnp.where(mask[:, None, None, :], wts, 0.0)
    den = 1.0 + jnp.sum(wts, axis=-1, keepdims=True)
    out = jnp.einsum("bngs,bnsh->bngh", wts / den, vc.astype(jnp.float32))
    return out.reshape(bsz, hq, hd).astype(v.dtype), _advance(cache, layer)


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


def slot_axis(path) -> int:
    """The slot axis of a leaf of the engine's slot stack: where a batch-1
    cache has its batch axis, right behind the layer axis in the model
    cache's layer-stacked subtree ("groups", models/transformer.py
    lm_init_cache) and leading elsewhere."""
    return int(bool(path) and getattr(path[0], "key", None) == "groups")


def broadcast_slot_caches(cache, slots: int):
    """Replicate a batch-1 decode cache into the engine's slot stack.

    The stack is layer-major: the slot axis takes the place of the batch
    axis, behind the layer axis, so a layer-stacked leaf (L, 1, ...)
    becomes (L, slots, ...) and an unstacked one (1, ...) becomes
    (slots, ...). A per-layer position gains its slot axis at the same
    place, (L,) -> (L, slots) and () -> (slots,), so each serve slot
    carries an independent position. The stack is then a batch-`slots`
    cache with one position per row, which the model's decode step runs
    as it is. Works for any of the cache pytrees in this module and the
    model-level dict-of-layers cache that stacks them.
    """
    def one(path, x):
        ax = slot_axis(path)
        if x.ndim == ax:
            x = jnp.expand_dims(x, ax)
        return jnp.broadcast_to(
            x, x.shape[:ax] + (slots,) + x.shape[ax + 1:]).copy()
    return jax.tree_util.tree_map_with_path(one, cache)


def slot_scatter(slot_caches, cache, slot):
    """Write one slot's batch-1 cache into the slot stack.

    `slot` may be a traced int32 scalar, so a single jitted scatter serves
    every slot index without retracing."""
    def one(path, full, x):
        ax = slot_axis(path)
        if x.ndim < full.ndim:
            x = jnp.expand_dims(x, ax)
        return jax.lax.dynamic_update_slice_in_dim(
            full, x.astype(full.dtype), slot, axis=ax)
    return jax.tree_util.tree_map_with_path(one, slot_caches, cache)


def slot_gather(slot_caches, slot):
    """Read one slot's batch-1 cache back out of the slot stack, in the
    form it had before stacking (a leaf with nothing behind its slot axis
    is a per-slot position, and drops the axis)."""
    def one(path, x):
        ax = slot_axis(path)
        y = jax.lax.dynamic_slice_in_dim(x, slot, 1, axis=ax)
        return jnp.squeeze(y, ax) if x.ndim == ax + 1 else y
    return jax.tree_util.tree_map_with_path(one, slot_caches)


def kv_decode_step(cache: KVCache, q, k, v, *, scale: float | None = None,
                   layer=None):
    """One softmax decode step with a full KV cache.

    q: (B, Hq, h); k, v: (B, Hkv, h); `layer` as in polysketch_decode_step.
    Returns (out (B, Hq, h), new_cache).
    """
    bsz, hq, hd = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    if scale is None:
        scale = 1.0 / float(hd) ** 0.5
    pos = _row_pos(cache, layer, bsz)
    cache = _write_rows(cache, layer, pos, k=k, v=v)
    kc, vc = layer_node(cache.k, layer), layer_node(cache.v, layer)
    qg = q.reshape(bsz, hkv, g, hd).astype(jnp.float32)
    logits = jnp.einsum("bngh,bnsh->bngs", qg, kc.astype(jnp.float32)) * scale
    mask = jnp.arange(kc.shape[2])[None, :] <= pos[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bngs,bnsh->bngh", w, vc.astype(jnp.float32))
    return out.reshape(bsz, hq, hd).astype(v.dtype), _advance(cache, layer)
