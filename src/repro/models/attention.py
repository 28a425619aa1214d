"""Attention mixers: softmax / polynomial / polysketch (the paper's knob),
sliding-window local attention, encoder (bidirectional) attention, and
cross-attention. Handles train / prefill / decode modes with the matching
cache types from core.decode.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import decode as dec
from repro.core import state as st
from repro.core.linear_attention import noncausal_linear_attention
from repro.core.poly_attention import (qk_layernorm, sliding_attention_blocked,
                                        softmax_attention_full)
from repro.core.sketches import init_sketch, sketch_half
from repro.kernels import ops
from repro.distributed.sharding import shard_act
from repro.models.layers import dense_init, rope


def attention_init(key, cfg, kind: str):
    """kind: attn | local_attn | encoder_attn | cross_attn."""
    d, hq, hkv = cfg.d_model, cfg.n_heads, cfg.n_kv_heads
    hd = cfg.resolved_head_dim
    if kind in ("encoder_attn", "cross_attn"):
        hkv = hq  # MHA for encoder/cross per the published whisper arch
    ks = jax.random.split(key, 6)
    params, axes = {}, {}
    params["wq"], axes["wq"] = dense_init(ks[0], d, (hq, hd), ("embed", "q_heads", "head_dim"))
    params["wk"], axes["wk"] = dense_init(ks[1], d, (hkv, hd), ("embed", "kv_heads", "head_dim"))
    params["wv"], axes["wv"] = dense_init(ks[2], d, (hkv, hd), ("embed", "kv_heads", "head_dim"))
    wo = jax.random.normal(ks[3], (hq, hd, d), jnp.float32) / math.sqrt(hq * hd)
    params["wo"], axes["wo"] = wo, ("q_heads", "head_dim", "embed")
    if cfg.qk_norm:
        params["q_norm"] = jnp.ones((hd,), jnp.float32)
        params["k_norm"] = jnp.ones((hd,), jnp.float32)
        axes["q_norm"] = (None,)
        axes["k_norm"] = (None,)
    if kind == "attn" and cfg.attention in ("polynomial", "polysketch"):
        # Paper S2.1: LayerNorm on q/k before the polynomial.
        for nm in ("pln_q_scale", "pln_k_scale"):
            params[nm] = jnp.ones((hd,), jnp.float32)
            axes[nm] = (None,)
        for nm in ("pln_q_bias", "pln_k_bias"):
            params[nm] = jnp.zeros((hd,), jnp.float32)
            axes[nm] = (None,)
    if kind == "attn" and cfg.attention == "polysketch":
        params["sketch"], axes["sketch"] = init_sketch(
            ks[4], hd, cfg.sketch_size, cfg.poly_degree, cfg.learned_sketch)
    return params, axes


def _rms(x, scale, eps=1e-6):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale).astype(x.dtype)


def _project(params, cfg, x, positions, kind):
    """x: (B, S, D) -> q (B,Hq,S,h), k,v (B,Hkv,S,h) with RoPE applied."""
    dt = x.dtype
    q = jnp.einsum("bsd,dnh->bnsh", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dnh->bnsh", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dnh->bnsh", x, params["wv"].astype(dt))
    if cfg.qk_norm:
        q = _rms(q, params["q_norm"])
        k = _rms(k, params["k_norm"])
    if cfg.use_rope and kind in ("attn", "local_attn"):
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = shard_act(q, "batch", "q_heads", "seq", "head_dim")
    k = shard_act(k, "batch", "kv_heads", "seq", "head_dim")
    v = shard_act(v, "batch", "kv_heads", "seq", "head_dim")
    return q, k, v


def _poly_ln(params, q, k):
    q = qk_layernorm(q, params["pln_q_scale"], params["pln_q_bias"])
    k = qk_layernorm(k, params["pln_k_scale"], params["pln_k_bias"])
    return q, k


def _out(params, y):
    """y: (B, Hq, S, h) -> (B, S, D)."""
    # the einsum contracts heads: "act_heads" resolves to "model" under
    # training rules (Megatron partial-sum) but to () under serving rules
    # so the reduction order is mesh-independent (bit-parity)
    y = shard_act(y, "batch", "act_heads")
    return jnp.einsum("bnsh,nhd->bsd", y, params["wo"].astype(y.dtype))


def init_cache(params, cfg, kind: str, batch: int, max_len: int, dtype):
    # NB: every array leaf carries the batch on axis 0, but the scalar
    # `pos` has none — a batched cache shares one position. Serving slots
    # at different depths therefore stack batch-1 caches with a slot axis
    # in the batch axis's place and a `pos` per slot
    # (core.decode.broadcast_slot_caches) instead of batching this one.
    spec = st.get_spec(st.mixer_state_kind(cfg, kind))
    return spec.init(cfg, batch, max_len, dtype)


def attention_apply(params, cfg, x, *, kind: str, positions, mode: str,
                    cache=None, memory=None, impl: str | None = None,
                    layer=None):
    """Returns (y (B,S,D), new_cache_or_None). In decode mode `layer`
    names the layer of a layer-stacked `cache` to step in place (None:
    `cache` is this layer's own node)."""
    scale = cfg.attn_scale
    mech = cfg.attention if kind == "attn" else "softmax"

    if kind == "cross_attn":
        return _cross_attention(params, cfg, x, cache=cache, memory=memory,
                                mode=mode), cache

    if mode == "decode":
        q, k, v = _project(params, cfg, x, positions, kind)
        q, k, v = q[:, :, 0], k[:, :, 0], v[:, :, 0]   # (B, H, h)
        skind = st.mixer_state_kind(cfg, kind)
        if skind == "polysketch":
            q, k = _poly_ln(params, q, k)
            rt = math.sqrt(scale)
            qm = sketch_half(params["sketch"], q * rt, cfg.poly_degree, cfg.learned_sketch)
            km = sketch_half(params["sketch"], k * rt, cfg.poly_degree, cfg.learned_sketch)
            y, cache = dec.polysketch_decode_step(
                cache, qm, km, q, k, v, degree=cfg.poly_degree, scale=scale,
                local_exact=cfg.local_exact, layer=layer)
        elif skind == "poly_kv":
            q, k = _poly_ln(params, q, k)
            y, cache = dec.poly_kv_decode_step(cache, q, k, v,
                                               degree=cfg.poly_degree,
                                               scale=scale, layer=layer)
        elif skind == "kv_ring":
            y, cache = dec.kv_ring_decode_step(cache, q, k, v, layer=layer)
        else:
            y, cache = dec.kv_decode_step(cache, q, k, v, layer=layer)
        return _out(params, y[:, :, None]), cache

    q, k, v = _project(params, cfg, x, positions, kind)

    if kind == "encoder_attn":
        y = softmax_attention_full(q, k, v, causal=False)
        return _out(params, y), None

    if mech == "polysketch":
        q, k = _poly_ln(params, q, k)
        rt = math.sqrt(scale)
        qm = shard_act(sketch_half(params["sketch"], q * rt, cfg.poly_degree,
                                   cfg.learned_sketch),
                       "batch", "q_heads", "seq", "sketch")
        km = shard_act(sketch_half(params["sketch"], k * rt, cfg.poly_degree,
                                   cfg.learned_sketch),
                       "batch", "kv_heads", "seq", "sketch")
        if mode == "prefill":
            y, cache = dec.polysketch_prefill(
                cache, qm, km, q, k, v, degree=cfg.poly_degree, scale=scale,
                local_exact=cfg.local_exact)
        else:
            y = ops.polysketch_attention(
                qm, km, q, k, v, degree=cfg.poly_degree, scale=scale,
                local_exact=cfg.local_exact,
                block_size=min(cfg.lt_block_size, q.shape[-2]), impl=impl,
                unroll=cfg.unroll_layers)
    elif mech == "polynomial":
        q, k = _poly_ln(params, q, k)
        y = ops.poly_attention(q, k, v, degree=cfg.poly_degree, scale=scale,
                               causal=True, impl=impl)
        if mode == "prefill":
            cache = _fill_kv(cache, k, v)
    elif kind == "local_attn" and mode == "prefill":
        # resumable ring prefill on a fixed sub-block lattice: the segment
        # continues at cache.pos (a block-grid multiple), attends through
        # the ring's window of earlier tokens, and is bit-identical to a
        # cold prefill of the full concatenation — the snapshot/resume
        # contract that gives sliding-window models prefix reuse
        y, cache = dec.kv_ring_prefill(
            cache, q, k, v,
            grid=dec.ring_grid(cfg.lt_block_size, cache.k.shape[2]))
    else:
        g = cfg.n_heads // k.shape[1]
        kr = jnp.repeat(k, g, axis=1) if g > 1 else k
        vr = jnp.repeat(v, g, axis=1) if g > 1 else v
        if kind == "local_attn":
            y = sliding_attention_blocked(q, kr, vr, window=cfg.sliding_window)
        else:
            y = softmax_attention_full(q, kr, vr, causal=True)
        if mode == "prefill":
            cache = _fill_kv(cache, k, v)
    return _out(params, y), cache


def _fill_kv(cache, k, v):
    s = k.shape[2]
    kc = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), 0, axis=2)
    vc = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), 0, axis=2)
    return dec.KVCache(kc, vc, jnp.asarray(s, jnp.int32))


def _cross_attention(params, cfg, x, *, cache, memory, mode):
    """Cross-attention over encoder memory. cache holds projected (k, v)."""
    dt = x.dtype
    q = jnp.einsum("bsd,dnh->bnsh", x, params["wq"].astype(dt))
    if memory is not None:
        k = jnp.einsum("btd,dnh->bnth", memory, params["wk"].astype(dt))
        v = jnp.einsum("btd,dnh->bnth", memory, params["wv"].astype(dt))
    else:
        k, v = cache.k, cache.v
    y = softmax_attention_full(q, k, v, causal=False)
    return _out(params, y)


def cross_attention_cache(params, memory, dtype):
    dt = memory.dtype
    k = jnp.einsum("btd,dnh->bnth", memory, params["wk"].astype(dt))
    v = jnp.einsum("btd,dnh->bnth", memory, params["wv"].astype(dt))
    return dec.KVCache(k.astype(dtype), v.astype(dtype), jnp.asarray(k.shape[2], jnp.int32))


def noncausal_polysketch(params, cfg, q, k, v):
    """Encoder-side linear polysketch attention (kept for completeness)."""
    rt = math.sqrt(cfg.attn_scale)
    qm = sketch_half(params["sketch"], q * rt, cfg.poly_degree, cfg.learned_sketch)
    km = sketch_half(params["sketch"], k * rt, cfg.poly_degree, cfg.learned_sketch)
    return noncausal_linear_attention(qm, km, v)
