"""Whisper-style encoder-decoder backbone (audio frontend STUBBED).

Per the task spec, the conv/mel frontend is a stub: `input_specs()` supplies
precomputed frame embeddings (B, enc_len, d_model). The encoder is a
bidirectional softmax transformer; the decoder is a causal LM whose
self-attention uses the configured mechanism (softmax|polynomial|polysketch
— the paper's technique applies to decoder self-attention) plus softmax
cross-attention over the fixed-length encoder memory.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.state import fold_blocks
from repro.models import attention as attn
from repro.distributed.sharding import shard_act
from repro.models.layers import (
    embedding_init, glu_ffn_apply, glu_ffn_init, norm_apply, norm_init,
    sinusoidal_positions,
)


def _enc_block_init(key, cfg):
    k1, k2 = jax.random.split(key)
    p, a = {}, {}
    p["norm1"], a["norm1"] = norm_init(cfg.d_model, cfg.norm)
    p["attn"], a["attn"] = attn.attention_init(k1, cfg, "encoder_attn")
    p["norm2"], a["norm2"] = norm_init(cfg.d_model, cfg.norm)
    p["ffn"], a["ffn"] = glu_ffn_init(k2, cfg.d_model, cfg.d_ff)
    return p, a


def _dec_block_init(key, cfg):
    k1, k2, k3 = jax.random.split(key, 3)
    p, a = {}, {}
    p["norm1"], a["norm1"] = norm_init(cfg.d_model, cfg.norm)
    p["self_attn"], a["self_attn"] = attn.attention_init(k1, cfg, "attn")
    p["norm_x"], a["norm_x"] = norm_init(cfg.d_model, cfg.norm)
    p["cross_attn"], a["cross_attn"] = attn.attention_init(k2, cfg, "cross_attn")
    p["norm2"], a["norm2"] = norm_init(cfg.d_model, cfg.norm)
    p["ffn"], a["ffn"] = glu_ffn_init(k3, cfg.d_model, cfg.d_ff)
    return p, a


def _stack(key, init_fn, cfg, n):
    ps, a0 = [], None
    for i in range(n):
        p, a = init_fn(jax.random.fold_in(key, i), cfg)
        ps.append(p)
        a0 = a0 or a
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ps)
    axes = jax.tree_util.tree_map(
        lambda names: ("layers",) + tuple(names), a0,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    return stacked, axes


def whisper_init(key, cfg):
    ks = jax.random.split(key, 4)
    params, axes = {}, {}
    params["embed"], axes["embed"] = embedding_init(ks[0], cfg.vocab_size, cfg.d_model)
    params["enc"], axes["enc"] = _stack(ks[1], _enc_block_init, cfg, cfg.encoder_layers)
    params["dec"], axes["dec"] = _stack(ks[2], _dec_block_init, cfg, cfg.n_layers)
    params["enc_norm"], axes["enc_norm"] = norm_init(cfg.d_model, cfg.norm)
    params["dec_norm"], axes["dec_norm"] = norm_init(cfg.d_model, cfg.norm)
    return params, axes


def whisper_encode(params, cfg, frames):
    """frames: (B, T_enc, D) precomputed frame embeddings (frontend stub)."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = frames.astype(dt) + sinusoidal_positions(frames.shape[1], cfg.d_model).astype(dt)

    def body(h, lp):
        h = shard_act(h, "batch", "seq", "embed")
        hn = norm_apply(lp["norm1"], h)
        y, _ = attn.attention_apply(lp["attn"], cfg, hn, kind="encoder_attn",
                                    positions=jnp.arange(h.shape[1]),
                                    mode="train", cache=None)
        h = h + y
        hn = norm_apply(lp["norm2"], h)
        return h + glu_ffn_apply(lp["ffn"], hn), 0.0

    if cfg.remat in ("dots", "full"):
        body = jax.checkpoint(body)
    if cfg.unroll_layers:
        for li in range(cfg.encoder_layers):
            lp = jax.tree_util.tree_map(lambda x: x[li], params["enc"])
            h, _ = body(h, lp)
    else:
        h, _ = jax.lax.scan(body, h, params["enc"])
    return norm_apply(params["enc_norm"], h)


def whisper_decode(params, cfg, tokens, memory=None, *, mode="train",
                   cache=None, positions=None, impl=None):
    """tokens: (B, S). memory: (B, T_enc, D) (required unless decode w/ cache).

    Returns (logits, new_cache). Cache = {"self": .., "cross": ..} stacked
    over decoder layers.
    """
    dt = jnp.dtype(cfg.compute_dtype)
    h = params["embed"]["table"].astype(dt)[tokens] * math.sqrt(cfg.d_model)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])

    def body(h, xs):
        lp, lcache = xs
        h = shard_act(h, "batch", "seq", "embed")
        self_cache = None if lcache is None else lcache["self"]
        cross_cache = None if lcache is None else lcache["cross"]
        hn = norm_apply(lp["norm1"], h)
        y, new_self = attn.attention_apply(
            lp["self_attn"], cfg, hn, kind="attn", positions=positions,
            mode=mode, cache=self_cache, impl=impl)
        h = h + y
        hn = norm_apply(lp["norm_x"], h)
        y, _ = attn.attention_apply(
            lp["cross_attn"], cfg, hn, kind="cross_attn", positions=positions,
            mode=mode, cache=cross_cache, memory=memory)
        h = h + y
        hn = norm_apply(lp["norm2"], h)
        h = h + glu_ffn_apply(lp["ffn"], hn)
        new_cache = None
        if mode in ("decode", "prefill"):
            if mode == "prefill":
                cross = attn.cross_attention_cache(lp["cross_attn"], memory, dt)
            else:
                cross = cross_cache
            new_cache = {"self": new_self, "cross": cross}
        return h, new_cache

    bodyw = body
    if cfg.remat in ("dots", "full") and mode == "train":
        bodyw = jax.checkpoint(body)

    lcaches = None if cache is None else cache
    if cfg.unroll_layers:
        ncs = []
        for li in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda x: x[li], params["dec"])
            lc = (None if lcaches is None else
                  jax.tree_util.tree_map(lambda x: x[li], lcaches))
            h, nc = bodyw(h, (lp, lc))
            ncs.append(nc)
        new_caches = (None if lcaches is None else
                      jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ncs))
    elif lcaches is None:
        h, _ = jax.lax.scan(lambda c, p: (bodyw(c, (p, None))[0], 0.0),
                            h, params["dec"])
        new_caches = None
    else:
        h, new_caches = jax.lax.scan(bodyw, h, (params["dec"], lcaches))

    if mode == "decode":
        new_caches = fold_blocks(new_caches)
    h = norm_apply(params["dec_norm"], h)
    logits = jnp.einsum("bsd,vd->bsv", h, params["embed"]["table"].astype(dt))
    return logits, new_caches


def whisper_init_cache(params, cfg, batch, max_len):
    dt = jnp.dtype(cfg.compute_dtype)
    self_c = attn.init_cache(None, cfg, "attn", batch, max_len, dt)
    from repro.core.decode import KVCache
    hd = cfg.resolved_head_dim
    cross = KVCache(
        k=jnp.zeros((batch, cfg.n_heads, cfg.encoder_len, hd), dt),
        v=jnp.zeros((batch, cfg.n_heads, cfg.encoder_len, hd), dt),
        pos=jnp.zeros((), jnp.int32))
    one = {"self": self_c, "cross": cross}
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (cfg.n_layers, *x.shape)).copy(), one)
