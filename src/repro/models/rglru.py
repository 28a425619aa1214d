"""RecurrentGemma recurrent block: gated branches + temporal conv + RG-LRU.

RG-LRU per channel:
  r_t = sigmoid(W_a xc_t + b_a)
  i_t = sigmoid(W_x xc_t + b_x)
  log a_t = -c * softplus(Lambda) * r_t
  h_t = exp(log a_t) h_{t-1} + sqrt(1 - exp(2 log a_t)) * (i_t * xc_t)

The linear recurrence is evaluated with jax.lax.associative_scan (parallel
prefix — the same primitive family as the paper's S3.1 prefix sums).
Attention-free: the paper's technique is inapplicable here by design (noted
in DESIGN.md); the hybrid's local-attention layers are where polysketch
applies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.decode import RecurrentCache, layer_node, put_layer_node
from repro.core.state import StateSpec, batch_shard_axes, register_state
from repro.distributed.sharding import shard_act
from repro.models.layers import dense_init


def rglru_init(key, cfg):
    d = cfg.d_model
    w = cfg.rglru_width or d
    ks = jax.random.split(key, 6)
    params, axes = {}, {}
    params["w_gate"], axes["w_gate"] = dense_init(ks[0], d, (w,), ("embed", "rnn"))
    params["w_in"], axes["w_in"] = dense_init(ks[1], d, (w,), ("embed", "rnn"))
    params["conv_w"] = jax.random.normal(ks[2], (4, w), jnp.float32) * 0.1
    axes["conv_w"] = (None, "rnn")
    params["conv_b"] = jnp.zeros((w,), jnp.float32)
    axes["conv_b"] = ("rnn",)
    params["w_a"], axes["w_a"] = dense_init(ks[3], w, (w,), ("rnn", "rnn2"))
    params["b_a"] = jnp.zeros((w,), jnp.float32)
    axes["b_a"] = ("rnn",)
    params["w_x"], axes["w_x"] = dense_init(ks[4], w, (w,), ("rnn", "rnn2"))
    params["b_x"] = jnp.zeros((w,), jnp.float32)
    axes["b_x"] = ("rnn",)
    # init Lambda so a^c in [0.9, 0.999] as in the Griffin paper
    lam = jnp.linspace(0.9, 0.999, w)
    params["lambda"] = jnp.log(jnp.expm1(-jnp.log(lam) / cfg.rglru_c))
    axes["lambda"] = ("rnn",)
    params["w_out"], axes["w_out"] = dense_init(ks[5], w, (d,), ("rnn", "embed"))
    return params, axes


def _conv4(params, x, state=None):
    """Causal width-4 depthwise conv. x: (B,S,W); state: (B,3,W)."""
    kw = params["conv_w"].shape[0]
    pad = (jnp.zeros((x.shape[0], kw - 1, x.shape[-1]), x.dtype)
           if state is None else state.astype(x.dtype))
    xp = jnp.concatenate([pad, x], axis=1)
    w = params["conv_w"].astype(x.dtype)
    out = sum(w[i] * xp[:, i:i + x.shape[1]] for i in range(kw))
    return out + params["conv_b"].astype(x.dtype), xp[:, -(kw - 1):]


def _rglru_coeffs(params, cfg, xc):
    f32 = jnp.float32
    x32 = xc.astype(f32)
    r = jax.nn.sigmoid(x32 @ params["w_a"] + params["b_a"])
    i = jax.nn.sigmoid(x32 @ params["w_x"] + params["b_x"])
    log_a = -cfg.rglru_c * jax.nn.softplus(params["lambda"]) * r
    a = jnp.exp(log_a)
    b = jnp.sqrt(jnp.clip(1.0 - jnp.exp(2.0 * log_a), 1e-12)) * (i * x32)
    return a, b


def _combine(lhs, rhs):
    a1, b1 = lhs
    a2, b2 = rhs
    return a1 * a2, a2 * b1 + b2


def _rglru_chunked(a, b, h0, chunk: int):
    """Linear recurrence h_t = a_t h_{t-1} + b_t from h0, on a fixed grid.

    lax.scan over fixed-width chunks (padded with the (1, 0) identity
    element of the recurrence), parallel associative_scan within a chunk.
    The scan body is one trace, so each chunk's arithmetic is independent
    of the call's total length — a prefill resumed from h0 at a chunk
    boundary is bit-identical to the longer cold prefill (the DecodeState
    snapshot contract). Returns (h (B,S,W), h_last (B,W))."""
    bs, s, w = a.shape
    pad = (-s) % chunk
    if pad:
        a = jnp.concatenate([a, jnp.ones((bs, pad, w), a.dtype)], axis=1)
        b = jnp.concatenate([b, jnp.zeros((bs, pad, w), b.dtype)], axis=1)
    nc = (s + pad) // chunk
    a_c = jnp.moveaxis(a.reshape(bs, nc, chunk, w), 1, 0)
    b_c = jnp.moveaxis(b.reshape(bs, nc, chunk, w), 1, 0)

    def step(hc, ab):
        al, bl = ab
        prod, zero_resp = jax.lax.associative_scan(_combine, (al, bl), axis=1)
        h = zero_resp + prod * hc[:, None, :]
        # pad steps are the identity, so the last column equals the state
        # at the chunk's last real token
        return h[:, -1, :], h

    h_last, hs = jax.lax.scan(step, h0, (a_c, b_c))
    h = jnp.moveaxis(hs, 0, 1).reshape(bs, nc * chunk, w)[:, :s]
    return h, h_last


def rglru_apply(params, cfg, x, *, mode="train", cache=None, layer=None):
    """x: (B,S,D) -> (y (B,S,D), new_cache). In decode mode `layer` names
    the layer of a layer-stacked `cache` to step (core.decode.layer_node).

    Prefill resume: `cache` (zeros for a cold start) is the state the
    sequence continues from; the recurrence runs on a fixed
    cfg.lt_block_size chunk grid so block-boundary resumes are
    bit-identical to cold prefills (see _rglru_chunked)."""
    dt = x.dtype
    gate = jax.nn.gelu(shard_act(x @ params["w_gate"].astype(dt),
                                 "batch", "seq", "rnn"))
    xin = shard_act(x @ params["w_in"].astype(dt), "batch", "seq", "rnn")

    if mode == "decode":
        # every leaf changes with each token: the whole node is written
        node = layer_node(cache, layer)
        xc, conv_state = _conv4(params, xin, node.conv)
        a, b = _rglru_coeffs(params, cfg, xc[:, 0])
        h = a * node.h + b
        y = h[:, None].astype(dt)
        new_cache = put_layer_node(cache, layer,
                                   RecurrentCache(h=h, conv=conv_state))
    elif mode == "prefill":
        resume = cache is not None
        xc, conv_state = _conv4(params, xin, cache.conv if resume else None)
        a, b = _rglru_coeffs(params, cfg, xc)
        h0 = (cache.h if resume else
              jnp.zeros((x.shape[0], a.shape[-1]), jnp.float32))
        h, h_last = _rglru_chunked(a, b, h0, cfg.lt_block_size)
        y = h.astype(dt)
        new_cache = RecurrentCache(h=h_last, conv=conv_state)
    else:
        xc, _ = _conv4(params, xin)
        a, b = _rglru_coeffs(params, cfg, xc)
        _, h = jax.lax.associative_scan(_combine, (a, b), axis=1)
        y = h.astype(dt)
        new_cache = None

    y = y * gate
    return y @ params["w_out"].astype(dt), new_cache


def rglru_init_cache(cfg, batch, dtype=jnp.float32) -> RecurrentCache:
    w = cfg.rglru_width or cfg.d_model
    return RecurrentCache(
        h=jnp.zeros((batch, w), jnp.float32),
        conv=jnp.zeros((batch, 3, w), dtype),
    )


register_state(StateSpec(
    kind="rglru", node_type=RecurrentCache, granularity="token",
    resumable=True,
    init=lambda cfg, batch, max_len, dtype: rglru_init_cache(cfg, batch,
                                                             dtype),
    shard_axes=batch_shard_axes))


def rglru_sequential_ref(params, cfg, x):
    """Token-by-token oracle (no conv/gating — core recurrence only)."""
    xc, _ = _conv4(params, x)
    a, b = _rglru_coeffs(params, cfg, xc)

    def step(h, inp):
        at, bt = inp
        h = at * h + bt
        return h, h

    init = jnp.zeros((x.shape[0], a.shape[-1]), jnp.float32)
    _, hs = jax.lax.scan(step, init, (a.transpose(1, 0, 2), b.transpose(1, 0, 2)))
    return hs.transpose(1, 0, 2)
