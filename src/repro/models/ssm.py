"""Mamba-2 SSD (state-space duality) mixer.

Structurally this is the paper's Section 3.1 block lower-triangular
algorithm with a decay factor: within-chunk terms are a masked quadratic
product, cross-chunk terms flow through a sequentially-updated prefix state
(here the (N x P) SSM state instead of the (r^2 x h) sketch state). We
implement the chunked algorithm with a lax.scan over chunks (n/L sequential
steps, same dependence structure as the paper's Z_l prefix sum).

Recurrence (per head; state N, head dim P):
  dt_t = softplus(dt_raw_t + dt_bias)
  a_t  = -exp(A_log) * dt_t
  h_t  = exp(a_t) h_{t-1} + dt_t * B_t x_t^T        (N x P)
  y_t  = C_t^T h_t + D * x_t
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core.decode import RecurrentCache, layer_node, put_layer_node
from repro.core.state import StateSpec, batch_shard_axes, register_state
from repro.distributed.sharding import shard_act
from repro.models.layers import dense_init


def ssm_init(key, cfg):
    d = cfg.d_model
    d_inner = cfg.ssm_expand * d
    n, p = cfg.ssm_state, cfg.ssm_head_dim
    heads = d_inner // p
    conv_dim = d_inner + 2 * n
    ks = jax.random.split(key, 5)
    params, axes = {}, {}
    proj_out = d_inner + conv_dim + heads  # z, (x,B,C), dt
    params["in_proj"], axes["in_proj"] = dense_init(
        ks[0], d, (proj_out,), ("embed", "rnn"))
    params["conv_w"] = jax.random.normal(ks[1], (cfg.ssm_conv, conv_dim), jnp.float32) * 0.1
    axes["conv_w"] = (None, "rnn")
    params["conv_b"] = jnp.zeros((conv_dim,), jnp.float32)
    axes["conv_b"] = ("rnn",)
    params["A_log"] = jnp.log(jnp.linspace(1.0, 16.0, heads))
    axes["A_log"] = (None,)
    params["dt_bias"] = jnp.zeros((heads,), jnp.float32)
    axes["dt_bias"] = (None,)
    params["D"] = jnp.ones((heads,), jnp.float32)
    axes["D"] = (None,)
    params["norm_scale"] = jnp.ones((d_inner,), jnp.float32)
    axes["norm_scale"] = (None,)
    params["out_proj"], axes["out_proj"] = dense_init(
        ks[2], d_inner, (d,), ("rnn", "embed"))
    return params, axes


def _split(params, cfg, x):
    d = cfg.d_model
    d_inner = cfg.ssm_expand * d
    n = cfg.ssm_state
    heads = d_inner // cfg.ssm_head_dim
    proj = shard_act(x @ params["in_proj"].astype(x.dtype),
                     "batch", "seq", "rnn")
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:d_inner + d_inner + 2 * n]
    dt_raw = proj[..., -heads:]
    return z, xbc, dt_raw


def _conv(params, xbc, state=None):
    """Causal depthwise conv over sequence. xbc: (B, S, C).

    state: (B, K-1, C) trailing inputs from the previous call (decode)."""
    kw = params["conv_w"].shape[0]
    xp = jnp.concatenate(
        [jnp.zeros((xbc.shape[0], kw - 1, xbc.shape[-1]), xbc.dtype) if state is None
         else state.astype(xbc.dtype), xbc], axis=1)
    w = params["conv_w"].astype(xbc.dtype)
    out = sum(w[i] * xp[:, i:i + xbc.shape[1]] for i in range(kw))
    out = jax.nn.silu(out + params["conv_b"].astype(xbc.dtype))
    return out, xp[:, -(kw - 1):]


def ssd_chunked(x, b, c, dt, a_log, *, chunk: int = 64, h0=None,
                return_state: bool = False, fixed_grid: bool = False):
    """x: (B,S,H,P); b,c: (B,S,N); dt: (B,S,H) post-softplus.

    Returns y: (B,S,H,P) — or (y, h_final) when return_state, where
    h_final is the exact (B,H,N,P) recurrent state after the last token
    (the value a resumed call passes back as h0). f32 internally.
    Sequences are padded to a chunk multiple with dt = 0 steps (decay 1,
    contribution 0): mathematically a no-op, and bitwise stable because
    the pad slots sit after every real token of their chunk (causally
    masked for outputs, identity for the state).

    Two lowerings with identical math:

    - Training (no h0 / state / fixed grid): the within-chunk masked
      quadratic of ALL chunks is one batched einsum and only the small
      cross-chunk state update is scanned — the parallel form, so a long
      training sequence never serializes its dominant cost.
    - Prefill/resume (h0, return_state, or fixed_grid): the whole chunk
      computation lives inside ONE lax.scan body. Because that body is a
      single trace, each chunk's arithmetic is identical no matter how
      many chunks a call spans — so a prefill resumed from h_final at a
      chunk boundary is bit-identical to the longer cold prefill (the
      same contract block_causal_linear_attention gives the polysketch
      state). fixed_grid additionally pins the chunk width when
      s < chunk, keeping every call on the same absolute grid.
    """
    f32 = jnp.float32
    bs, s, h, p = x.shape
    n = b.shape[-1]
    grid_stable = fixed_grid or return_state or h0 is not None
    l = chunk if fixed_grid else min(chunk, s)
    pad = (-s) % l
    if pad:
        zpad = lambda v: jnp.concatenate(
            [v, jnp.zeros((bs, pad) + v.shape[2:], v.dtype)], axis=1)
        x, b, c, dt = zpad(x), zpad(b), zpad(c), zpad(dt)
    nc = (s + pad) // l
    x = x.reshape(bs, nc, l, h, p).astype(f32)
    b = b.reshape(bs, nc, l, n).astype(f32)
    c = c.reshape(bs, nc, l, n).astype(f32)
    dt = dt.reshape(bs, nc, l, h).astype(f32)
    tri = jnp.tril(jnp.ones((l, l), bool))
    neg_a = jnp.exp(a_log.astype(f32))                          # (H,)

    if not grid_stable:
        return _ssd_batched(x, b, c, dt, neg_a, tri, s)

    def step(hstate, inp):
        x_l, b_l, c_l, dt_l = inp                               # (B,l,...)
        a = -neg_a[None, None, :] * dt_l                        # (B,l,H)
        acum = jnp.cumsum(a, axis=1)                            # inclusive
        # within-chunk (masked quadratic, cf. paper's diagonal block)
        cb = jnp.einsum("bin,bjn->bij", c_l, b_l)               # (B,l,l)
        diff = acum[:, :, None, :] - acum[:, None, :, :]        # (B,i,j,H)
        # mask BEFORE exp: j>i entries have diff>0 and overflow to inf,
        # which poisons the gradient through where (the classic
        # jnp.where-NaN pitfall)
        diff = jnp.where(tri[None, :, :, None], diff, -1e30)
        w = cb[..., None] * jnp.exp(diff)                       # (B,i,j,H)
        xdt = x_l * dt_l[..., None]
        y = jnp.einsum("bijh,bjhp->bihp", w, xdt)
        # cross-chunk read through the carried prefix state
        y += jnp.einsum("bln,blh,bhnp->blhp", c_l, jnp.exp(acum), hstate)
        # state update
        decay_to_end = jnp.exp(acum[:, -1:, :] - acum)          # (B,l,H)
        st = jnp.einsum("bln,blh,blhp->bhnp", b_l, decay_to_end * dt_l, x_l)
        hstate = jnp.exp(acum[:, -1, :])[..., None, None] * hstate + st
        return hstate, y

    init = (jnp.zeros((bs, h, n, p), f32) if h0 is None
            else jnp.asarray(h0, f32))
    move = lambda v: jnp.moveaxis(v, 1, 0)
    h_final, ys = jax.lax.scan(step, init, (move(x), move(b), move(c),
                                            move(dt)))
    y = jnp.moveaxis(ys, 0, 1).reshape(bs, nc * l, h, p)[:, :s]
    return (y, h_final) if return_state else y


def _ssd_batched(x, b, c, dt, neg_a, tri, s):
    """Training lowering: within-chunk quadratic batched over all chunks
    at once, only the cross-chunk state recurrence scanned. Inputs are
    pre-chunked (B, nc, l, ...) f32; returns y (B, s, H, P)."""
    f32 = jnp.float32
    bs, nc, l, h, p = x.shape
    a = -neg_a[None, None, None, :] * dt                        # (B,nc,l,H)
    acum = jnp.cumsum(a, axis=2)                                # inclusive

    # ---- within-chunk (masked quadratic, cf. paper's diagonal block) ----
    cb = jnp.einsum("bkin,bkjn->bkij", c, b)                    # (B,nc,l,l)
    diff = acum[:, :, :, None, :] - acum[:, :, None, :, :]      # (B,nc,i,j,H)
    # mask BEFORE exp: j>i entries have diff>0 and overflow to inf, which
    # poisons the gradient through where (the classic jnp.where-NaN pitfall)
    diff = jnp.where(tri[None, None, :, :, None], diff, -1e30)
    w = cb[..., None] * jnp.exp(diff)                           # (B,nc,i,j,H)
    xdt = x * dt[..., None]
    y = jnp.einsum("bkijh,bkjhp->bkihp", w, xdt)

    # ---- cross-chunk prefix state (lax.scan over chunks) ----
    decay_to_end = jnp.exp(acum[:, :, -1:, :] - acum)           # (B,nc,l,H)
    states = jnp.einsum("bkln,bklh,bklhp->bkhnp", b, decay_to_end * dt, x)
    chunk_decay = jnp.exp(acum[:, :, -1, :])                    # (B,nc,H)

    def step(hstate, inp):
        st, cd = inp
        out = hstate
        hstate = cd[..., None, None] * hstate + st
        return hstate, out

    init = jnp.zeros((bs, h, states.shape[-2], p), f32)
    _, h0 = jax.lax.scan(step, init,
                         (states.transpose(1, 0, 2, 3, 4),
                          chunk_decay.transpose(1, 0, 2)))
    h0 = h0.transpose(1, 0, 2, 3, 4)                            # (B,nc,H,N,P)
    y += jnp.einsum("bkln,bklh,bkhnp->bklhp", c, jnp.exp(acum), h0)
    return y.reshape(bs, nc * l, h, p)[:, :s]


def ssm_apply(params, cfg, x, *, mode="train", cache=None, layer=None):
    """x: (B,S,D). Returns (y (B,S,D), new_cache). In decode mode `layer`
    names the layer of a layer-stacked `cache` to step
    (core.decode.layer_node).

    Prefill resume: in prefill mode, `cache` (zeros for a cold start) is
    the state the sequence continues from — the conv window replays the
    trailing inputs and the SSD scan starts at cache.h. The prefill scan
    runs on a fixed cfg.lt_block_size chunk grid, so a prefill resumed at
    a block boundary is bit-identical to the cold full-sequence prefill
    (the DecodeState snapshot contract; see core/state.py).
    """
    d_inner = cfg.ssm_expand * cfg.d_model
    n, p = cfg.ssm_state, cfg.ssm_head_dim
    heads = d_inner // p
    dt_f = jnp.float32
    z, xbc, dt_raw = _split(params, cfg, x)

    if mode == "decode":
        # every leaf changes with each token: the whole node is written
        node = layer_node(cache, layer)
        xbc_conv, conv_state = _conv(params, xbc, node.conv)
        xin = xbc_conv[..., :d_inner]
        bmat = xbc_conv[..., d_inner:d_inner + n]
        cmat = xbc_conv[..., d_inner + n:]
        dt = jax.nn.softplus(dt_raw.astype(dt_f) + params["dt_bias"])
        a = -jnp.exp(params["A_log"].astype(dt_f)) * dt[:, 0]       # (B,H)
        xh = xin[:, 0].reshape(-1, heads, p).astype(dt_f)
        hs = jnp.exp(a)[..., None, None] * node.h + \
            dt[:, 0, :, None, None] * jnp.einsum("bn,bhp->bhnp", bmat[:, 0].astype(dt_f), xh)
        y = jnp.einsum("bn,bhnp->bhp", cmat[:, 0].astype(dt_f), hs)
        y = y + params["D"][None, :, None] * xh
        y = y.reshape(-1, 1, d_inner)
        new_cache = put_layer_node(cache, layer,
                                   RecurrentCache(h=hs, conv=conv_state))
    else:
        resume = mode == "prefill" and cache is not None
        xbc_conv, conv_state = _conv(params, xbc,
                                     cache.conv if resume else None)
        xin = xbc_conv[..., :d_inner]
        bmat = xbc_conv[..., d_inner:d_inner + n]
        cmat = xbc_conv[..., d_inner + n:]
        dt = jax.nn.softplus(dt_raw.astype(dt_f) + params["dt_bias"])
        xh = xin.reshape(*xin.shape[:2], heads, p)
        if mode == "prefill":
            y, h_final = ssd_chunked(
                xh, bmat, cmat, dt, params["A_log"],
                chunk=cfg.lt_block_size, h0=cache.h if resume else None,
                return_state=True, fixed_grid=True)
            new_cache = RecurrentCache(h=h_final, conv=conv_state)
        else:
            y = ssd_chunked(xh, bmat, cmat, dt, params["A_log"],
                            chunk=min(64, x.shape[1]))
            new_cache = None
        y = y + params["D"][None, None, :, None] * xh.astype(dt_f)
        y = y.reshape(*x.shape[:2], d_inner)

    y = y.astype(x.dtype) * jax.nn.silu(z)
    y32 = y.astype(jnp.float32)
    y = (y32 * jax.lax.rsqrt(jnp.mean(y32 * y32, -1, keepdims=True) + 1e-6)
         * params["norm_scale"]).astype(x.dtype)
    return y @ params["out_proj"].astype(x.dtype), new_cache


def ssm_init_cache(cfg, batch, dtype=jnp.float32) -> RecurrentCache:
    d_inner = cfg.ssm_expand * cfg.d_model
    n, p = cfg.ssm_state, cfg.ssm_head_dim
    heads = d_inner // p
    conv_dim = d_inner + 2 * n
    return RecurrentCache(
        h=jnp.zeros((batch, heads, n, p), jnp.float32),
        conv=jnp.zeros((batch, cfg.ssm_conv - 1, conv_dim), dtype),
    )


register_state(StateSpec(
    kind="ssd", node_type=RecurrentCache, granularity="token",
    resumable=True,
    init=lambda cfg, batch, max_len, dtype: ssm_init_cache(cfg, batch,
                                                           dtype),
    # batch-only: the depthwise conv mixes d_inner+2n channels, so a
    # per-head split would cut across a reduced dim (bit-parity hazard)
    shard_axes=batch_shard_axes))


def ssd_sequential_ref(x, b, c, dt, a_log):
    """Token-by-token oracle for tests."""
    f32 = jnp.float32
    bs, s, h, p = x.shape
    n = b.shape[-1]
    dt = dt.astype(f32)
    a = -jnp.exp(a_log.astype(f32))[None, None, :] * dt

    def step(hstate, inp):
        xt, bt, ct, at, dtt = inp
        hstate = jnp.exp(at)[..., None, None] * hstate + \
            dtt[..., None, None] * jnp.einsum("bn,bhp->bhnp", bt, xt)
        yt = jnp.einsum("bn,bhnp->bhp", ct, hstate)
        return hstate, yt

    init = jnp.zeros((bs, h, n, p), f32)
    xs = (x.transpose(1, 0, 2, 3).astype(f32), b.transpose(1, 0, 2).astype(f32),
          c.transpose(1, 0, 2).astype(f32), a.transpose(1, 0, 2),
          dt.transpose(1, 0, 2))
    _, ys = jax.lax.scan(step, init, xs)
    return ys.transpose(1, 0, 2, 3)
