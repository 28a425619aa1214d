"""Decoder-only transformer LM supporting all assigned architecture families.

Layers are organized in repeating *pattern groups* (e.g. recurrentgemma's
(rglru, rglru, local_attn)); parameters are stacked over groups and the
stack is traversed with lax.scan so the HLO stays small for 40+ layer
models. A remainder (n_layers % pattern) is handled as an unscanned tail.

Each block: pre-norm -> mixer -> residual; pre-norm -> ffn -> residual.
The mixer is attn (softmax|polynomial|polysketch — the paper's knob),
local_attn (sliding window), rglru, or ssd. The ffn is GLU or MoE
(interleaved via moe_period).
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from repro.core import state as core_state
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import ssm as ssm_mod
from repro.distributed.sharding import (device_layouts, keep_layouts,
                                        shard_act)
from repro.models.layers import (
    embedding_init, glu_ffn_apply, glu_ffn_init, norm_apply, norm_init,
)


def effective_pattern(cfg) -> tuple[tuple[str, str], ...]:
    """Per-layer (mixer, ffn) cycle of length lcm(|pattern|, moe_period)."""
    mixers = cfg.block_pattern
    period = cfg.moe_period if cfg.ffn == "moe" else 1
    g = math.lcm(len(mixers), period)
    out = []
    for i in range(g):
        mixer = mixers[i % len(mixers)]
        ffn = "moe" if (cfg.ffn == "moe" and (i % period == period - 1)) else "glu"
        out.append((mixer, ffn))
    return tuple(out)


def _block_init(key, cfg, mixer_kind, ffn_kind):
    k1, k2 = jax.random.split(key)
    params, axes = {}, {}
    params["norm1"], axes["norm1"] = norm_init(cfg.d_model, cfg.norm)
    params["norm2"], axes["norm2"] = norm_init(cfg.d_model, cfg.norm)
    if mixer_kind in ("attn", "local_attn"):
        params["mixer"], axes["mixer"] = attn.attention_init(k1, cfg, mixer_kind)
    elif mixer_kind == "rglru":
        params["mixer"], axes["mixer"] = rglru_mod.rglru_init(k1, cfg)
    elif mixer_kind == "ssd":
        params["mixer"], axes["mixer"] = ssm_mod.ssm_init(k1, cfg)
    else:
        raise ValueError(mixer_kind)
    if ffn_kind == "moe":
        params["ffn"], axes["ffn"] = moe_mod.moe_init(k2, cfg)
    elif cfg.d_ff > 0:
        params["ffn"], axes["ffn"] = glu_ffn_init(k2, cfg.d_model, cfg.d_ff)
    else:  # attention/mixer-only blocks (mamba2)
        del params["norm2"], axes["norm2"]
    return params, axes


def _stack_init(key, cfg, pattern, n_groups):
    """Init n_groups copies of the pattern, stacked over a leading axis."""
    params_list, axes = [], None
    for gi in range(n_groups):
        gp = {}
        for bi, (mk, fk) in enumerate(pattern):
            bk = jax.random.fold_in(key, gi * 131 + bi)
            gp[f"block{bi}"], a = _block_init(bk, cfg, mk, fk)
            if gi == 0:
                if axes is None:
                    axes = {}
                axes[f"block{bi}"] = a
        params_list.append(gp)
    stacked = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *params_list)
    axes = jax.tree_util.tree_map(
        lambda names: ("layers",) + tuple(names), axes,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x))
    return stacked, axes


def lm_init(key, cfg):
    """Returns (params, axes) for the full LM."""
    pattern = effective_pattern(cfg)
    g = len(pattern)
    n_groups, rem = divmod(cfg.n_layers, g)
    ks = jax.random.split(key, 4)
    params, axes = {}, {}
    params["embed"], axes["embed"] = embedding_init(ks[0], cfg.vocab_size, cfg.d_model)
    params["groups"], axes["groups"] = _stack_init(ks[1], cfg, pattern, n_groups)
    if rem:
        tail_pattern = pattern[:rem]
        tp, ta = {}, {}
        for bi, (mk, fk) in enumerate(tail_pattern):
            tp[f"block{bi}"], ta[f"block{bi}"] = _block_init(
                jax.random.fold_in(ks[2], bi), cfg, mk, fk)
        params["tail"], axes["tail"] = tp, ta
    params["final_norm"], axes["final_norm"] = norm_init(cfg.d_model, cfg.norm)
    if not cfg.tie_embeddings:
        w = jax.random.normal(ks[3], (cfg.d_model, cfg.vocab_size), jnp.float32) * 0.02
        params["lm_head"], axes["lm_head"] = w, ("embed", "vocab")
    return params, axes


def _apply_block(bp, cfg, h, mixer_kind, ffn_kind, *, positions, mode, cache,
                 impl, layer=None):
    h = shard_act(h, "batch", "seq", "embed")
    # the scope names ("mixer", "ffn", and "embed", "logits" in lm_apply)
    # label the compiled ops in a device trace; tests pin the vocabulary
    with jax.named_scope("mixer"):
        hn = norm_apply(bp["norm1"], h)
        if mixer_kind in ("attn", "local_attn"):
            y, new_cache = attn.attention_apply(
                bp["mixer"], cfg, hn, kind=mixer_kind, positions=positions,
                mode=mode, cache=cache, impl=impl, layer=layer)
        elif mixer_kind == "rglru":
            y, new_cache = rglru_mod.rglru_apply(bp["mixer"], cfg, hn,
                                                 mode=mode, cache=cache,
                                                 layer=layer)
        else:
            y, new_cache = ssm_mod.ssm_apply(bp["mixer"], cfg, hn, mode=mode,
                                             cache=cache, layer=layer)
        h = h + y
    if "ffn" not in bp:  # mixer-only block (mamba2)
        return h, new_cache, {}
    with jax.named_scope("ffn"):
        hn = norm_apply(bp["norm2"], h)
        if ffn_kind == "moe":
            y, aux = moe_mod.moe_apply(bp["ffn"], cfg, hn)
        else:
            y, aux = glu_ffn_apply(bp["ffn"], hn), {}
        h = h + y
    return h, new_cache, aux


def _zero_aux(cfg):
    if cfg.ffn == "moe":
        return {"load_balance": jnp.zeros((), jnp.float32),
                "router_z": jnp.zeros((), jnp.float32)}
    return {}


def _add_aux(acc, aux):
    for k, v in aux.items():
        acc[k] = acc.get(k, jnp.zeros((), jnp.float32)) + v
    return acc


def lm_apply(params, cfg, tokens, *, mode: str = "train", cache=None,
             positions=None, image_embeds=None, impl: str | None = None,
             fold_due=None):
    """tokens: (B, S) int32 (S==1 for decode).

    In decode mode the layer loop carries the whole layer-stacked cache,
    and each layer's decode step writes its token's rows into it in place
    (core.decode); the blocks the step completed then fold, gated by
    `fold_due` (core.state.fold_blocks). Prefill scans the layers' caches
    as xs/ys, since a chunk rewrites whole buffers anyway.

    Returns (logits (B, S, V), new_cache, aux) — cache is None in train mode.
    """
    pattern = effective_pattern(cfg)
    g = len(pattern)
    n_groups, rem = divmod(cfg.n_layers, g)
    dt = jnp.dtype(cfg.compute_dtype)

    with jax.named_scope("embed"):
        h = (params["embed"]["table"].astype(dt)[tokens]
             * math.sqrt(cfg.d_model))
    if image_embeds is not None and cfg.n_image_tokens:
        n_img = image_embeds.shape[1]
        h = jnp.concatenate([image_embeds.astype(dt), h[:, n_img:]], axis=1)
    if positions is None:
        positions = jnp.arange(tokens.shape[1])

    aux_total = _zero_aux(cfg)
    gcaches = None if cache is None else cache["groups"]
    carried = mode == "decode" and gcaches is not None
    # the carried stack stays in the layout the device holds it in
    pins = (device_layouts(cache, core_state.state_shard_axes(cache))
            if carried else None)

    def group_body(carry, xs):
        # decode: the carry holds the whole stack, stepped at layer `li`;
        # otherwise xs hold this group's cache (None in training)
        hh, auxc, stack = carry
        gp, gcache, li = xs
        caches = stack if carried else gcache
        new_caches = {}
        for bi, (mk, fk) in enumerate(pattern):
            c_in = None if caches is None else caches[f"block{bi}"]
            hh, nc, aux = _apply_block(gp[f"block{bi}"], cfg, hh, mk, fk,
                                       positions=positions, mode=mode,
                                       cache=c_in, impl=impl,
                                       layer=li if carried else None)
            new_caches[f"block{bi}"] = nc
            auxc = _add_aux(auxc, aux)
        if carried:
            return (hh, auxc, keep_layouts(new_caches, pins and
                                           pins["groups"])), None
        return (hh, auxc, None), new_caches

    body = group_body
    if cfg.remat == "dots":
        body = jax.checkpoint(group_body,
                              policy=jax.checkpoint_policies.checkpoint_dots)
    elif cfg.remat == "full":
        body = jax.checkpoint(group_body)

    carry = (h, aux_total, gcaches if carried else None)
    if n_groups and cfg.unroll_layers:
        # Python loop (HLO contains every layer; used by dry-run cost probes)
        ncs = []
        for gi in range(n_groups):
            gp = jax.tree_util.tree_map(lambda x: x[gi], params["groups"])
            gc = (None if gcaches is None or carried else
                  jax.tree_util.tree_map(lambda x: x[gi], gcaches))
            carry, nc = body(carry, (gp, gc, gi))
            ncs.append(nc)
        (h, aux_total, stack) = carry
        new_group_caches = (stack if carried or gcaches is None else
                            jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *ncs))
    elif n_groups:
        if gcaches is None:
            # scan needs matching pytree structures in xs; substitute params-only
            (h, aux_total, _), _ = jax.lax.scan(
                lambda c, p: (body(c, (p, None, None))[0], 0.0),
                carry, params["groups"])
            new_group_caches = None
        else:
            xs = (params["groups"], None if carried else gcaches,
                  jnp.arange(n_groups))
            (h, aux_total, stack), ys = jax.lax.scan(body, carry, xs)
            new_group_caches = stack if carried else ys
    else:
        new_group_caches = gcaches

    new_tail = None
    if rem:
        new_tail = {}
        for bi, (mk, fk) in enumerate(pattern[:rem]):
            c_in = None if cache is None else cache["tail"][f"block{bi}"]
            h, nc, aux = _apply_block(params["tail"][f"block{bi}"], cfg, h, mk,
                                      fk, positions=positions, mode=mode,
                                      cache=c_in, impl=impl)
            new_tail[f"block{bi}"] = nc
            aux_total = _add_aux(aux_total, aux)

    with jax.named_scope("logits"):
        h = norm_apply(params["final_norm"], h)
        if cfg.tie_embeddings:
            logits = jnp.einsum("bsd,vd->bsv", h,
                                params["embed"]["table"].astype(dt))
        else:
            logits = h @ params["lm_head"].astype(dt)

    new_cache = None
    if mode in ("decode", "prefill"):
        new_cache = {"groups": new_group_caches}
        if rem:
            new_cache["tail"] = new_tail
    if mode == "decode":
        new_cache = core_state.fold_blocks(new_cache, fold_due)
    return logits, new_cache, aux_total


def lm_init_cache(params, cfg, batch: int, max_len: int):
    """Build the decode cache pytree (stacked over groups).

    Every mixer's cache node comes from its registered StateSpec
    (core.state) — no per-family branching here."""
    pattern = effective_pattern(cfg)
    g = len(pattern)
    n_groups, rem = divmod(cfg.n_layers, g)
    dt = jnp.dtype(cfg.compute_dtype)

    def one_block(mk):
        spec = core_state.get_spec(core_state.mixer_state_kind(cfg, mk))
        return spec.init(cfg, batch, max_len, dt)

    group_cache = {f"block{bi}": one_block(mk)
                   for bi, (mk, _) in enumerate(pattern)}
    stacked = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (n_groups, *x.shape)).copy() if n_groups else x,
        group_cache)
    out = {"groups": stacked if n_groups else None}
    if rem:
        out["tail"] = {f"block{bi}": one_block(mk)
                       for bi, (mk, _) in enumerate(pattern[:rem])}
    return out


def lm_init_slot_cache(params, cfg, max_len: int):
    """Decode cache for one serve slot: batch 1, per-slot `pos` scalars.

    The serve engine stacks these layer-major
    (core.decode.broadcast_slot_caches): the slot axis takes the batch
    axis's place, behind the layer axis, and each `pos` gains a slot axis
    there too, so every slot advances its own position — the batched cache
    from lm_init_cache shares one `pos` and cannot represent slots at
    different depths.
    """
    return lm_init_cache(params, cfg, 1, max_len)
