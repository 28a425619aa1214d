"""Uniform model facade: (init, apply, init_cache, input_specs) per config.

`serve_step`/`train_step` factories in train/ and serve/ consume this; the
dry-run lowers these functions for every (arch x shape) cell.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.core.state import DecodeState
from repro.models import transformer as tfm
from repro.models import whisper as whp


class Model(NamedTuple):
    cfg: Any
    init: Any          # (key) -> (params, axes)
    apply: Any         # (params, batch, mode, cache, impl) -> (logits, cache, aux)
    init_cache: Any    # (params, batch_size, max_len) -> cache
    init_slot_cache: Any = None  # (params, max_len) -> batch-1 cache (serving)
    state: DecodeState | None = None  # decode-state protocol (None: unservable)


def build_model(cfg) -> Model:
    if cfg.family == "audio":
        def init(key):
            return whp.whisper_init(key, cfg)

        def apply(params, batch, *, mode="train", cache=None, impl=None,
                  positions=None):
            if mode in ("train", "prefill"):
                memory = whp.whisper_encode(params, cfg, batch["frames"])
            else:
                memory = None
            logits, new_cache = whp.whisper_decode(
                params, cfg, batch["tokens"], memory, mode=mode, cache=cache,
                positions=positions, impl=impl)
            return logits, new_cache, {}

        def init_cache(params, batch_size, max_len):
            return whp.whisper_init_cache(params, cfg, batch_size, max_len)

        # no DecodeState: ServeEngine rejects models without one (the slot
        # machinery doesn't carry cross-attention/encoder state, and the
        # prefill needs encoder frames the token-only protocol can't feed)
        return Model(cfg, init, apply, init_cache)

    def init(key):
        return tfm.lm_init(key, cfg)

    def apply(params, batch, *, mode="train", cache=None, impl=None,
              positions=None, fold_due=None):
        return tfm.lm_apply(params, cfg, batch["tokens"], mode=mode,
                            cache=cache, positions=positions,
                            image_embeds=batch.get("image_embeds"), impl=impl,
                            fold_due=fold_due)

    def init_cache(params, batch_size, max_len):
        return tfm.lm_init_cache(params, cfg, batch_size, max_len)

    def init_slot_cache(params, max_len):
        return tfm.lm_init_slot_cache(params, cfg, max_len)

    state = DecodeState(cfg, apply, init_cache, init_slot_cache)
    return Model(cfg, init, apply, init_cache, init_slot_cache, state)


def input_specs(cfg, shape, *, for_train: bool | None = None) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of a shape cell.

    train:   tokens (B, S+1) — the step shifts internally.
    prefill: tokens (B, S).
    decode:  tokens (B, 1) + the cache is built separately.
    """
    b, s = shape.global_batch, shape.seq_len
    kind = shape.kind if for_train is None else ("train" if for_train else shape.kind)
    tok = jnp.int32
    specs: dict[str, Any] = {}
    if kind == "train":
        specs["tokens"] = jax.ShapeDtypeStruct((b, s + 1), tok)
    elif kind == "prefill":
        specs["tokens"] = jax.ShapeDtypeStruct((b, s), tok)
    else:  # decode: one new token against a seq_len-deep cache
        specs["tokens"] = jax.ShapeDtypeStruct((b, 1), tok)
    if cfg.family == "vlm" and kind != "decode":
        specs["image_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_image_tokens, cfg.d_model), jnp.dtype(cfg.compute_dtype))
    if cfg.family == "audio" and kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_len, cfg.d_model), jnp.dtype(cfg.compute_dtype))
    return specs
