"""DecodeState protocol conformance, parameterized over every registered
state kind: registry capabilities, init/prefill/decode bit-parity against
the raw model.apply paths (the pre-protocol surface), snapshot -> restore
-> resume bit-parity for every spec that declares snapshot support
(including the SSM/RG-LRU recurrent kinds), serialization round-trips, and
composite-granularity rules for hybrid models."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import decode as dec
from repro.core.state import (REGISTRY, bucket_chunks, composite_granularity,
                              get_spec, mixer_state_kind, state_kinds)
from repro.models import build_model

BLK = 16

# every registered kind, the smoke config that exercises it, and its
# declared capabilities (granularity, resumable)
KIND_SETUPS = {
    "polysketch": ("gpt2s-polysketch", {}, "block", True),
    "kv_full": ("gpt2s-polysketch", dict(attention="softmax"), None, False),
    "poly_kv": ("gpt2s-polysketch", dict(attention="polynomial"), None, False),
    "kv_ring": ("gpt2s-polysketch",
                dict(block_pattern=("local_attn",), sliding_window=8),
                "token", True),
    "ssd": ("mamba2-780m", dict(lt_block_size=BLK), "token", True),
    "rglru": ("recurrentgemma-9b",
              dict(block_pattern=("rglru",), lt_block_size=BLK),
              "token", True),
}

SNAPSHOT_KINDS = [k for k, (_, _, g, _) in KIND_SETUPS.items()
                  if g is not None]


@functools.lru_cache(maxsize=None)
def _setup(kind):
    arch, overrides, _, _ = KIND_SETUPS[kind]
    cfg = get_config(arch, smoke=True).replace(**overrides)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(sum(map(ord, kind))))
    return model, cfg, params


def _tokens(cfg, n, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, cfg.vocab_size, n), jnp.int32)


def _leaves_equal(a, b):
    la, lb = map(jax.tree_util.tree_leaves, (a, b))
    assert len(la) == len(lb)
    return all(bool(jnp.array_equal(x, y)) for x, y in zip(la, lb))


def test_registry_complete_and_capabilities_declared():
    for kind, (_, _, gran, resumable) in KIND_SETUPS.items():
        spec = get_spec(kind)
        assert spec.kind == kind
        assert spec.granularity == gran, kind
        assert spec.resumable == resumable, kind
    assert set(KIND_SETUPS) <= set(REGISTRY)


@pytest.mark.parametrize("kind", list(KIND_SETUPS))
def test_model_state_kinds_and_capabilities(kind):
    model, cfg, _ = _setup(kind)
    st = model.state
    assert st.kinds == (kind,)
    _, _, gran, resumable = KIND_SETUPS[kind]
    assert st.snapshot_granularity == gran
    assert st.resumable == resumable
    assert st.block_size == cfg.lt_block_size


@pytest.mark.parametrize("kind", list(KIND_SETUPS))
def test_prefill_decode_bit_parity_vs_raw_apply(kind):
    """The protocol adds no transform: DecodeState.prefill / decode_step
    bit-match the raw model.apply path (the pre-protocol engine surface)
    for every kind — init_cache shapes included."""
    model, cfg, params = _setup(kind)
    st = model.state
    prompt = _tokens(cfg, 21, seed=1)[None]
    max_len = 40

    raw_cache = model.init_cache(params, 1, max_len)
    assert _leaves_equal(raw_cache, st.init(params, 1, max_len))
    raw_logits, raw_cache, _ = model.apply(
        params, {"tokens": prompt}, mode="prefill", cache=raw_cache)

    logits, cache = st.prefill(params, prompt, st.init(params, 1, max_len))
    assert bool(jnp.array_equal(logits, raw_logits[:, -1]))
    assert _leaves_equal(cache, raw_cache)

    tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    for t in range(3):
        pos = jnp.asarray(21 + t, jnp.int32)
        raw_logits, raw_cache, _ = model.apply(
            params, {"tokens": tok}, mode="decode", cache=raw_cache,
            positions=pos[None])
        logits, cache = st.decode_step(params, tok, pos, cache)
        assert bool(jnp.array_equal(logits, raw_logits[:, -1])), (kind, t)
        assert _leaves_equal(cache, raw_cache), (kind, t)
        tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)


@pytest.mark.parametrize("suffix", [BLK, BLK + 5, 3])
@pytest.mark.parametrize("kind", SNAPSHOT_KINDS)
def test_snapshot_restore_resume_bit_parity(kind, suffix):
    """For every snapshot-capable spec: prefill(prefix) -> snapshot ->
    restore -> resume(suffix) equals the cold full prefill bit-for-bit
    (logits AND final state), then decodes identically."""
    model, cfg, params = _setup(kind)
    st = model.state
    n0 = 2 * BLK
    prompt = _tokens(cfg, n0 + suffix, seed=suffix)[None]
    max_len = prompt.shape[1] + 8

    logits_cold, state_cold = st.prefill(params, prompt,
                                         st.init_slot(params, max_len))

    _, state_pfx = st.prefill(params, prompt[:, :n0],
                              st.init_slot(params, max_len))
    snap = st.snapshot(state_pfx)
    restored = st.restore(st.init_slot(params, max_len), snap,
                          jnp.asarray(n0, jnp.int32))
    logits_res, state_res = st.resume(params, prompt[:, n0:], restored, n0)

    assert bool(jnp.array_equal(logits_res, logits_cold))
    assert _leaves_equal(state_res, state_cold)

    tok = jnp.argmax(logits_cold, axis=-1)[:, None].astype(jnp.int32)
    pos = jnp.asarray(prompt.shape[1], jnp.int32)
    d_cold, _ = st.decode_step(params, tok, pos, state_cold)
    d_res, _ = st.decode_step(params, tok, pos, state_res)
    assert bool(jnp.array_equal(d_cold, d_res))


@pytest.mark.parametrize("kind", SNAPSHOT_KINDS)
def test_snapshot_serialize_roundtrip(kind):
    """serialize -> deserialize reproduces the snapshot leaves and position
    exactly (the on-disk persistence seam)."""
    model, cfg, params = _setup(kind)
    st = model.state
    prompt = _tokens(cfg, 2 * BLK, seed=9)[None]
    _, state = st.prefill(params, prompt, st.init_slot(params, 64))
    snap = st.snapshot(state)
    data = st.serialize(snap, 2 * BLK)
    assert isinstance(data, bytes) and len(data) > 0
    snap2, n = st.deserialize(data)
    assert n == 2 * BLK
    assert _leaves_equal(snap, snap2)
    # a restored-from-disk snapshot resumes exactly like the original
    r1 = st.restore(st.init_slot(params, 64), snap,
                    jnp.asarray(2 * BLK, jnp.int32))
    r2 = st.restore(st.init_slot(params, 64), snap2,
                    jnp.asarray(2 * BLK, jnp.int32))
    assert _leaves_equal(r1, r2)


@pytest.mark.parametrize("kind", [k for k in KIND_SETUPS
                                  if k not in SNAPSHOT_KINDS])
def test_unsupported_snapshot_raises(kind):
    model, cfg, params = _setup(kind)
    st = model.state
    assert st.snapshot_granularity is None
    with pytest.raises(ValueError):
        st.snapshot(st.init_slot(params, 32))


def test_composite_granularity_weakest_member():
    """A model mixing kinds gets the weakest member's capability: the
    recurrentgemma hybrid (rglru + ring-KV local attention) snapshots at
    token granularity since the ring gained O(W) snapshots; a pure-block
    mix stays block; any token member forces token (split-at-boundary)
    behavior; a full-KV member disables snapshots."""
    hybrid = get_config("recurrentgemma-9b", smoke=True)
    assert state_kinds(hybrid) == ("rglru", "kv_ring")
    assert composite_granularity(state_kinds(hybrid)) == "token"
    st = build_model(hybrid).state
    assert st.snapshot_granularity == "token" and st.resumable
    assert composite_granularity(("polysketch",)) == "block"
    assert composite_granularity(("polysketch", "ssd")) == "token"
    assert composite_granularity(("ssd", "rglru")) == "token"
    assert composite_granularity(("rglru", "kv_full")) is None


def test_mixer_state_kind_mapping():
    cfg = get_config("gpt2s-polysketch", smoke=True)
    assert mixer_state_kind(cfg, "attn") == "polysketch"
    assert mixer_state_kind(cfg.replace(attention="softmax"), "attn") == "kv_full"
    assert mixer_state_kind(cfg.replace(attention="polynomial"), "attn") == "poly_kv"
    assert mixer_state_kind(cfg, "local_attn") == "kv_ring"
    assert mixer_state_kind(cfg, "ssd") == "ssd"
    assert mixer_state_kind(cfg, "rglru") == "rglru"
    with pytest.raises(ValueError):
        mixer_state_kind(cfg, "encoder_attn")


def test_slot_helpers_roundtrip_recurrent_state():
    """broadcast -> scatter -> gather works for position-free recurrent
    nodes exactly as for the attention caches."""
    model, cfg, params = _setup("ssd")
    st = model.state
    one = st.init_slot(params, 32)
    slots = st.broadcast_slots(one, 3)
    filled = jax.tree_util.tree_map(lambda x: x + 1.0, one)
    slots = st.slot_scatter(slots, filled, jnp.asarray(2, jnp.int32))
    got = st.slot_gather(slots, jnp.asarray(2, jnp.int32))
    assert _leaves_equal(got, filled)
    other = st.slot_gather(slots, jnp.asarray(0, jnp.int32))
    assert _leaves_equal(other, one)


@pytest.mark.parametrize("kind", list(KIND_SETUPS))
def test_slot_stack_roundtrip_and_snapshot_bytes(kind):
    """A prefilled batch-1 cache scattered into the layer-major slot stack
    gathers back bit for bit, its neighbours untouched, and a snapshot of
    the gathered slot serializes to the same bytes as one taken before
    stacking."""
    model, cfg, params = _setup(kind)
    st = model.state
    one = st.init_slot(params, 48)
    _, filled = st.prefill(params, _tokens(cfg, 2 * BLK, seed=3)[None],
                           st.init_slot(params, 48))
    stack = st.broadcast_slots(one, 3)
    # layer-major: the slot axis sits behind the layer axis, in the batch
    # axis's place, and every layer keeps a position per slot
    for a, b in zip(jax.tree_util.tree_leaves(one["groups"]),
                    jax.tree_util.tree_leaves(stack["groups"])):
        want = ((a.shape[0], 3) + a.shape[2:] if a.ndim > 1
                else a.shape + (3,))
        assert b.shape == want, (a.shape, b.shape)
    stack = st.slot_scatter(stack, filled, jnp.asarray(1, jnp.int32))
    got = st.slot_gather(stack, jnp.asarray(1, jnp.int32))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(filled)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(filled)):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_array_equal(a, b)
    assert _leaves_equal(st.slot_gather(stack, jnp.asarray(0, jnp.int32)),
                         one)
    if st.snapshot_granularity is not None:
        assert (st.serialize(st.snapshot(got), 2 * BLK)
                == st.serialize(st.snapshot(filled), 2 * BLK))


def test_audio_model_has_no_decode_state():
    cfg = get_config("whisper-large-v3", smoke=True)
    assert build_model(cfg).state is None


def test_bucket_chunks_edges():
    assert bucket_chunks(0, 0, 16) == []
    assert bucket_chunks(16, 16, 16) == []
    assert bucket_chunks(0, 5, 16) == [5]
    assert bucket_chunks(0, 16, 16) == [16]
    assert bucket_chunks(0, 37, 16) == [32, 37]
    assert bucket_chunks(16, 96, 16) == [80, 96]        # 5 blocks = 4 + 1
    assert bucket_chunks(32, 32 + 7 * 16 + 3, 16) == [32 + 4 * 16,
                                                      32 + 6 * 16,
                                                      32 + 7 * 16,
                                                      32 + 7 * 16 + 3]
