"""Continuous-batching engine invariants: per-request parity with
single-request `generate`, no cross-slot contamination for mixed prompt
lengths, independent per-slot EOS stop, and FIFO queue draining with more
requests than slots."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core.decode import PolysketchCache
from repro.models import build_model
from repro.serve import SamplingParams, ServeEngine, Telemetry, generate
from repro.serve.engine import make_decode_tick
from repro.serve.plan import ServePlan
from repro.serve.sampling import init_slot_keys, init_slot_sampling


def _setup(seed=0, **overrides):
    cfg = get_config("gpt2s-polysketch", smoke=True)
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(seed))
    return model, cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, cfg.vocab_size, n), jnp.int32)
            for n in lens]


def _ref_tokens(model, cfg, params, prompt, steps):
    return np.asarray(generate(model, cfg, params, prompt[None], steps).tokens[0])


def test_engine_matches_generate_per_request():
    """Each engine output bit-matches the single-request generate() path."""
    model, cfg, params = _setup()
    lens, steps = [5, 12, 23], [6, 8, 4]
    prompts = _prompts(cfg, lens)
    eng = ServeEngine(model, cfg, params, slots=3, max_len=64)
    for p, n in zip(prompts, steps):
        eng.submit(p, n)
    outs = {o.rid: o for o in eng.run()}
    assert len(outs) == 3
    for rid, (p, n) in enumerate(zip(prompts, steps)):
        np.testing.assert_array_equal(
            outs[rid].tokens, _ref_tokens(model, cfg, params, p, n))
        assert outs[rid].finish_reason == "length"
        assert outs[rid].prompt_len == p.shape[0]


def test_mixed_lengths_no_cross_slot_contamination():
    """Prompt lengths straddling the lt block size (16) share one decode
    batch; every slot must still match its solo run exactly."""
    model, cfg, params = _setup(seed=3)
    lens = [3, 16, 17, 40]  # < blk, == blk, blk+1, multi-block
    prompts = _prompts(cfg, lens, seed=3)
    eng = ServeEngine(model, cfg, params, slots=4, max_len=64)
    for p in prompts:
        eng.submit(p, 8)
    outs = {o.rid: o for o in eng.run()}
    for rid, p in enumerate(prompts):
        np.testing.assert_array_equal(
            outs[rid].tokens, _ref_tokens(model, cfg, params, p, 8))


def test_eos_stops_slot_early_while_others_continue():
    model, cfg, params = _setup(seed=1)
    prompts = _prompts(cfg, [8, 9], seed=1)
    ref_a = _ref_tokens(model, cfg, params, prompts[0], 10)
    ref_b = _ref_tokens(model, cfg, params, prompts[1], 10)
    eos = int(ref_a[3])  # greedy path hits this at step 3
    eng = ServeEngine(model, cfg, params, slots=2, max_len=32)
    eng.submit(prompts[0], 10, eos_id=eos)
    eng.submit(prompts[1], 10)
    outs = {o.rid: o for o in eng.run()}
    assert outs[0].finish_reason == "eos"
    assert outs[0].tokens[-1] == eos
    assert len(outs[0].tokens) <= 4  # stopped at (or before) the known hit
    np.testing.assert_array_equal(outs[0].tokens,
                                  ref_a[:len(outs[0].tokens)])
    # the other slot was untouched by the early retirement
    assert outs[1].finish_reason == "length"
    np.testing.assert_array_equal(outs[1].tokens, ref_b)


def test_queue_longer_than_slots_drains_in_arrival_order():
    model, cfg, params = _setup(seed=2)
    lens = [7, 20, 15, 31, 9, 12, 25]
    prompts = _prompts(cfg, lens, seed=2)
    eng = ServeEngine(model, cfg, params, slots=2, max_len=64)
    rids = [eng.submit(p, 5) for p in prompts]
    outs = eng.run()
    # complete drain, FIFO completion (equal generation lengths)
    assert [o.rid for o in outs] == rids
    assert not eng.busy and eng.n_active == 0
    for o in outs:
        np.testing.assert_array_equal(
            o.tokens, _ref_tokens(model, cfg, params, prompts[o.rid], 5))


@pytest.mark.parametrize("overrides", [dict(attention="softmax"),
                                       dict(n_kv_heads=2)])
def test_engine_other_cache_paths(overrides):
    """The slot machinery is cache-type agnostic: softmax KV and GQA
    polysketch slots behave identically to their solo runs."""
    model, cfg, params = _setup(seed=4, **overrides)
    prompts = _prompts(cfg, [6, 19], seed=4)
    eng = ServeEngine(model, cfg, params, slots=2, max_len=48)
    for p in prompts:
        eng.submit(p, 6)
    outs = {o.rid: o for o in eng.run()}
    for rid, p in enumerate(prompts):
        np.testing.assert_array_equal(
            outs[rid].tokens, _ref_tokens(model, cfg, params, p, 6))


def test_free_slot_pos_frozen_during_long_drain():
    """A slot that retires early (deep prompt, quick EOS) must not keep
    advancing its position while other slots drain: for KV-cache families
    `pos` indexes the cache and feeds RoPE, so an unbounded stale-decode
    drift could push it past max_len. Frozen slots stay put."""
    model, cfg, params = _setup(seed=6, attention="softmax")
    prompts = _prompts(cfg, [15, 4], seed=6)
    ref_a = _ref_tokens(model, cfg, params, prompts[0], 3)
    eos = int(ref_a[1])  # retire slot 0 after its second token
    eng = ServeEngine(model, cfg, params, slots=2, max_len=21)
    eng.submit(prompts[0], 3, eos_id=eos)
    eng.submit(prompts[1], 16)   # drains for many more ticks
    frozen = None
    outs = {}
    while eng.busy:
        for o in eng.step():
            outs[o.rid] = o
        if not eng._slots[0].free:
            continue
        pos0 = int(np.asarray(eng._slot_pos)[0])
        if frozen is None:
            frozen = pos0          # position at retirement
        assert pos0 == frozen      # never advances again
    assert frozen is not None and frozen <= eng.max_len
    assert int(np.asarray(eng._slot_pos).max()) <= eng.max_len
    # the freeze never disturbed the live slot
    np.testing.assert_array_equal(
        outs[1].tokens, _ref_tokens(model, cfg, params, prompts[1], 16))


def test_submit_rejects_invalid_requests():
    model, cfg, params = _setup()
    with pytest.raises(ValueError):
        ServeEngine(model, cfg, params, slots=0)  # would spin forever
    eng = ServeEngine(model, cfg, params, slots=1, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(jnp.zeros((12,), jnp.int32), 8)   # overflows max_len
    with pytest.raises(ValueError):
        eng.submit(jnp.zeros((0,), jnp.int32), 4)    # empty prompt
    with pytest.raises(ValueError):
        eng.submit(jnp.zeros((4,), jnp.int32), 0)    # no token budget


def test_stats_count_live_slots_mid_run():
    """Regression: stats() must count tokens emitted by requests still
    resident in a slot — total_decode_s includes their ticks, so counting
    only self.finished biased mid-drain throughput low."""
    model, cfg, params = _setup()
    eng = ServeEngine(model, cfg, params, slots=1, max_len=32)
    eng.submit(_prompts(cfg, [6])[0], 8)
    for _ in range(3):    # each step: (admit at step 1) + one decode tick
        eng.step()
    st = eng.stats()
    assert not eng._slots[0].free and st["requests"] == 0
    assert st["active_requests"] == 1
    assert st["generated_tokens"] == 4   # prefill token + 3 decode ticks
    assert st["decode_tok_per_s"] > 0
    # draining moves the same tokens from live to finished, never drops any
    eng.run()
    st = eng.stats()
    assert st["requests"] == 1 and st["active_requests"] == 0
    assert st["generated_tokens"] == 8


@pytest.mark.parametrize("overrides", [
    dict(),                                            # polysketch cache
    dict(block_pattern=("local_attn",), sliding_window=8),  # kv_ring cache
])
def test_generate_rejects_max_len_overflow(overrides):
    """Regression: generate() must reject s0 + steps > max_len like
    ServeEngine.submit — KV-cache families' `dynamic_update_index_in_dim`
    would silently clamp and corrupt the last cache slot instead."""
    model, cfg, params = _setup(seed=5, **overrides)
    prompt = _prompts(cfg, [10], seed=5)[0][None]
    with pytest.raises(ValueError, match="max_len"):
        generate(model, cfg, params, prompt, 8, max_len=12)
    # the boundary itself is fine
    generate(model, cfg, params, prompt, 2, max_len=12)


def test_free_slot_tokens_preserved_between_retire_and_admit():
    """Regression: a free slot's feed token must survive decode ticks —
    the stale-state decode's output is garbage, and a retire -> step ->
    admit interleaving must never observe it in `_slot_tokens`."""
    model, cfg, params = _setup(seed=7)
    prompts = _prompts(cfg, [5, 9, 14], seed=7)
    eng = ServeEngine(model, cfg, params, slots=2, max_len=48)
    eng.submit(prompts[0], 2)          # retires quickly
    # the survivor is SAMPLED so the tick takes the mixed (key-splitting)
    # path — the all-greedy fast path would trivially preserve keys
    eng.submit(prompts[1], 12, sampling=SamplingParams(temperature=0.7,
                                                       seed=3))
    eng.step()                         # admit both + first decode tick
    assert not eng._slots[1].free
    while not eng._slots[0].free:
        eng.step()
    # sentinel the free slot's state: no decode output can ever equal it,
    # so any overwrite by the stale-state decode is caught deterministically
    eng._slot_tokens = eng._slot_tokens.at[0, 0, 0].set(-1)
    eng._slot_keys = eng._slot_keys.at[0].set(
        jnp.asarray([0xDEAD, 0xBEEF], jnp.uint32))
    for _ in range(3):                 # retire -> step (slot 0 stays free)
        eng.step()
    assert int(np.asarray(eng._slot_tokens)[0, 0, 0]) == -1
    np.testing.assert_array_equal(np.asarray(eng._slot_keys)[0],
                                  np.asarray([0xDEAD, 0xBEEF], np.uint32))
    # -> admit: the late request still bit-matches its solo run
    eng.submit(prompts[2], 6)
    outs = {o.rid: o for o in eng.run()}
    np.testing.assert_array_equal(
        outs[2].tokens, _ref_tokens(model, cfg, params, prompts[2], 6))


def test_ssm_family_engine_matches_generate():
    """The slot machinery is family-agnostic through DecodeState: an
    SSM-family (mamba2) engine bit-matches its solo generate() runs,
    mixed prompt lengths sharing a batch."""
    cfg = get_config("mamba2-780m", smoke=True).replace(lt_block_size=16)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(9))
    prompts = _prompts(cfg, [5, 19, 33], seed=9)
    eng = ServeEngine(model, cfg, params, slots=3, max_len=64)
    for p in prompts:
        eng.submit(p, 6)
    outs = {o.rid: o for o in eng.run()}
    for rid, p in enumerate(prompts):
        np.testing.assert_array_equal(
            outs[rid].tokens, _ref_tokens(model, cfg, params, p, 6))


def test_audio_model_rejected_without_decode_state():
    cfg = get_config("whisper-large-v3", smoke=True)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    assert model.state is None
    with pytest.raises(NotImplementedError):
        ServeEngine(model, cfg, params, slots=1, max_len=32)


def test_logprobs_match_model_distribution():
    """logprobs=True reports log p(sampled token) under the raw model
    distribution for every emitted token (first token included), exactly
    matching a stepwise replay; logprobs=False reports None."""
    model, cfg, params = _setup(seed=10)
    prompt = _prompts(cfg, [9], seed=10)[0]
    steps = 5
    eng = ServeEngine(model, cfg, params, slots=1, max_len=32, logprobs=True)
    eng.submit(prompt, steps)
    out = eng.run()[0]
    assert out.logprobs is not None and out.logprobs.shape == (steps,)

    st = model.state
    logits, cache = st.prefill(params, prompt[None],
                               st.init_slot(params, 32))
    want = []
    pos = prompt.shape[0]
    for t, tok in enumerate(out.tokens):
        lsm = jax.nn.log_softmax(logits[0].astype(jnp.float32))
        want.append(float(lsm[int(tok)]))
        if t + 1 < len(out.tokens):
            logits, cache = st.decode_step(
                params, jnp.asarray([[int(tok)]], jnp.int32),
                jnp.asarray(pos + t, jnp.int32), cache)
    np.testing.assert_allclose(out.logprobs, np.asarray(want, np.float32),
                               rtol=1e-6, atol=1e-6)

    eng2 = ServeEngine(model, cfg, params, slots=1, max_len=32)
    eng2.submit(prompt, 2)
    assert eng2.run()[0].logprobs is None


def test_engine_accounting():
    model, cfg, params = _setup()
    eng = ServeEngine(model, cfg, params, slots=2, max_len=32)
    for p in _prompts(cfg, [4, 10]):
        eng.submit(p, 5)
    outs = eng.run()
    st = eng.stats()
    assert st["requests"] == 2 and st["prefills"] == 2
    assert st["generated_tokens"] == sum(len(o.tokens) for o in outs) == 10
    assert st["decode_s"] > 0 and st["decode_tok_per_s"] > 0
    for o in outs:
        assert 0 < o.ttft_s <= o.latency_s
        assert o.decode_steps == len(o.tokens) - 1


class _SelectFold:
    """The state protocol with every slot folding on its own position:
    the fold's predicate is read from the caches inside the step instead
    of being passed in."""

    def __init__(self, state):
        self.state = state

    def decode_step(self, params, tok, pos, cache, fold_due):
        del fold_due
        return self.state.decode_step(params, tok, pos, cache)

    def fold_due(self, caches):
        return self.state.fold_due(caches)


class _PerSlotStep:
    """The state protocol with the tick's step taken slot by slot, as
    before the slot stack went layer-major: each slot's batch-1 cache
    steps alone under a vmap over slots (the fold gated by the same
    unbatched predicate), and is written back whole."""

    def __init__(self, state):
        self._state = state

    def __getattr__(self, name):
        return getattr(self._state, name)

    def decode_step(self, params, tok, pos, caches, fold_due=None):
        st = self._state
        if jnp.ndim(pos) == 0:         # one batch-1 cache (replay)
            return st.decode_step(params, tok, pos, caches, fold_due)
        slots = range(tok.shape[0])
        ones = jax.tree_util.tree_map(
            lambda *xs: jnp.stack(xs),
            *[st.slot_gather(caches, si) for si in slots])

        def one(t, p, c):
            logits, c = st.decode_step(params, t[None], p, c, fold_due)
            return logits[0], c

        logits, ones = jax.vmap(one)(tok, pos, ones)
        for si in slots:
            caches = st.slot_scatter(
                caches, jax.tree_util.tree_map(lambda x: x[si], ones), si)
        return logits, caches


def _psk_nodes(caches):
    return [n for n in jax.tree_util.tree_leaves(
        caches, is_leaf=lambda x: isinstance(x, PolysketchCache))
        if isinstance(n, PolysketchCache)]


FOLD_CASES = pytest.mark.parametrize("cache_pos,active", [
    ([3, 20, 33, 40], [1, 1, 1, 1]),    # no slot completes a block
    ([15, 20, 33, 40], [1, 1, 1, 1]),   # exactly one does
    ([15, 31, 33, 47], [1, 1, 1, 1]),   # several in the same tick
    ([3, 20, 31, 40], [1, 1, 0, 1]),    # only a free slot does
], ids=["none", "one", "several", "free-only"])


@FOLD_CASES
def test_gated_fold_tick_matches_select_fold(cache_pos, active):
    """The engine's tick, folding under one unbatched predicate, is bit-
    identical to the same tick folding by a per-slot select: tokens, log-
    probabilities and every cache leaf."""
    _check_gated_fold(cache_pos, active)


@FOLD_CASES
def test_gated_fold_tick_matches_select_fold_hybrid(cache_pos, active):
    """The same in a composite of an RG-LRU and a polysketch layer, whose
    polysketch nodes alone decide the fold."""
    _check_gated_fold(cache_pos, active, block_pattern=("rglru", "attn"))


def _check_gated_fold(cache_pos, active, **overrides):
    model, cfg, params = _setup(seed=5, **overrides)
    st, plan, blk = model.state, ServePlan.single_device(), cfg.lt_block_size
    slots = len(cache_pos)
    caches = st.broadcast_slots(st.init_slot(params, 64), slots)
    for si, prompt in enumerate(_prompts(cfg, cache_pos, seed=5)):
        _, one = st.prefill(params, prompt[None], st.init_slot(params, 64))
        caches = st.slot_scatter(caches, one, si)
    rng = np.random.default_rng(5)
    args = (params,
            jnp.asarray(rng.integers(0, cfg.vocab_size, (slots, 1, 1)),
                        jnp.int32),
            jnp.asarray(cache_pos, jnp.int32), init_slot_keys(slots),
            init_slot_sampling(slots), caches,
            jnp.asarray(active, bool))
    with plan.activation_context():
        got = jax.jit(make_decode_tick(st, plan, logprobs=True))(*args)
        for ref in (_SelectFold(st), _PerSlotStep(st)):
            want = jax.jit(make_decode_tick(ref, plan, logprobs=True))(*args)
            for a, b in zip(jax.tree_util.tree_leaves(got),
                            jax.tree_util.tree_leaves(want)):
                np.testing.assert_array_equal(a, b)
    completes = [n % blk == blk - 1 for n in cache_pos]
    assert bool(got[6]) == any(completes)
    # the tick folded exactly the slots whose block completed
    for si, done in enumerate(completes):
        before = _psk_nodes(st.slot_gather(caches, si))
        after = _psk_nodes(st.slot_gather(got[5], si))
        for b, a in zip(before, after):
            assert (not np.array_equal(b.z, a.z)) == done, (si, done)


# every state kind through the engine: the smoke config and overrides
KINDS = {
    "polysketch": ("gpt2s-polysketch", {}),
    "kv_full": ("gpt2s-polysketch", dict(attention="softmax")),
    "poly_kv": ("gpt2s-polysketch", dict(attention="polynomial")),
    "kv_ring": ("gpt2s-polysketch",
                dict(block_pattern=("local_attn",), sliding_window=8)),
    "ssd": ("mamba2-780m", dict(lt_block_size=16)),
    "rglru": ("recurrentgemma-9b",
              dict(block_pattern=("rglru",), lt_block_size=16)),
    "hybrid": ("gpt2s-polysketch", dict(block_pattern=("rglru", "attn"))),
}


@pytest.mark.parametrize("kind", list(KINDS))
def test_engine_bits_match_generate_and_per_slot_step(kind):
    """Every state kind: slots at different depths decode across a block
    fold, one retires early and its slot drains free, then takes a queued
    request. Tokens equal solo generate() runs, and tokens and log-prob
    bits equal an engine whose tick steps each slot's batch-1 cache
    alone."""
    arch, overrides = KINDS[kind]
    cfg = get_config(arch, smoke=True).replace(**overrides)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(7))
    prompts = _prompts(cfg, [5, 14, 20, 9], seed=7)
    gens = [22, 3, 12, 14]

    def serve(m):
        eng = ServeEngine(m, cfg, params, slots=3, max_len=64,
                          logprobs=True)
        for p, n in zip(prompts, gens):
            eng.submit(p, n)
        return {o.rid: o for o in eng.run()}

    got = serve(model)
    want = serve(model._replace(state=_PerSlotStep(model.state)))
    for rid, (p, n) in enumerate(zip(prompts, gens)):
        np.testing.assert_array_equal(
            got[rid].tokens, _ref_tokens(model, cfg, params, p, n))
        np.testing.assert_array_equal(got[rid].tokens, want[rid].tokens)
        np.testing.assert_array_equal(got[rid].logprobs.view(np.uint32),
                                      want[rid].logprobs.view(np.uint32))


@pytest.mark.parametrize("overlap", [False, True])
def test_fold_ticks_count_the_ticks_that_fold(overlap):
    """stats()["fold_ticks"] and the tick event's `fold` flag report the
    ticks on which some slot's state, free slots' included, completed a
    block."""
    model, cfg, params = _setup(seed=4)
    blk = cfg.lt_block_size
    eng = ServeEngine(model, cfg, params, slots=3, max_len=64,
                      overlap=overlap, telemetry=Telemetry(trace=True))
    tick, due = eng._decode, []

    def spy(*args):
        due.append(any(np.any(np.asarray(n.pos) % blk == blk - 1)
                       for n in _psk_nodes(args[5])))
        return tick(*args)

    eng._decode = spy
    for p, n in zip(_prompts(cfg, [5, 14, 30, 9], seed=4), [20, 6, 12, 18]):
        eng.submit(p, n)
    eng.run()
    st = eng.stats()
    assert st["decode_steps"] == len(due)
    assert 0 < st["fold_ticks"] == sum(due) < len(due)
    flags = [ev[5]["fold"] for ev in eng.telemetry.tracer._events
             if ev[0] == "X" and ev[1] == "host_sync"]
    assert flags == due
