"""Continuous-batching serve engine over the DecodeState protocol.

The paper's inference story: polysketch attention's decode state is O(1) in
context length (r^2 x (h+1) per kv-head + one partial block), so a 32k
context costs the same per decode step as a 1k context and slot admission
never depends on prompt length — no paging, no eviction, no per-request
O(n) cache. The engine itself is family-agnostic: it speaks only the
DecodeState protocol (core.state), so the same slot machinery serves
polysketch, softmax/poly KV, sliding-window ring, and SSM / RG-LRU
recurrent-state models — any model whose `Model.state` is non-None.

The engine keeps a fixed number of decode *slots*. Every slot owns an
independent cache slice: the model's batch-1 decode-state pytrees are
stacked layer-major, with the slot axis in the batch axis's place and a
``pos`` per slot (core.decode.broadcast_slot_caches).
Admission runs through a chunked prefill scheduler
(serve/scheduler.py): each request's prompt is split into power-of-two
block-bucketed chunks, and every tick dispatches at most a
``prefill_budget`` worth of chunk work before the lockstep decode tick —
so a long prompt admits incrementally across ticks instead of stalling
every live request for its whole prefill. The finished prefill (carried
between chunks as a core.state.PartialPrefill) is scattered into the free
slot with a jitted `dynamic_update_slice`; live slots are never
touched. Decode runs all slots lockstep through one jitted tick, a batch
of all slots whose layer loop writes each slot's token into the stack in
place; free slots decode along on stale state (their outputs are never
read, and admission rewrites the whole slot slice — cache, token, pos)
until the queue refills them.

With ``overlap=True`` the tick pipeline is double-buffered: prefill
chunks and the decode tick are dispatched asynchronously (no
block_until_ready anywhere in admission), and the host syncs only on the
*previous* tick's sampled tokens — one tick of lag between a token being
computed and the host observing it. Retirement decisions therefore lag
one tick too; the single decode step a slot may run past its EOS is
dropped at sync (its request id no longer matches), so emitted tokens are
bit-identical to the lockstep engine's. Decode throughput stays flat
while long prompts admit, which is the whole point: the O(1)-state
families make prefill preemptible at block granularity, and this engine
cashes that in as stall-free admission.

With a `PrefixCache` attached (legal whenever the model's
`snapshot_granularity` is non-None — polysketch, SSM, RG-LRU), admission
does a longest-prefix lookup over a content-addressed store of
constant-size state snapshots and resumes prefill from the match point;
the resumed suffix is split into power-of-two buckets
(core.state.bucket_chunks) so the per-chunk-length jit cache stays
bounded under diverse workloads. A shared system prompt costs its prefill
once, then a dictionary lookup — across engine restarts too, when the
cache has a `save_dir`.

serve_prefill / serve_step (`make_serve_fns`) remain the single-shot
functions the dry-run lowers for prefill_* / decode_* / long_* shape cells
(batch-dict based, so encoder/VLM inputs lower too).
"""
from __future__ import annotations

import hashlib
import time
from collections import deque
from dataclasses import dataclass, field
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.state import bucket_chunks
from repro.serve.plan import ServePlan
from repro.serve.prefix_cache import PrefixCache
from repro.serve.sampling import (SamplingParams, advance_key,
                                  device_scalars, init_slot_keys,
                                  init_slot_sampling, request_key,
                                  sample_first, sample_step)
from repro.serve.scheduler import PrefillScheduler
from repro.serve.telemetry import Telemetry


def make_serve_fns(model, cfg):
    """Returns (prefill_fn, decode_fn) for shape-cell lowering.

    prefill_fn(params, batch)            -> (last_logits, cache)
    decode_fn(params, tokens, cache)     -> (logits, cache)   tokens (B, 1)

    Batch-dict based (not DecodeState) so encoder (frames) and VLM
    (image_embeds) prefill cells lower through the same path.
    """

    def prefill(params, batch):
        cache = model.init_cache(params, batch["tokens"].shape[0],
                                 batch["tokens"].shape[1])
        logits, cache, _ = model.apply(params, batch, mode="prefill",
                                       cache=cache)
        return logits[:, -1], cache

    def decode(params, tokens, cache, positions):
        logits, cache, _ = model.apply(params, {"tokens": tokens},
                                       mode="decode", cache=cache,
                                       positions=positions)
        return logits[:, -1], cache

    return prefill, decode


class GenerationResult(NamedTuple):
    tokens: jax.Array     # (B, steps)
    logits_last: jax.Array


def generate(model, cfg, params, prompt: jax.Array, steps: int, *,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
             seed: int = 0, sampling: SamplingParams | None = None,
             rng=None, max_len: int | None = None):
    """Sampling loop on the engine's fused sampler. prompt: (B, S0) int32.

    Runs entirely on the DecodeState protocol, so every servable family
    works here identically to a ServeEngine slot. Batch row r draws the
    PRNG stream `request_key(seed, r)` and advances it by one split per
    emitted token, exactly like a ServeEngine slot — so
    `generate(..., sampling=sp).tokens[0]` is bit-identical to a
    single-slot engine run of the same `(seed, prompt, SamplingParams)`.
    `rng` (legacy) overrides the seed-derived base key when given.
    """
    state = model.state
    if state is None:
        raise NotImplementedError(
            f"{cfg.name!r} exposes no DecodeState; generate() serves "
            "decode-state models only")
    sp = sampling or SamplingParams(temperature=temperature, top_k=top_k,
                                    top_p=top_p, seed=seed)
    bsz, s0 = prompt.shape
    max_len = max_len or (s0 + steps)
    if s0 + steps > max_len:
        # KV-cache state kinds index the cache at pos and
        # `dynamic_update_index_in_dim` CLAMPS out-of-range positions —
        # overflow would silently corrupt the last cache slot, so reject
        # it up front exactly like ServeEngine.submit does.
        raise ValueError(
            f"prompt({s0}) + steps({steps}) exceeds max_len={max_len}")
    last, cache = state.prefill(params, prompt, max_len=max_len)
    base = rng if rng is not None else jax.random.PRNGKey(sp.seed)
    keys = jax.vmap(lambda r: jax.random.fold_in(base, r))(jnp.arange(bsz))
    t, k, p, g = device_scalars(sp)
    sample = jax.vmap(sample_step, in_axes=(0, 0, None, None, None, None))

    def body(carry, i):
        keys, last, cache = carry
        tok, keys = sample(keys, last, t, k, p, g)
        logits, cache = state.decode_step(params, tok[:, None],
                                          jnp.asarray(s0, jnp.int32) + i,
                                          cache)
        return (keys, logits, cache), tok

    (_, last, cache), toks = jax.lax.scan(body, (keys, last, cache),
                                          jnp.arange(steps))
    return GenerationResult(tokens=toks.T, logits_last=last)


def make_decode_tick(state, plan: ServePlan, logprobs: bool):
    """The body of the engine's jitted decode tick over every slot:
    decode_all(params, toks, pos, keys, samp, caches, active) ->
    (tokens, logprobs, toks, pos, keys, caches, fold_due). Trace it inside
    `plan.activation_context()`.

    `caches` is the layer-major slot stack
    (core.decode.broadcast_slot_caches): its slot axis is the batch axis,
    so one decode step runs every slot, and the layer loop writes each
    slot's token into the stack in place.

    `fold_due` is one scalar for the whole tick: some slot's state
    completes a block, and folds it, on this tick (`state.fold_due`; False
    for state kinds that never fold). It is read from the stack's own
    per-slot positions, not from `pos`: a free slot's `pos` is frozen
    while its stale state decodes, and folds, along. It is computed
    before the step and passed in unbatched, so the fold stays a real
    conditional that runs on the few ticks that need it."""

    def decode_all(params, toks, pos, keys, samp, caches, active):
        # model tick for all slots, then sampling in a scalar lax.cond
        # that skips the sampler ops entirely for all-greedy batches (a
        # batched cond would lower to select and run both branches) —
        # greedy-only serving keeps the pre-sampling argmax-tick cost.
        # Free slots' stale params are ignored by the predicate
        # (`| ~active`): a retired sampled request must not force the
        # sampler on a greedy drain. Greedy slots never consume their
        # PRNG stream, so the fast path leaving keys un-split is not
        # observable in any request's tokens.
        fold_due = state.fold_due(caches)
        logits, caches = state.decode_step(params, toks[:, 0], pos, caches,
                                           fold_due)
        # gather the vocab dim before softmax/argmax: the reductions
        # below must see identically-ordered operands on every mesh
        logits = plan.constrain_logits(logits)

        def all_greedy(_):
            return jnp.argmax(logits, axis=-1).astype(jnp.int32), keys

        def mixed(_):
            return jax.vmap(sample_step)(keys, logits, samp.temperature,
                                         samp.top_k, samp.top_p,
                                         samp.greedy)

        with jax.named_scope("sampler"):
            out, new_keys = jax.lax.cond(jnp.all(samp.greedy | ~active),
                                         all_greedy, mixed, None)
            if logprobs:
                # raw-distribution logprob of the emitted token, fused
                # into the tick (logprobs is trace-static)
                lsm = jax.nn.log_softmax(logits.astype(jnp.float32),
                                         axis=-1)
                lps = jnp.take_along_axis(lsm, out[:, None], axis=-1)[:, 0]
            else:
                lps = jnp.zeros((out.shape[0],), jnp.float32)
        # free slots decode along on stale state but their feed token,
        # PRNG key, and position are all FROZEN here (one fused tick,
        # no per-field host dispatch): admission rewrites the whole
        # slot, yet a retire -> step -> admit interleaving must never
        # observe stale-decode garbage in a free slot's state, and a
        # long drain must never push pos past max_len (KV-cache state
        # kinds index their cache at pos; RoPE stays bounded)
        new_toks = jnp.where(active[:, None, None], out[:, None, None],
                             toks)
        new_keys = jnp.where(active[:, None], new_keys, keys)
        new_pos = jnp.where(active, pos + 1, pos)
        return out, lps, new_toks, new_pos, new_keys, caches, fold_due

    return decode_all


@dataclass
class Request:
    rid: int
    prompt: jax.Array            # (S,) int32
    max_new_tokens: int
    eos_id: int | None = None
    submit_time: float = 0.0
    sampling: SamplingParams = field(default_factory=SamplingParams)


@dataclass
class RequestOutput:
    rid: int
    tokens: np.ndarray           # (n_generated,) int32, includes EOS if hit
    prompt_len: int
    finish_reason: str           # "eos" | "length"
    ttft_s: float = 0.0          # submit -> first token (prefill argmax)
    latency_s: float = 0.0       # submit -> retirement
    decode_steps: int = 0
    logprobs: np.ndarray | None = None  # (n_generated,) f32, engine opt-in


@dataclass
class RecoveredRequest:
    """Host-side record of an in-flight request being re-homed after its
    replica died (serve/replicas.py builds these from its mirror — the
    token stream a front-end had already observed). `snapshot` is the
    deepest usable decode-state checkpoint; recovery is correct with
    snapshot=None too (cold prompt prefill + full token replay), a
    checkpoint only shortens the replay."""
    prompt: np.ndarray
    emitted: list[int]
    lps: list[float]
    max_new_tokens: int
    eos_id: int | None
    sampling: SamplingParams
    submit_time: float
    ttft_s: float = 0.0
    snapshot: object = None
    snap_tokens: int = 0


@dataclass
class _Slot:
    request: Request | None = None
    prefilling: bool = False     # reserved: prefill in flight, not decoding
    emitted: list[int] = field(default_factory=list)
    lps: list[float] = field(default_factory=list)
    ttft_s: float = 0.0
    last_tok_s: float | None = None  # inter-token latency tracking
    pos0: int = 0                # device pos at install (prompt len, or the
                                 # rebuilt position after a failover)
    ticks: int = 0               # decode ticks dispatched since install

    @property
    def free(self) -> bool:
        return self.request is None

    @property
    def decoding(self) -> bool:
        return self.request is not None and not self.prefilling


@dataclass
class _TickRecord:
    """One dispatched decode tick, not yet synced (the overlap pipeline's
    double buffer). `rids` pins which request occupied each slot at
    dispatch time: a slot retired (and possibly re-admitted) between
    dispatch and sync drops its speculative token via the rid mismatch."""
    toks: object                 # (slots,) device array
    lps: object
    fold: object                 # () device bool: the tick folded a block
    active: np.ndarray           # dispatch-time decoding mask
    rids: list[int | None]
    firsts: list[tuple]          # (slot, rid, tok_dev, lp_dev) admissions
    t_dispatch: float


class ServeEngine:
    """Continuous-batching engine over fixed decode slots.

    Requests are admitted into free slots one at a time: each prefill runs
    at the request's own prompt length, and the resulting batch-1 state is
    scattered into the slot axis without disturbing live slots. All slots
    then decode lockstep through one jitted step; each slot stops
    independently on EOS or its max-new-tokens budget.

    Decoding is per-request `SamplingParams` (greedy by default): the
    stacked per-slot params and PRNG keys are engine device state, so one
    jitted tick samples every slot with heterogeneous params — a greedy
    request, a temperature-0.8 top-k-40 one, and a nucleus-sampled one can
    share a batch without retracing. Tokens depend only on
    `(seed, prompt, SamplingParams)`, never on slot placement, admission
    order, or batch composition, and match `generate(..., sampling=sp)`
    token-for-token.

    `logprobs=True` additionally reports the model log-probability of each
    emitted token (from the raw pre-sampling distribution), computed inside
    the same jitted tick — no extra host sync per token.

    `min_snapshot_blocks` is the prefix-cache admission cost floor: only
    prefixes of at least that many blocks are snapshotted or promoted
    (1 = snapshot everything, the default).

    `prefill_budget` (prompt tokens per tick, None = unlimited) bounds how
    much admission prefill work each tick dispatches ahead of its decode
    step — the knob that trades time-to-first-token against decode-tick
    jitter. `overlap=True` additionally pipelines the host: chunk and tick
    dispatches never block, and tokens are synced one tick late (emitted
    tokens stay bit-identical to the lockstep engine's).

    `telemetry` (serve/telemetry.py) carries the engine's observability:
    its MetricsRegistry is ALWAYS the accounting substrate (`stats()` is
    a thin view over it), its Tracer records the request/tick event
    timeline when enabled, and its watchdog/memory hooks run per tick.
    The default `Telemetry()` keeps tracing and memory sampling off —
    the zero-overhead configuration. One Telemetry per engine: the
    registry holds gauges reading this engine's live state.
    """

    def __init__(self, model, cfg, params, *, slots: int = 4,
                 max_len: int = 4096,
                 prefix_cache: PrefixCache | None = None,
                 min_snapshot_blocks: int = 1,
                 logprobs: bool = False,
                 prefill_budget: int | None = None,
                 overlap: bool = False,
                 telemetry: Telemetry | None = None,
                 plan: ServePlan | None = None,
                 param_axes=None):
        if model.state is None:
            raise NotImplementedError(
                f"{cfg.name!r} exposes no DecodeState; ServeEngine serves "
                "decode-state models only")
        if slots < 1:
            raise ValueError("need at least one decode slot")
        if min_snapshot_blocks < 1:
            raise ValueError("min_snapshot_blocks must be >= 1")
        # every engine runs under a ServePlan; single-device is the
        # trivial 1x1 plan, so there is exactly one code path
        self.plan = plan if plan is not None else ServePlan.single_device()
        self.model, self.cfg = model, cfg
        self._param_sh = self.plan.param_shardings(params, param_axes)
        self.params = jax.device_put(params, self._param_sh)
        self.state = model.state
        self.slots = slots
        self.max_len = max_len
        self.min_snapshot_blocks = min_snapshot_blocks
        self.logprobs = logprobs
        self.overlap = overlap
        self.queue: deque[Request] = deque()
        # failover re-admissions waiting for a free slot; drained ahead of
        # the ordinary queue (a recovered request already has latency debt)
        self._recover_pending: deque[tuple[Request, RecoveredRequest]] = \
            deque()
        self.finished: list[RequestOutput] = []
        self._next_rid = 0
        self._slots = [_Slot() for _ in range(slots)]
        self._pending: _TickRecord | None = None  # overlap double buffer
        self._tick_fold = None     # the last tick's fold flag (see _decode)
        self.telemetry = telemetry if telemetry is not None else Telemetry()

        state = self.state

        # Device state: the layer-major slot stack of the batch-1 caches
        # (the slot axis in the batch axis's place, a `pos` per slot and
        # layer), the next token to feed each slot, each slot's context
        # depth, and the sampling state (per-slot PRNG key + stacked
        # SamplingParams).
        slot_cache0 = state.init_slot(params, max_len)
        self._slot_caches = state.broadcast_slots(slot_cache0, slots)
        self._slot_tokens = jnp.zeros((slots, 1, 1), jnp.int32)
        self._slot_pos = jnp.zeros((slots,), jnp.int32)
        self._slot_keys = init_slot_keys(slots)
        self._slot_samp = init_slot_sampling(slots)

        # placement: the slot stack spreads slots over "data", both it and
        # batch-1 prefill caches shard kv-heads over "model", everything
        # the host reads or writes per request stays replicated
        plan_ = self.plan
        rep = plan_.replicated()
        cache1_sh = plan_.state_shardings(slot_cache0)
        cacheS_sh = plan_.state_shardings(self._slot_caches)
        tok_sh = plan_.slot_sharding(self._slot_tokens)
        pos_sh = plan_.slot_sharding(self._slot_pos)
        keys_sh = plan_.slot_sharding(self._slot_keys)
        samp_sh = jax.tree_util.tree_map(plan_.slot_sharding,
                                         self._slot_samp)
        self._slot_caches = jax.device_put(self._slot_caches, cacheS_sh)
        self._slot_tokens = jax.device_put(self._slot_tokens, tok_sh)
        self._slot_pos = jax.device_put(self._slot_pos, pos_sh)
        self._slot_keys = jax.device_put(self._slot_keys, keys_sh)
        self._slot_samp = jax.device_put(self._slot_samp, samp_sh)

        self.prefix_cache = prefix_cache
        if prefix_cache is not None:
            if state.snapshot_granularity is None:
                raise ValueError(
                    "prefix cache requires a snapshot-capable decode state "
                    f"(config {cfg.name!r}, state kinds "
                    f"{'/'.join(state.kinds)} declare no constant-size "
                    "snapshot)")
            prefix_cache.bind_block_size(state.block_size)
            # snapshots are weight-specific AND shape-specific: a ring-KV
            # snapshot embeds the engine's window (min(sliding_window,
            # max_len)), so the binding fingerprints the snapshot leaf
            # shapes too — engines differing only in max_len share
            # snapshots exactly when the shapes agree
            probe = jax.eval_shape(
                lambda: state.snapshot(state.init_slot(params, max_len)))
            sig = repr([(leaf.shape, str(leaf.dtype)) for leaf in
                        jax.tree_util.tree_leaves(probe)]).encode()
            prefix_cache.bind_params(params, state_sig=sig)
            prefix_cache.bind_codec(state.serialize, state.deserialize)
        # distinct resumed-chunk lengths ever compiled (bounded by the
        # power-of-two bucketing; asserted in tests)
        self._resume_lens: set[int] = set()
        # distinct replay-chunk lengths (failover recovery; power-of-two)
        self._replay_lens: set[int] = set()

        def prefill_one(params, tokens):
            # tokens: (1, S) at the request's own length — no padding enters
            # attention. Retraced per distinct prompt length. Returns the
            # last-position logits; the first token is sampled separately
            # (sample_first) so greedy/sampled requests share this trace.
            return state.prefill(params, tokens, state.init_slot(params,
                                                                 self.max_len))

        def prefill_resume(params, tokens, cache, pos0):
            # resumed prefill: `cache` already covers the first pos0
            # (block-aligned) tokens, so this chunk attends through it and
            # RoPE runs at the true absolute positions. Retraced per chunk
            # length (bounded by bucket_chunks). NOT donated: `cache` may
            # alias stored snapshot arrays.
            return state.resume(params, tokens, cache, pos0)

        def fresh_slot(params):
            return state.init_slot(params, self.max_len)

        def restore(params, snapshot, n_tokens):
            return state.restore(state.init_slot(params, self.max_len),
                                 snapshot, n_tokens)

        def first_token(logits, key, temperature, top_k, top_p, greedy):
            # logits (1, V): the request's prefill last-position logits
            # (self.logprobs is trace-static)
            return sample_first(logits, key, temperature, top_k, top_p,
                                greedy, logprobs=self.logprobs)

        def install_slot(caches, toks, pos, keys, samp, cache, logits,
                         base_key, si, s0, t, k, p, g):
            # whole-slot install as ONE jitted dispatch with a TRACED slot
            # index: first-token sampling off the final prefill chunk's
            # logits, cache scatter, and the token/pos/key/params writes.
            # A per-field eager `.at[si].set` would compile per slot index
            # and could stall an admission tick mid-run; this is one trace
            # for every slot.
            tok, key, lp = first_token(logits, base_key, t, k, p, g)
            caches = state.slot_scatter(caches, cache, si)
            toks = jax.lax.dynamic_update_index_in_dim(
                toks, tok[:, None], si, axis=0)
            pos = jax.lax.dynamic_update_index_in_dim(pos, s0, si, axis=0)
            keys = jax.lax.dynamic_update_index_in_dim(keys, key, si, axis=0)
            samp = jax.tree_util.tree_map(
                lambda full, v: jax.lax.dynamic_update_index_in_dim(
                    full, v.astype(full.dtype), si, axis=0),
                samp, type(samp)(t, k, p, g))
            return caches, toks, pos, keys, samp, tok, lp

        decode_all = make_decode_tick(state, self.plan, self.logprobs)

        def replay_tokens(params, tokens, pos0, cache):
            # decode-path REPLAY of already-emitted tokens (failover
            # recovery): prefill and decode produce numerically different
            # states (different matmul shapes => different f32 reduction
            # orders), so tokens a client already observed must be
            # re-absorbed through the same decode_step the dead replica
            # ran, or the recovered stream would diverge from the
            # fault-free one. One scan iteration per token; callers bucket
            # chunk lengths to powers of two so the trace count stays
            # O(log max_new_tokens). Logits are discarded — the tokens are
            # known. NOT donated: `cache` may come straight out of a
            # restored checkpoint.
            def body(carry, tok):
                pos, cache = carry
                _, cache = state.decode_step(params, tok[None, None], pos,
                                             cache)
                return (pos + 1, cache), None
            (_, cache), _ = jax.lax.scan(
                body, (jnp.asarray(pos0, jnp.int32), cache), tokens[0])
            return cache

        def install_restored(caches, toks, pos, keys, samp, cache, si, tok,
                             pos_val, key, t, k, p, g):
            # recovery install: like install_slot but the feed token and
            # PRNG key are GIVEN (the last mirrored token and the stream
            # key advanced past every emitted token) instead of sampled
            # from prefill logits — the recovered request resumes
            # mid-stream, bit-exactly where the dead replica left off.
            caches = state.slot_scatter(caches, cache, si)
            toks = jax.lax.dynamic_update_index_in_dim(
                toks, tok[:, None], si, axis=0)
            pos = jax.lax.dynamic_update_index_in_dim(pos, pos_val, si,
                                                      axis=0)
            keys = jax.lax.dynamic_update_index_in_dim(keys, key, si, axis=0)
            samp = jax.tree_util.tree_map(
                lambda full, v: jax.lax.dynamic_update_index_in_dim(
                    full, v.astype(full.dtype), si, axis=0),
                samp, type(samp)(t, k, p, g))
            return caches, toks, pos, keys, samp

        # The slot stack is donated on both hot paths (decode tick,
        # slot install) so XLA updates it in place instead of copying the
        # full cache pytree every generated token; callers must treat the
        # cache they pass in as consumed. Every entry point carries the
        # plan's explicit in/out shardings (donated args keep in == out so
        # donation survives) and is wrapped in the plan's activation
        # context so model-code shard_act constraints resolve against the
        # serving rules at trace time. On the 1x1 plan every sharding is
        # the single device and nothing changes.
        param_sh = self._param_sh
        wrap = plan_.wrap
        self._prefill = wrap(jax.jit(
            prefill_one,
            in_shardings=(param_sh, rep), out_shardings=(rep, cache1_sh)))
        self._prefill_resume = wrap(jax.jit(
            prefill_resume,
            in_shardings=(param_sh, rep, cache1_sh, rep),
            out_shardings=(rep, cache1_sh)))
        self._fresh_slot = wrap(jax.jit(
            fresh_slot, in_shardings=(param_sh,), out_shardings=cache1_sh))
        self._restore = wrap(jax.jit(
            # snapshots arrive host-replicated (gather-on-snapshot in the
            # prefix cache); the out sharding re-shards on restore
            restore, in_shardings=(param_sh, rep, rep),
            out_shardings=cache1_sh))
        self._install_slot = wrap(jax.jit(
            install_slot, donate_argnums=(0,),
            in_shardings=(cacheS_sh, tok_sh, pos_sh, keys_sh, samp_sh,
                          cache1_sh, rep, rep, rep, rep, rep, rep, rep,
                          rep),
            out_shardings=(cacheS_sh, tok_sh, pos_sh, keys_sh, samp_sh,
                           rep, rep)))
        self._decode_tick = wrap(jax.jit(
            decode_all, donate_argnums=(5,),
            in_shardings=(param_sh, tok_sh, pos_sh, keys_sh, samp_sh,
                          cacheS_sh, rep),
            out_shardings=(rep, rep, tok_sh, pos_sh, keys_sh, cacheS_sh,
                           rep)))
        self._replay = wrap(jax.jit(
            replay_tokens,
            in_shardings=(param_sh, rep, rep, cache1_sh),
            out_shardings=cache1_sh))
        self._install_restored = wrap(jax.jit(
            install_restored, donate_argnums=(0,),
            in_shardings=(cacheS_sh, tok_sh, pos_sh, keys_sh, samp_sh,
                          cache1_sh, rep, rep, rep, rep, rep, rep, rep,
                          rep),
            out_shardings=(cacheS_sh, tok_sh, pos_sh, keys_sh, samp_sh)))

        # retrace watchdog: every jitted entry point's jit-cache size is
        # sampled per tick; growth after reset_stats() (= warm-up done) is
        # a mid-serve recompile stalling a live tick, counted and flagged
        for _name, _fn in (("prefill", self._prefill),
                           ("prefill_resume", self._prefill_resume),
                           ("fresh_slot", self._fresh_slot),
                           ("restore", self._restore),
                           ("install_slot", self._install_slot),
                           ("decode", self._decode_tick),
                           ("replay", self._replay),
                           ("install_restored", self._install_restored)):
            self.telemetry.watchdog.register(_name, _fn)

        # the chunked admission scheduler drives the jitted prefill fns;
        # all its dispatches are asynchronous (the host syncs on sampled
        # tokens only)
        self.scheduler = PrefillScheduler(
            state,
            prefill_fn=lambda toks: self._prefill(self.params, toks),
            resume_fn=lambda toks, st, pos: self._prefill_resume(
                self.params, toks, st, jnp.asarray(pos, jnp.int32)),
            fresh_fn=lambda: self._fresh_slot(self.params),
            restore_fn=lambda snap, n: self._restore(
                self.params, snap, jnp.asarray(n, jnp.int32)),
            prefix_cache=prefix_cache,
            min_snapshot_blocks=min_snapshot_blocks,
            budget=prefill_budget,
            resume_lens=self._resume_lens,
            tracer=self.telemetry.tracer,
            mesh_shape=self.plan.describe())
        if prefix_cache is not None:
            prefix_cache.attach_tracer(self.telemetry.tracer)

        # Accounting lives in the telemetry registry; stats() is a thin
        # view over it and the Prometheus exposition reads the same
        # numbers. Histograms keep bounded raw-value windows — a
        # long-lived engine must not grow host memory per emitted token,
        # and percentiles over the recent window are what an operator
        # actually watches.
        reg = self.telemetry.registry
        self._m_prefills = reg.counter(
            "serve_prefills_total", "prefills installed into slots")
        self._m_sampled = reg.counter(
            "serve_sampled_requests_total",
            "installed requests with non-greedy sampling")
        self._m_ticks = reg.counter(
            "serve_decode_ticks_total", "jitted decode ticks dispatched")
        self._m_fold_ticks = reg.counter(
            "serve_fold_ticks_total",
            "decode ticks on which some slot folded a completed block")
        self._m_tokens = reg.counter(
            "serve_tokens_total", "tokens emitted (first + decode)")
        self._m_finished = reg.counter(
            "serve_requests_finished_total",
            "retired requests by finish reason", labels=("reason",))
        self._m_recovered = reg.counter(
            "serve_recovered_slots_total",
            "requests re-installed mid-stream after a replica failover")
        self._m_prefill_s = reg.counter(
            "serve_prefill_seconds_total",
            "admission dispatch + lockstep first-token sync wall time")
        self._m_decode_s = reg.counter(
            "serve_decode_seconds_total", "decode pipeline wall time")
        self._m_ttft = reg.histogram(
            "serve_ttft_ms", "submit -> first token (prefill argmax)",
            edges=self.TTFT_EDGES_MS)
        self._m_itl = reg.histogram(
            "serve_itl_ms", "inter-token latency across all requests",
            edges=self.ITL_EDGES_MS)
        self._m_tick_gap = reg.histogram(
            "serve_tick_gap_ms",
            "host-observed gap between consecutive decode-tick "
            "completions within a busy streak",
            edges=self.TICK_GAP_EDGES_MS, window=16384)
        # mesh topology exported as set-gauges (reset() zeroes them, so
        # reset_stats re-sets; see _set_mesh_gauges)
        self._g_mesh_devices = reg.gauge(
            "serve_mesh_devices", "devices per mesh axis", labels=("axis",))
        self._g_mesh_info = reg.gauge(
            "serve_mesh_info", "serving mesh shape (constant 1, "
            "shape in the label)", labels=("shape",))
        self._set_mesh_gauges()
        reg.gauge("serve_slots", "decode slots", fn=lambda: float(slots))
        reg.gauge("serve_active_requests",
                  "slots with an installed decoding request",
                  fn=lambda: float(self.n_active))
        reg.gauge("serve_queue_depth", "requests waiting for a slot",
                  fn=lambda: float(len(self.queue)))
        sch = self.scheduler
        reg.counter("serve_scheduler_chunks_total",
                    "prefill chunks dispatched", fn=lambda: sch.chunks)
        reg.counter("serve_scheduler_chunk_tokens_total",
                    "prompt tokens dispatched as chunks",
                    fn=lambda: sch.chunk_tokens)
        reg.counter("serve_scheduler_coalesced_total",
                    "admissions parked on an in-flight shared prefix",
                    fn=lambda: sch.coalesced)
        reg.counter("serve_scheduler_promote_splits_total",
                    "prefix-cache promote splits planned",
                    fn=lambda: sch.promotes)
        reg.gauge("serve_scheduler_inflight", "prefills in flight",
                  fn=lambda: float(len(sch.jobs)))
        if prefix_cache is not None:
            pc = prefix_cache
            reg.counter("serve_prefix_cache_lookups_total",
                        "prefix-cache probes", fn=lambda: pc.lookups)
            reg.counter("serve_prefix_cache_hits_total",
                        "probes that restored a snapshot",
                        fn=lambda: pc.hits)
            reg.counter("serve_prefix_cache_hit_tokens_total",
                        "prompt tokens skipped via snapshot restore",
                        fn=lambda: pc.hit_tokens)
            reg.counter("serve_prefix_cache_evictions_total",
                        "snapshots evicted", fn=lambda: pc.evictions)
            reg.counter("serve_prefix_disk_corrupt_total",
                        "disk-tier snapshots quarantined as corrupt",
                        fn=lambda: pc.disk_corrupt)

        self._mesh_desc = self.plan.describe()

        # gap anchor: the previous tick's sync time within the current
        # busy streak; None across idle periods, so a bursty workload's
        # think time between requests never reads as a decode stall
        self._gap_anchor: float | None = None
        self._last_sync: float | None = None

    # ------------------------------------------------------------------
    # submission / scheduling
    # ------------------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               eos_id: int | None = None,
               sampling: SamplingParams | None = None) -> int:
        """Enqueue a request; returns its id. prompt: (S,) or (1, S) int32.
        `sampling` defaults to greedy decoding."""
        # host-resident on purpose: the chunked scheduler slices the prompt
        # on host and does ONE h2d per chunk — device_put here would force
        # a d2h round-trip at admission (scheduler.start re-materializes
        # the np view for slicing and chain keys)
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.shape[0] < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if prompt.shape[0] + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt({prompt.shape[0]}) + max_new({max_new_tokens}) "
                f"exceeds engine max_len={self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, max_new_tokens, eos_id,
                      time.perf_counter(), sampling or SamplingParams())
        self.queue.append(req)
        tr = self.telemetry.tracer
        if tr:
            tr.instant("queue", "submit", rid=rid,
                       prompt_len=int(prompt.shape[0]),
                       max_new=int(max_new_tokens),
                       sampling=req.sampling.describe())
        return rid

    @property
    def n_active(self) -> int:
        """Slots with an installed (decoding) request; mid-prefill slots
        are reserved but not yet decoding."""
        return sum(s.decoding for s in self._slots)

    @property
    def busy(self) -> bool:
        return (bool(self.queue) or bool(self._recover_pending)
                or self.scheduler.active
                or self.n_active > 0 or self._pending is not None)

    # legacy accounting attributes, now views over the telemetry registry
    # (one source of truth for stats(), the Prometheus exposition, and
    # these) — external callers keep reading the same names

    @property
    def total_prefill_s(self) -> float:
        return self._m_prefill_s.value

    @property
    def total_decode_s(self) -> float:
        return self._m_decode_s.value

    @property
    def decode_steps(self) -> int:
        return int(self._m_ticks.value)

    @property
    def prefills(self) -> int:
        return int(self._m_prefills.value)

    @property
    def sampled_requests(self) -> int:
        return int(self._m_sampled.value)

    @property
    def _tick_gaps(self) -> np.ndarray:
        """Recent tick-gap window in SECONDS (the histogram stores ms)."""
        return np.asarray(self._m_tick_gap.window, np.float64) * 1e-3

    def _retire(self, si: int, reason: str) -> RequestOutput:
        slot = self._slots[si]
        req = slot.request
        now = time.perf_counter()
        out = RequestOutput(
            rid=req.rid, tokens=np.asarray(slot.emitted, np.int32),  # jaxlint: disable=host-sync-in-jit-path -- slot.emitted is a host python list (already synced token ints)
            prompt_len=int(req.prompt.shape[0]), finish_reason=reason,
            ttft_s=slot.ttft_s, latency_s=now - req.submit_time,
            decode_steps=len(slot.emitted) - 1,
            logprobs=(np.asarray(slot.lps, np.float32) if self.logprobs  # jaxlint: disable=host-sync-in-jit-path -- slot.lps is a host python list
                      else None))
        slot.request = None
        slot.prefilling = False
        slot.emitted = []
        slot.lps = []
        slot.last_tok_s = None
        slot.pos0 = 0
        slot.ticks = 0
        self.finished.append(out)
        self._m_finished.labels(reason=reason).inc()
        tr = self.telemetry.tracer
        if tr:
            tr.end(f"slot{si}", rid=out.rid, reason=reason)  # decode span
            tr.instant(f"slot{si}", "retire", rid=out.rid, reason=reason,
                       tokens=int(len(out.tokens)))
        return out

    def _check_finished(self, si: int) -> RequestOutput | None:
        slot = self._slots[si]
        req = slot.request
        if req.eos_id is not None and slot.emitted[-1] == req.eos_id:
            return self._retire(si, "eos")
        if len(slot.emitted) >= req.max_new_tokens:
            return self._retire(si, "length")
        return None

    def _start_admissions(self):
        """Reserve free slots for queued requests (FIFO) and hand their
        prefills to the chunked scheduler. No device work beyond the plan's
        snapshot restore is dispatched here; chunks flow from
        scheduler.tick() under the per-tick budget."""
        for si, slot in enumerate(self._slots):
            if not (self.queue or self._recover_pending):
                break
            if not slot.free:
                continue
            if self._recover_pending:
                req, rec = self._recover_pending.popleft()
                self._install_recovery(si, req, rec)
                continue
            req = self.queue.popleft()
            slot.request = req
            slot.prefilling = True
            self.scheduler.start(req, si)

    def _install(self, job):
        """Completed prefill -> slot device state. Every operation here is
        an async dispatch (first-token sampling off the final chunk's
        logits, cache scatter, per-slot token/pos/key/params writes): the
        host does NOT wait for the prefill — the token is synced with the
        tick record (overlap) or once per step for all admissions
        (lockstep). The PRNG stream is request_key(seed), independent of
        the slot index, so placement never changes the tokens."""
        req, si = job.req, job.slot
        (self._slot_caches, self._slot_tokens, self._slot_pos,
         self._slot_keys, self._slot_samp, tok, lp) = self._install_slot(
            self._slot_caches, self._slot_tokens, self._slot_pos,
            self._slot_keys, self._slot_samp, job.part.state,
            job.part.logits, request_key(req.sampling.seed),
            jnp.asarray(si, jnp.int32),
            jnp.asarray(req.prompt.shape[0], jnp.int32),
            *device_scalars(req.sampling))
        self._slots[si].prefilling = False
        self._slots[si].pos0 = int(req.prompt.shape[0])
        self._slots[si].ticks = 0
        self._m_prefills.inc()
        if not req.sampling.is_greedy:
            self._m_sampled.inc()
        tr = self.telemetry.tracer
        if tr:
            tr.end(f"slot{si}", rid=req.rid)  # prefill span
            tr.begin(f"slot{si}", "decode", rid=req.rid,
                     prompt_len=int(req.prompt.shape[0]))
        return (si, req.rid, tok, lp)

    # ------------------------------------------------------------------
    # failover: checkpoint export, recovered admission, cancellation
    # ------------------------------------------------------------------

    def slot_covered(self, si: int) -> int:
        """Stream tokens (prompt + absorbed emitted) the slot's device
        state covers right now. Pure host arithmetic — no device sync."""
        slot = self._slots[si]
        return slot.pos0 + slot.ticks

    def snapshot_slot(self, si: int):
        """Slot si's decode state as ``(snapshot, n_tokens)``, or None when
        the slot is not checkpointable right now (free, mid-prefill, a
        state family with no constant-size snapshot, or off the block
        grid). The gather/snapshot is dispatched asynchronously — it is
        enqueued on the device stream BEFORE the next tick's donating
        dispatch, so it reads the pre-donation buffers; host
        materialization happens later, in PrefixCache.put_ckpt."""
        slot = self._slots[si]
        if not slot.decoding:
            return None
        state = self.state
        if state.snapshot_granularity is None:
            return None
        covered = slot.pos0 + slot.ticks
        if covered <= 0 or covered % state.block_size != 0:
            return None
        snap = state.snapshot(state.slot_gather(self._slot_caches, si))
        return snap, covered

    def live_requests(self) -> list[dict]:
        """Host-side view of every request the engine still owes tokens:
        queued, pending recovery, mid-prefill, and decoding. The mirror
        fields (`emitted`/`lps`) are plain host lists — already-synced
        token ints, no device wait. This is what a coordinator checkpoints
        and what a SIGTERM drain persists."""
        out = []
        for req in self.queue:
            out.append(dict(rid=req.rid, phase="queued", request=req,
                            emitted=[], lps=[], ttft_s=0.0))
        for req, rec in self._recover_pending:
            out.append(dict(rid=req.rid, phase="queued", request=req,
                            emitted=list(rec.emitted), lps=list(rec.lps),
                            ttft_s=rec.ttft_s))
        for si, slot in enumerate(self._slots):
            if slot.free:
                continue
            out.append(dict(
                rid=slot.request.rid,
                phase="prefill" if slot.prefilling else "decode",
                request=slot.request, emitted=list(slot.emitted),
                lps=list(slot.lps), ttft_s=slot.ttft_s))
        return out

    def admit_recovered(self, rec: RecoveredRequest) -> int:
        """Re-home a request recovered from a dead replica. Installs into
        a free slot immediately when one exists, else parks it ahead of
        the ordinary queue. Returns the request's NEW rid on this engine
        (the coordinator maps it back to the global id)."""
        prompt = np.asarray(rec.prompt, np.int32).reshape(-1)
        if prompt.shape[0] + rec.max_new_tokens > self.max_len:
            raise ValueError(
                f"recovered prompt({prompt.shape[0]}) + "
                f"max_new({rec.max_new_tokens}) exceeds engine "
                f"max_len={self.max_len}")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid, prompt, rec.max_new_tokens, rec.eos_id,
                      rec.submit_time, rec.sampling)
        for si, slot in enumerate(self._slots):
            if slot.free:
                self._install_recovery(si, req, rec)
                return rid
        self._recover_pending.append((req, rec))
        return rid

    def _install_recovery(self, si: int, req: Request,
                          rec: RecoveredRequest):
        """Rebuild a recovered request's device state in slot `si` so its
        remaining tokens come out bit-identical to the fault-free run.

        The stream the dead replica absorbed is prompt ++ emitted; the
        last emitted token was sampled but NOT yet absorbed (it is the
        next feed). So the rebuilt cache must cover
        ``target = prompt_len + k - 1`` tokens (k = len(emitted)):
        restore the deepest usable checkpoint (block-aligned, <= target),
        prefill any uncovered PROMPT tokens through the resumable prefill
        path, then REPLAY the emitted tokens through the decode path —
        prefill and decode are not bitwise-interchangeable, and the
        original run absorbed emitted tokens via decode_step. Finally the
        slot is installed with feed = emitted[-1] at pos = target and the
        request's PRNG key advanced past all k sampled tokens."""
        slot = self._slots[si]
        k = len(rec.emitted)
        if k == 0:
            # nothing emitted yet: an ordinary admission (the chunked
            # scheduler path, prefix cache and all)
            slot.request = req
            slot.prefilling = True
            self.scheduler.start(req, si)
            self._m_recovered.inc()
            return
        state = self.state
        prompt = req.prompt
        s0 = int(prompt.shape[0])
        target = s0 + k - 1
        ctx = np.concatenate([prompt,  # host list of ints, no d2h here
                              np.asarray(rec.emitted[:-1], np.int32)])  # jaxlint: disable=host-sync-in-jit-path -- emitted tokens are host ints (the coordinator's mirror), not device arrays
        blk = state.block_size
        pos = 0
        cache = None
        if (rec.snapshot is not None and state.resumable
                and 0 < rec.snap_tokens <= target
                and rec.snap_tokens % blk == 0):
            cache = self._restore(self.params, rec.snapshot,
                                  jnp.asarray(rec.snap_tokens, jnp.int32))
            pos = rec.snap_tokens
        if pos < s0:
            if pos == 0 or not state.resumable:
                # whole prompt at its own length — the same trace ordinary
                # admission warmed
                _, cache = self._prefill(
                    self.params, jnp.asarray(prompt[None, :], jnp.int32))
                pos = s0
            else:
                for cut in bucket_chunks(pos, s0, blk,
                                         self.scheduler.max_chunk_blocks):
                    chunk = jnp.asarray(ctx[None, pos:cut], jnp.int32)
                    self._resume_lens.add(cut - pos)
                    _, cache = self._prefill_resume(
                        self.params, chunk, cache,
                        jnp.asarray(pos, jnp.int32))
                    pos = cut
        # decode-path replay of emitted tokens, power-of-two chunked so
        # the compiled-trace count stays O(log max_new_tokens)
        if pos < target:
            for cut in bucket_chunks(pos, target, 1, None):
                seg = jnp.asarray(ctx[None, pos:cut], jnp.int32)
                self._replay_lens.add(cut - pos)
                cache = self._replay(self.params, seg,
                                     jnp.asarray(pos, jnp.int32), cache)
                pos = cut
        key = advance_key(request_key(req.sampling.seed),
                          jnp.asarray(k, jnp.int32))
        feed = jnp.asarray([rec.emitted[-1]], jnp.int32)
        (self._slot_caches, self._slot_tokens, self._slot_pos,
         self._slot_keys, self._slot_samp) = self._install_restored(
            self._slot_caches, self._slot_tokens, self._slot_pos,
            self._slot_keys, self._slot_samp, cache,
            jnp.asarray(si, jnp.int32), feed,
            jnp.asarray(target, jnp.int32), key,
            *device_scalars(req.sampling))
        slot.request = req
        slot.prefilling = False
        slot.emitted = list(rec.emitted)
        slot.lps = list(rec.lps)
        slot.ttft_s = rec.ttft_s
        slot.last_tok_s = None
        slot.pos0 = target
        slot.ticks = 0
        self._m_recovered.inc()
        if not req.sampling.is_greedy:
            self._m_sampled.inc()
        tr = self.telemetry.tracer
        if tr:
            tr.begin(f"slot{si}", "decode", rid=req.rid, recovered=True,
                     prompt_len=s0, replayed=k - 1,
                     from_ckpt=int(rec.snap_tokens))
        # recovery legitimately compiles fresh traces (replay lengths,
        # install_restored); re-arm the steady-state baseline so they are
        # not flagged as mid-serve retraces while real retraces on the
        # survivors' hot path still are
        wd = self.telemetry.watchdog
        if wd.steady:
            wd.mark_steady()

    def drain_checkpoints(self, *, tag_ns: bytes = b"psk-drain",
                          flush: bool = True) -> list[str]:
        """Graceful-shutdown persistence (the SIGTERM path): stop
        admissions, then run AT MOST one block of extra decode ticks so
        every live slot crosses a snapshot boundary, checkpointing each
        into the prefix cache's failover side-store as it aligns, and
        flush the store to the disk tier. Block-granularity states can
        only snapshot ON the grid, so "finish the current step, then
        checkpoint" necessarily means finishing out the current block.
        Returns the disk paths written ([] without a cache/save_dir)."""
        pc = self.prefix_cache
        if pc is None or self.state.snapshot_granularity is None:
            return []
        self.queue.clear()  # admissions stop; queued prompts are dropped
        done: set[int] = set()

        def sweep():
            for si, slot in enumerate(self._slots):
                if not slot.decoding or slot.request.rid in done:
                    continue
                got = self.snapshot_slot(si)
                if got is None:
                    continue
                tag = hashlib.sha256(
                    tag_ns + b":%d" % slot.request.rid).digest()
                pc.put_ckpt(tag, got[1], got[0])
                done.add(slot.request.rid)

        sweep()
        for _ in range(self.state.block_size):
            if all(not s.decoding or s.request.rid in done
                   for s in self._slots):
                break
            self.step()
            sweep()
        if flush and pc.save_dir is not None:
            return pc.flush_ckpts_to_disk()
        return []

    def cancel(self, rid: int):
        """Withdraw a request that has not yet produced a token: queued,
        pending recovery, or mid-prefill (its slot is freed and in-flight
        chunk work dropped; parked followers replan). A request that has
        emitted tokens is not cancellable here — let it retire. Returns
        the withdrawn Request, or None if rid is unknown/decoding."""
        tr = self.telemetry.tracer
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                del self.queue[i]
                if tr:
                    tr.instant("queue", "cancel", rid=rid)
                return req
        for i, (req, _rec) in enumerate(self._recover_pending):
            if req.rid == rid:
                del self._recover_pending[i]
                if tr:
                    tr.instant("queue", "cancel", rid=rid)
                return req
        for si, slot in enumerate(self._slots):
            if (slot.request is not None and slot.request.rid == rid
                    and slot.prefilling):
                job = next((j for j in self.scheduler.jobs
                            if j.slot == si), None)
                if job is not None:
                    self.scheduler.drop(job)
                req = slot.request
                slot.request = None
                slot.prefilling = False
                slot.emitted = []
                slot.lps = []
                slot.pos0 = 0
                slot.ticks = 0
                return req
        return None

    def _note_token(self, slot: _Slot, now: float) -> float | None:
        """Returns this token's inter-token latency in ms (None for a
        request's first token)."""
        itl_ms = None
        if slot.last_tok_s is not None:
            itl_ms = (now - slot.last_tok_s) * 1e3
            self._m_itl.observe(itl_ms)
        slot.last_tok_s = now
        return itl_ms

    def _append_firsts(self, firsts, done, now: float):
        """Record admissions' first tokens (host sync per token future —
        they were dispatched together, so the first wait covers all)."""
        tr = self.telemetry.tracer
        for si, rid, tok, lp in firsts:
            slot = self._slots[si]
            req = slot.request
            if req is None or req.rid != rid:
                continue
            slot.emitted.append(int(np.asarray(tok)[0]))  # jaxlint: disable=host-sync-in-jit-path -- deliberate: admissions' first-token sync (one wait covers the batch)
            if self.logprobs:
                slot.lps.append(float(np.asarray(lp)))  # jaxlint: disable=host-sync-in-jit-path -- rides the first-token wait above
            slot.ttft_s = now - req.submit_time
            self._m_ttft.observe(slot.ttft_s * 1e3)
            self._m_tokens.inc()
            self._note_token(slot, now)
            if tr:
                tr.instant(f"slot{si}", "first_token", rid=rid,
                           ttft_ms=round(slot.ttft_s * 1e3, 3))
            fin = self._check_finished(si)
            if fin is not None:
                done.append(fin)

    def _dispatch_decode(self, firsts) -> _TickRecord | None:
        """Dispatch one lockstep decode tick over the installed slots
        (async). Mid-prefill slots are frozen by the active mask exactly
        like drained ones. An install always leaves its slot decoding, so
        admissions' first tokens (`firsts`) always ride a real tick
        record."""
        active = np.array([s.decoding for s in self._slots])
        if not active.any():
            assert not firsts, "installed slots must be decoding"
            return None
        rids = [s.request.rid if s.decoding else None for s in self._slots]
        t0 = time.perf_counter()
        (toks, lps, self._slot_tokens, self._slot_pos, self._slot_keys,
         self._slot_caches) = self._decode(
            self.params, self._slot_tokens, self._slot_pos, self._slot_keys,
            self._slot_samp, self._slot_caches, jnp.asarray(active))
        self._m_ticks.inc()
        # ticks counts DISPATCHED decode steps per occupancy: the device
        # cache absorbs each slot's feed token at dispatch, so
        # pos0 + ticks is the number of stream tokens the device state
        # covers right now — the checkpoint depth snapshot_slot reports
        for si, slot in enumerate(self._slots):
            if active[si]:
                slot.ticks += 1
        return _TickRecord(toks, lps, self._tick_fold, active, rids, firsts,
                           t0)

    def _decode(self, params, toks, pos, keys, samp, caches, active):
        """Run the jitted decode tick and return its first six outputs
        (tokens, logprobs, then the slots' feed tokens, positions, keys
        and caches); its fold flag is left in `self._tick_fold` for the
        tick record, so whatever wraps this method keeps the six-output
        form. `caches` is donated."""
        *outs, self._tick_fold = self._decode_tick(params, toks, pos, keys,
                                                   samp, caches, active)
        return tuple(outs)

    def _sync_record(self, rec: _TickRecord, done):
        """Sync one tick record's tokens to the host and account them.
        First tokens precede the tick's token in each request's stream, so
        admissions recorded on this tick are appended first; a slot whose
        request retired (or was replaced) since dispatch fails the rid
        check and its speculative token is dropped."""
        tr = self.telemetry.tracer
        if tr:
            tr.begin("tick", "host_sync")
        toks = np.asarray(rec.toks)  # jaxlint: disable=host-sync-in-jit-path -- THE per-tick sync: double-buffered one tick behind under overlap
        lps = np.asarray(rec.lps) if self.logprobs else None  # jaxlint: disable=host-sync-in-jit-path -- same wait as toks (dispatched together)
        fold = bool(rec.fold)  # jaxlint: disable=host-sync-in-jit-path -- same wait as toks (dispatched together)
        now = time.perf_counter()
        if fold:
            self._m_fold_ticks.inc()
        if tr:
            tr.end("tick", slots=int(rec.active.sum()), fold=fold)
        # NB: with a prefill budget (or overlap), admission chunk work
        # dispatched ahead of this tick executes on the same device stream
        # and is absorbed into this wait — decode_s measures the decode
        # PIPELINE's wall time (the serving cadence), while prefill_s
        # holds admission host dispatch + lockstep first-token sync time
        t_ref = (rec.t_dispatch if self._last_sync is None
                 else max(rec.t_dispatch, self._last_sync))
        self._m_decode_s.inc(now - t_ref)
        self._last_sync = now
        if self._gap_anchor is not None:
            self._m_tick_gap.observe((now - self._gap_anchor) * 1e3)
        self._gap_anchor = now
        if tr:
            tr.begin("tick", "retire")
        self._append_firsts(rec.firsts, done, now)
        for si, slot in enumerate(self._slots):
            if not rec.active[si]:
                continue
            req = slot.request
            if req is None or req.rid != rec.rids[si]:
                continue
            slot.emitted.append(int(toks[si]))
            if self.logprobs:
                slot.lps.append(float(lps[si]))
            self._m_tokens.inc()
            itl_ms = self._note_token(slot, now)
            if tr:
                tr.instant(f"slot{si}", "token", rid=req.rid,
                           itl_ms=round(itl_ms, 3) if itl_ms else 0.0)
            fin = self._check_finished(si)
            if fin is not None:
                done.append(fin)
        if tr:
            tr.end("tick", retired=len(done))
        if not any(s.decoding for s in self._slots) and self._pending is None:
            # busy streak over (nothing decoding, no tick in flight): the
            # interval until the next admission's tick is idle time, not a
            # decode stall
            self._gap_anchor = None

    # root of the tick critical path: jaxlint walks the call graph from
    # here and flags any un-annotated device->host sync
    # jaxlint: hot-path
    def step(self) -> list[RequestOutput]:
        """One engine tick.

        Lockstep (overlap=False): admit (up to one prefill budget of chunk
        work, all admissions' first tokens synced together), decode every
        installed slot once, sync this tick's tokens before returning.

        Overlapped (overlap=True): dispatch chunk work and the decode tick
        asynchronously, then sync the PREVIOUS tick's tokens — the device
        computes tick N while the host accounts tick N-1.

        Returns requests that finished this tick."""
        done: list[RequestOutput] = []
        tr = self.telemetry.tracer
        if tr:
            tr.begin("tick", "tick", n=int(self._m_ticks.value))
            tr.begin("tick", "plan")
        self._start_admissions()
        if tr:
            tr.end("tick")
        t0 = time.perf_counter()
        if tr:
            tr.begin("tick", "chunk_dispatch")
        firsts = [self._install(job) for job in self.scheduler.tick()]
        if not self.overlap and firsts:
            # one host sync for every admission this tick (the dispatches
            # above all ran back-to-back without blocking)
            jax.block_until_ready(firsts[-1][2])  # jaxlint: disable=host-sync-in-jit-path -- lockstep mode's single per-tick admission sync, by design
        if tr:
            tr.end("tick", installs=len(firsts))
        self._m_prefill_s.inc(time.perf_counter() - t0)
        if self.overlap:
            if tr:
                tr.begin("tick", "decode_dispatch")
            rec = self._dispatch_decode(firsts)
            if tr:
                tr.end("tick")
            prev, self._pending = self._pending, rec
            if prev is not None:
                self._sync_record(prev, done)
        else:
            self._append_firsts(firsts, done, time.perf_counter())
            if tr:
                tr.begin("tick", "decode_dispatch")
            rec = self._dispatch_decode([])
            if tr:
                tr.end("tick")
            if rec is not None:
                self._sync_record(rec, done)
        if tr:
            tr.end("tick")  # the enclosing per-tick span
        self.telemetry.on_tick()
        return done

    def run(self) -> list[RequestOutput]:
        """Drain the queue and all active slots. Returns outputs in
        completion order (FIFO admission => arrival order for equal-length
        generations)."""
        out = []
        while self.busy:
            out.extend(self.step())
        return out

    # ------------------------------------------------------------------
    # accounting
    # ------------------------------------------------------------------

    def reset_stats(self):
        """Zero the accounting (e.g. after a compile warm-up run) and mark
        the jit caches steady: any compiled-trace growth after this point
        is a mid-serve recompile the retrace watchdog counts."""
        self.finished = []
        self._gap_anchor = None
        self._last_sync = None
        self.telemetry.reset()
        self._set_mesh_gauges()  # reset() zeroes set-gauges
        self.scheduler.reset_stats()
        if self.prefix_cache is not None:
            self.prefix_cache.reset_stats()

    def _set_mesh_gauges(self):
        for axis, n in self.plan.axis_sizes.items():
            self._g_mesh_devices.labels(axis=axis).set(float(n))
        self._g_mesh_info.labels(shape=self.plan.describe()).set(1.0)

    # histogram bucket edges (milliseconds, final bucket open-ended);
    # registry semantics are Prometheus `le`: a value exactly on an edge
    # falls in the bucket that edge bounds
    TTFT_EDGES_MS = (1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
                     1000.0, float("inf"))
    ITL_EDGES_MS = (0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 250.0,
                    1000.0, float("inf"))
    TICK_GAP_EDGES_MS = ITL_EDGES_MS

    def stats(self) -> dict:
        # still-resident requests count too: total_decode_s includes the
        # ticks spent on live slots, so summing only self.finished would
        # bias mid-drain throughput low
        live = [s for s in self._slots if not s.free]
        gen_tokens = (sum(len(o.tokens) for o in self.finished)
                      + sum(len(s.emitted) for s in live))
        # first token of every request comes from the prefill logits, so
        # decode throughput counts only decode-step-produced tokens
        decode_tokens = (sum(o.decode_steps for o in self.finished)
                         + sum(max(len(s.emitted) - 1, 0) for s in live))
        decode_s = self._m_decode_s.value
        # tick_gap `median` and `p50` are one number from one code path
        # (the registry histogram); both keys stay for compatibility
        gap_p = self._m_tick_gap.percentiles()
        out = {
            "requests": len(self.finished),
            "active_requests": len(live),
            "generated_tokens": gen_tokens,
            "prefills": int(self._m_prefills.value),
            "sampled_requests": int(self._m_sampled.value),
            "recovered": int(self._m_recovered.value),
            "decode_steps": int(self._m_ticks.value),
            "fold_ticks": int(self._m_fold_ticks.value),
            "prefill_s": self._m_prefill_s.value,
            "decode_s": decode_s,
            "decode_tok_per_s": (decode_tokens / decode_s
                                 if decode_s else 0.0),
            # observability for the stall this engine's scheduler removes:
            # inter-token latency across all requests, TTFT distribution,
            # and the host-observed gap between CONSECUTIVE decode-tick
            # completions within a busy streak — idle periods between
            # bursts are excluded, so an admission that stalls decode
            # shows up as a max gap far above the median while think time
            # between requests never does (recent bounded window)
            "itl_ms": self._m_itl.percentiles(),
            "ttft_ms": self._m_ttft.percentiles(),
            "ttft_hist": {"edges_ms": list(self.TTFT_EDGES_MS),
                          "counts": self._m_ttft.counts},
            "tick_gap_ms": {
                **gap_p,
                "median": gap_p["p50"],
                "max": self._m_tick_gap.max,
            },
            "retraces": self.telemetry.watchdog.retraces,
            "scheduler": self.scheduler.stats(),
            "mesh": {
                "shape": self._mesh_desc,
                "devices": dict(self.plan.axis_sizes),
            },
        }
        if self.prefix_cache is not None:
            out["prefix_cache"] = self.prefix_cache.stats()
        return out
