"""Per-request sampling for the serve stack (temperature / top-k / top-p).

One fused sampler serves every consumer — `generate`, the engine's
prefill first-token, and the all-slot decode tick. Per-slot parameters
live on device as stacked arrays (`SlotSampling`) next to the engine's
`_slot_tokens`/`_slot_pos`, so a single jitted decode step samples all
slots with *heterogeneous* params (a greedy request co-resident with a
temperature-0.8 top-k-40 one) without retracing per combination: top-k /
top-p are data, applied as mask-to-neg-inf in f32, and greedy is a
`jnp.where` over the argmax.

Determinism contract: a request's tokens depend only on
`(seed, prompt, SamplingParams)` — never on slot index, admission order,
or what else shares the batch. Each request's PRNG stream starts at
`request_key(seed)` and advances by one `jax.random.split` per sampled
token (the first split happens at the prefill first-token), so
`generate(..., sampling=sp)` row 0 is bit-identical to a single-slot
`ServeEngine` run of the same request.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SamplingParams:
    """Per-request decoding parameters.

    temperature: 0 => greedy (argmax). top_k: 0 disables; k >= 1 keeps the
    k highest logits. top_p: 1.0 disables; in (0, 1) keeps the smallest
    prefix of the sorted distribution with cumulative probability >= p
    (the argmax token is always kept). seed: the request's whole PRNG
    stream. greedy: explicit override; None => temperature <= 0.
    """
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    greedy: bool | None = None

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0, got {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 = off), got {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1], got {self.top_p}")
        if self.greedy is False and self.temperature <= 0.0:
            raise ValueError("greedy=False requires temperature > 0")

    @property
    def is_greedy(self) -> bool:
        return self.greedy if self.greedy is not None else self.temperature <= 0.0

    def describe(self) -> str:
        """Compact human-readable form for telemetry event args and logs
        ("greedy", "t=0.8", "t=0.8 k=40 p=0.95"). Omits defaults; the seed
        is identity, not strategy, so it is not part of the description."""
        if self.is_greedy:
            return "greedy"
        parts = [f"t={self.temperature:g}"]
        if self.top_k:
            parts.append(f"k={self.top_k}")
        if self.top_p < 1.0:
            parts.append(f"p={self.top_p:g}")
        return " ".join(parts)


class SlotSampling(NamedTuple):
    """Slot-stacked device mirror of SamplingParams (engine state)."""
    temperature: jax.Array   # (slots,) f32
    top_k: jax.Array         # (slots,) i32, 0 = off
    top_p: jax.Array         # (slots,) f32
    greedy: jax.Array        # (slots,) bool


def init_slot_sampling(slots: int) -> SlotSampling:
    """All-greedy stacked params (free slots sample-along harmlessly)."""
    return SlotSampling(
        temperature=jnp.zeros((slots,), jnp.float32),
        top_k=jnp.zeros((slots,), jnp.int32),
        top_p=jnp.ones((slots,), jnp.float32),
        greedy=jnp.ones((slots,), jnp.bool_),
    )


def device_scalars(sp: SamplingParams):
    """(temperature, top_k, top_p, greedy) as fixed-dtype device scalars,
    so jitted consumers never retrace across parameter values."""
    return (jnp.asarray(sp.temperature, jnp.float32),
            jnp.asarray(sp.top_k, jnp.int32),
            jnp.asarray(sp.top_p, jnp.float32),
            jnp.asarray(sp.is_greedy, jnp.bool_))


def init_slot_keys(slots: int) -> jax.Array:
    """(slots, 2) uint32 raw PRNG keys; admission overwrites per request."""
    return jnp.zeros((slots, 2), jnp.uint32)


def request_key(seed: int, row: int = 0) -> jax.Array:
    """The PRNG stream for one request: depends only on (seed, row).

    `generate` gives batch row r stream `request_key(seed, r)`; the engine
    is batch-1 per request and uses row 0, which is what makes the two
    paths bit-identical.
    """
    return jax.random.fold_in(jax.random.PRNGKey(seed), row)


@jax.jit
def advance_key(key: jax.Array, n: jax.Array) -> jax.Array:
    """The request stream key after `n` emitted tokens.

    `sample_step` advances a stream as `split(key)[0]` once per token, so
    a recovered request that already emitted n tokens resumes its stream
    at exactly `advance_key(request_key(seed), n)` — this is what makes
    failover bit-identical to the fault-free run. `n` is traced (one
    compiled trace for every replay length).
    """
    return jax.lax.fori_loop(
        0, jnp.asarray(n, jnp.int32),
        lambda _, k: jax.random.split(k)[0], key)


# Filter candidate budget: top-k / top-p thresholds are computed over the
# CANDIDATES largest logits (lax.top_k) instead of a full-vocab sort —
# XLA's CPU sort is serial and costs milliseconds at LM vocab sizes, while
# top_k stays ~100us. Exact whenever the vocab fits (V <= CANDIDATES,
# every smoke config) or the filtered set does (top_k <= CANDIDATES and
# the p-mass nucleus inside the top CANDIDATES logits — standard serving
# practice); beyond that top_k clips and the nucleus truncates to the
# candidate set. The top_k=0 / top_p>=1.0 bypass never touches candidates
# and stays bit-exact at any vocab size.
CANDIDATES = 128


def sample_token(key, logits, temperature, top_k, top_p, greedy):
    """Sample one token id from unnormalized logits (V,) -> int32 scalar.

    All params are traced scalars (vmap-able over slots). Filtering is
    mask-to-neg-inf in f32 on the temperature-scaled logits: top-k keeps
    the k largest, then top-p keeps the shortest descending-sorted prefix
    reaching cumulative probability p (computed over the top CANDIDATES
    logits, see above; ties at the threshold are all kept). top_k=0 and
    top_p>=1.0 are exact no-ops (the masked logits equal the scaled
    logits bit-for-bit, so top_p=1.0 sampling == plain
    `jax.random.categorical(key, logits/temperature)`).
    """
    l32 = logits.astype(jnp.float32)
    greedy_tok = jnp.argmax(l32).astype(jnp.int32)
    v = l32.shape[-1]
    c = min(v, CANDIDATES)
    t = jnp.where(temperature > 0, jnp.asarray(temperature, jnp.float32), 1.0)
    scaled = l32 / t
    cand = jax.lax.top_k(scaled, c)[0]                 # (c,) descending
    k = jnp.where(top_k <= 0, c, jnp.clip(top_k, 1, c))
    in_k = jnp.arange(c) < k
    cand_kept = jnp.where(in_k, cand, -jnp.inf)
    # nucleus probabilities: normalized over the top-k-kept set when top-k
    # is on (matching a post-top-k softmax), over the FULL vocab when off
    lse = jnp.where(top_k <= 0,
                    jax.scipy.special.logsumexp(scaled),
                    jax.scipy.special.logsumexp(cand_kept))
    probs = jnp.exp(cand_kept - lse)
    cum_excl = jnp.cumsum(probs) - probs               # mass strictly above
    keep = in_k & ((cum_excl < top_p) | (top_p >= 1.0))
    keep = keep.at[0].set(True)                        # argmax always kept
    # both filters keep a prefix of the descending candidates, so one
    # logit threshold applies them jointly in the original order
    thresh = jnp.min(jnp.where(keep, cand, jnp.inf))
    thresh = jnp.where((top_k <= 0) & (top_p >= 1.0), -jnp.inf, thresh)
    masked = jnp.where(scaled >= thresh, scaled, -jnp.inf)
    tok = jax.random.categorical(key, masked).astype(jnp.int32)
    return jnp.where(greedy, greedy_tok, tok)


def sample_step(key, logits, temperature, top_k, top_p, greedy):
    """One step of a request's sampling schedule: split the stream key,
    sample from (V,) logits. Returns (token, advanced_key). Every consumer
    (generate scan, engine first-token, engine decode tick) goes through
    this so the key schedule — one split per emitted token — is identical
    everywhere; that schedule IS the determinism contract.
    """
    key, sub = jax.random.split(key)
    return sample_token(sub, logits, temperature, top_k, top_p, greedy), key


def sample_first(logits, key, temperature, top_k, top_p, greedy, *,
                 logprobs: bool = False):
    """A request's first token, from its prefill last-position logits
    (1, V) — the first split of the request's PRNG stream happens here.
    Lives in the chunked admission path: the scheduler's final prefill
    chunk produces `logits`, and this runs as one more async dispatch on
    top of it (no host sync). Returns (token (1,), advanced_key,
    logprob ()) — the logprob is 0 unless `logprobs` (trace-static).
    """
    tok, key = sample_step(key, logits[0], temperature, top_k, top_p, greedy)
    if logprobs:
        lp = jax.nn.log_softmax(logits[0].astype(jnp.float32))[tok]
    else:
        lp = jnp.zeros((), jnp.float32)
    return tok[None], key, lp
