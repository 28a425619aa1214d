"""The one traffic generator: reads a mix file from `traffic/` and makes a
request schedule or training data from a seed.

Every seed gets the same work in another order. Prompt and output lengths
and inter-arrival gaps are drawn as the quantiles (i + 0.5) / n of the
mix's distributions, then put in an order drawn from the seed; only token
ids and the order are random. So two seeds differ in which request comes
when, never in how much there is to do, and a run's numbers move with the
system and not with the draw. In the open loop each run of STRATA
consecutive requests holds one value from each of STRATA equal-count
slices of every distribution, so no stretch of the window gathers the
longest requests or the shortest gaps: under a plain shuffle the order
decided how often all slots were held, so a TTFT tail moved with the seed
more than with the system.

Serving mix keys:
  kind            "serve"
  loop            "open" (arrivals on a schedule) or "closed" (a retired
                  request is replaced at once)
  rate_per_s      open loop: mean Poisson arrival rate
  prompt, output  {"dist": "lognormal", "median", "sigma", "min", "max",
                   "multiple"} or {"dist": "uniform", "min", "max",
                   "multiple"}
  first_wave_output  closed loop: the outputs still owed by the requests
                  that fill the slots at window start
  pool            closed loop: requests drawn per run
  engine          ServeEngine settings (slots, max_len, prefill_budget,
                  overlap)
  base            optional: the name of a mix whose keys this one extends
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n values of `spec` at the quantiles (i + 0.5) / n, clipped and
    rounded up to `multiple`."""
    u = (np.arange(n) + 0.5) / n
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown distribution {spec['dist']!r}")
    m = spec.get("multiple", 1)
    x = np.ceil(np.clip(x, spec["min"], spec["max"]) / m) * m
    return x.astype(np.int64)


def lengths(spec: dict) -> list[int]:
    """Every length `spec` can produce."""
    m = spec.get("multiple", 1)
    lo = math.ceil(spec["min"] / m) * m
    return list(range(lo, spec["max"] + 1, m))


@dataclass
class Schedule:
    due_s: np.ndarray        # (n,) seconds after window start, ascending
    prompts: list            # n int32 arrays
    outputs: np.ndarray      # (n,) tokens owed per request
    first_wave: int          # leading requests that fill the slots before
                             # the window (closed loop), 0 for open loop


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), salt])


STRATA = 4


def deal(values: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """`values` (ascending) in an order drawn from `rng`: cut into STRATA
    equal-count slices and dealt in runs of STRATA, each run holding one
    value of every slice, in a random order."""
    slices = [rng.permutation(s) for s in np.array_split(values, STRATA)]
    out = [slices[g][j] for j in range(len(slices[0]))
           for g in rng.permutation(STRATA) if j < len(slices[g])]
    return np.asarray(out, values.dtype)


def serve_schedule(mix: dict, seed: int, seconds: float,
                   vocab: int) -> Schedule:
    rng = _rng(seed, 1)
    if mix["loop"] == "open":
        n = max(1, round(mix["rate_per_s"] * seconds))
        p = deal(_quantiles(mix["prompt"], n), rng)
        o = deal(_quantiles(mix["output"], n), rng)
        gaps = deal(-np.log1p(-(np.arange(n) + 0.5) / n), rng)  # Exp(1)
        # n arrivals fill [0, seconds): the first at 0, the rest after the
        # dealt gaps, scaled so that the window holds them all
        due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        due *= seconds / gaps.sum()
        first = 0
    elif mix["loop"] == "closed":
        slots = mix["engine"]["slots"]
        n = mix["pool"]
        p = rng.permutation(_quantiles(mix["prompt"], n + slots))
        o = np.concatenate([
            rng.permutation(_quantiles(mix["first_wave_output"], slots)),
            rng.permutation(_quantiles(mix["output"], n))])
        due = np.zeros(n + slots)
        first = slots
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    toks = _rng(seed, 2)
    prompts = [toks.integers(0, vocab, size=int(k), dtype=np.int32)
               for k in p]
    return Schedule(due, prompts, o.astype(np.int64), first)


def warm_prompts(mix: dict, vocab: int) -> list[np.ndarray]:
    """One prompt of every length the mix can draw (fixed tokens)."""
    rng = np.random.default_rng(0)
    return [rng.integers(0, vocab, size=n, dtype=np.int32)
            for n in lengths(mix["prompt"])]


# ---------------------------------------------------------------------------
# training data: a sparse random Markov chain, vectorised over rows
# ---------------------------------------------------------------------------

def markov_rows(vocab: int, rows: int, seq: int, seed: int,
                branching: int = 4) -> np.ndarray:
    """(rows, seq + 1) int32 token rows from one random Markov chain with
    `branching` successors per token (Dirichlet(0.5) weights). The rows
    are independent walks, so every row differs."""
    rng = _rng(seed, 3)
    nxt = rng.integers(0, vocab, size=(vocab, branching))
    cum = np.cumsum(rng.dirichlet(np.ones(branching) * 0.5, size=vocab), 1)
    toks = np.empty((rows, seq + 1), np.int32)
    toks[:, 0] = rng.integers(0, vocab, size=rows)
    u = rng.random((seq, rows))
    for t in range(seq):
        c = cum[toks[:, t]]
        choice = (u[t][:, None] > c).sum(1).clip(0, branching - 1)
        toks[:, t + 1] = nxt[toks[:, t], choice]
    return toks
