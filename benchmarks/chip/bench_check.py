"""The comparison that decides `correct`, against the plain reference.

Serving: for each sampled finished request, the reference runs once over
its prompt and served tokens (teacher-forced, padded at the end to one
length so it compiles once), in float32 at full precision. Read per
request, from the engine's reported log-probability of each served token:
  logit_gap            the widest gap by which a served token's logit lies
                       below the reference's best at its position
  logprob_gap_first    |log p| gap between the engine's and the
                       reference's log-probability of the first served
                       token (made by prefill)
  logprob_gap_decode   the widest such gap over the tokens made by decode
                       ticks (folds included)
A wrong token, state or fold moves all three by O(1). The cell's limits
file names those that are compared; the others are logged.

The control puts the reference in the program's place one precision down.
The configuration's matmuls run at `matmul_precision` bfloat16 (one
bfloat16 pass, float32 sums), so the control rounds every matmul operand
to the 3 mantissa bits of fp8 (e4m3; float32's exponent range, so no scale
enters), with float32 sums. Its logit_gap is read at the token it puts
first, as a greedy control would serve it. That gap is only ever the
margin of a near tie, the same for a control as for the program, so it
does not tell them apart; the log-probability gaps do.

Training: see bench_train.
"""
from __future__ import annotations

import numpy as np


def make_params(jax, ref, shapes, seed: int):
    """The benchmark's weights for `seed`, drawn on the device in one
    jitted call (the seed is an argument, so every seed shares one
    compiled program)."""
    words = np.array([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF],
                     np.uint32)

    def init(w):
        k = jax.random.fold_in(jax.random.PRNGKey(0), w[0])
        return ref.init_params(shapes, jax.random.fold_in(k, w[1]))

    return jax.jit(init)(words)


def pick_sample(outputs, seed: int, min_tokens: int, max_requests: int):
    """The longest finished request, then others drawn from the seed until
    `min_tokens` served tokens or `max_requests` requests."""
    if not outputs:
        return []
    order = sorted(outputs, key=lambda o: -(o.prompt_len + len(o.tokens)))
    pick = [order[0]]
    rng = np.random.default_rng([seed & (2 ** 64 - 1), 4])
    rest = [order[i] for i in rng.permutation(np.arange(1, len(order)))]
    for o in rest:
        if (sum(len(p.tokens) for p in pick) >= min_tokens
                or len(pick) >= max_requests):
            break
        pick.append(o)
    return pick


def _rows(prompt, served, length: int):
    """Padded input, targets and mask: row P-1+t of the reference predicts
    served[t]."""
    p, n = len(prompt), len(served)
    toks = np.zeros(length, np.int32)
    toks[:p] = prompt
    toks[p:p + n - 1] = served[:-1]
    target = np.zeros(length, np.int32)
    target[p - 1:p - 1 + n] = served
    mask = np.zeros(length, bool)
    mask[p - 1:p - 1 + n] = True
    return toks, target, mask


CONTROL_MANTISSA = 3      # fp8 e4m3's mantissa: one step below bfloat16


def _read(logits, target):
    """log p of `target` and the gap of its logit below the row's best."""
    import jax
    import jax.numpy as jnp
    lsm = jax.nn.log_softmax(logits, axis=-1)
    lp = jnp.take_along_axis(lsm, target[:, None], -1)[:, 0]
    return lp, lsm.max(-1) - lp


class Reference:
    """Compiled reference programs for one configuration and length: per
    served token, the reference's log-probability and logit gap below its
    best, and the same read at the control's choices."""

    def __init__(self, jax, ref, config: dict, length: int, rows: int = 512):
        import jax.numpy as jnp
        self.jax = jax
        self.padded = -(-length // rows) * rows

        def reference(params, toks, target, pick):
            logits = ref.forward(config, params, toks, rows=rows)
            lp, gap = _read(logits, target)
            return lp, gap, _read(logits, pick)[1]

        def control(params, toks, target):
            logits = ref.forward(config, params, toks, rows=rows,
                                 mantissa=CONTROL_MANTISSA)
            return _read(logits, target)[0], jnp.argmax(logits, -1)

        self._ref = jax.jit(reference)
        self._control = jax.jit(control)

    def _call(self, fn, *args):
        # float32 matmuls at full float32 precision: on a TPU the default
        # is one bfloat16 pass
        with self.jax.default_matmul_precision("highest"):
            return [np.asarray(x) for x in fn(*args)]

    def reference(self, params, prompt, served) -> dict:
        """Reference log-probability and logit gap of each served token."""
        toks, target, mask = _rows(prompt, served, self.padded)
        lp, gap, _ = self._call(self._ref, params, toks, target, target)
        return {"lp": lp[mask], "gap": gap[mask]}

    def control(self, params, prompt, served) -> dict:
        """The control in the program's place: its log-probability of each
        served token, and the reference's logit gap at the token the
        control puts first."""
        toks, target, mask = _rows(prompt, served, self.padded)
        lp, pick = self._call(self._control, params, toks, target)
        _, _, gap = self._call(self._ref, params, toks, target,
                               pick.astype(np.int32))
        return {"lp": lp[mask], "gap": gap[mask]}


def gaps(got_lp, want: dict, gap=None) -> dict:
    """The numbers read for one request: the widest logit gap (`want`'s,
    or `gap` where given) and, where log-probabilities `got_lp` were
    reported, their gaps to the reference's: the first served token (made
    by prefill) and the widest over the tokens made by decode ticks."""
    out = {"logit_gap": float(np.max(want["gap"] if gap is None else gap))}
    if got_lp is not None:
        d = np.abs(np.asarray(got_lp, np.float64) - want["lp"])
        out["logprob_gap_first"] = float(d[0])
        out["logprob_gap_decode"] = float(d[1:].max(initial=0.0))
    return out


def control_gaps(ctrl: dict, want: dict) -> dict:
    """The same numbers for the control in the program's place."""
    return gaps(ctrl["lp"], want, ctrl["gap"])


def widest(per_request: list[dict]) -> dict:
    """Per number, the widest of several requests' readings."""
    return ({k: max(r[k] for r in per_request) for k in per_request[0]}
            if per_request else {})
