"""Training cells: the program's jitted `make_train_step`, fed through its
DataIterator, with the loss read back every step as the launcher does.

Set-up makes the weights from the seed and the rows of training data from
the benchmark's Markov source (a pool of distinct rows, cycled in the
window), builds the one compiled step with its state, and drives it
through its first three steps, which the reference follows. The same step
and state then run the window.

End-to-end: train_tokens_per_s = tokens of steps completed in the window /
the window, on the host clock.

The check (`correct`), against the plain reference in float32 at full
precision, on the same weights and rows:
  train_loss_gap    worst of the three steps' |loss - ref| / |ref|
  train_grad_gap    worst leaf of | |g| - |g_ref| | / max(|g_ref|, the
                    median leaf's |g_ref|), g the first step's gradient as
                    the optimizer got it (read back from Adam's first
                    moment: m = (1 - b1) g after one step)
  train_update_gap  the same for the parameters' change over the three
                    steps; leaves whose reference gradient is under a
                    thousandth of the median leaf's move by round-off alone
                    under Adam and are left out
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass

import numpy as np

import bench_check
import bench_harness as bh
import bench_trace
import bench_traffic

CHECKED_STEPS = 3


@dataclass
class TrainCtx:
    """What the per-layer readers of a training cell can read."""
    cell: object
    device: dict
    reduced: object = None
    rates: dict = None

    @property
    def peaks(self):
        return self.device["peaks"]

    def work(self, name):
        return self.cell.work(name)


def leaf_norms(jax, tree) -> dict:
    """{path: float32 norm} of every leaf, computed on the device."""
    import jax.numpy as jnp
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda xs: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in xs])([x for _, x in flat])
    return {jax.tree_util.keystr(kp): float(n)
            for (kp, _), n in zip(flat, norms)}


def worst_norm_gap(got: dict, want: dict, keep=None) -> tuple[float, str]:
    """Worst leaf of | |got| - |want| | / max(|want|, median |want|)."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], med) for k in keys}
    k = max(gaps, key=gaps.get)
    return gaps[k], k


def optimizer(mix: dict):
    from repro.configs.base import TrainConfig
    return TrainConfig(seq_len=mix["seq"], global_batch=mix["batch"])


@dataclass
class Program:
    """The one compiled step and its state after the checked steps."""
    step: object
    state: object
    it: object
    losses: list
    grad: dict          # leaf norms of the first gradient the optimizer got
    delta: dict         # leaf norms of the parameters' change
    shapes: object
    rows: object
    tcfg: object
    ref: object


def program_steps(cell, jax, seed: int, hook=None) -> Program:
    """Weights and rows from the seed, the jitted step, and its first
    CHECKED_STEPS steps through the iterator the window goes on with."""
    from repro.data import DataIterator
    from repro.models import build_model
    from repro.train import init_train_state, make_train_step
    mix = cell.traffic
    cfg = bh.arch_config(cell.config, remat=mix["remat"])
    model = build_model(cfg)
    ref = cell.reference()
    tcfg = optimizer(mix)
    batch, seq, pool = mix["batch"], mix["seq"], mix["pool"]
    shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    params = bench_check.make_params(jax, ref, shapes, seed)
    rows = bench_traffic.markov_rows(cfg.vocab_size, pool * batch, seq, seed)
    it = DataIterator(lambda b, s, k: rows[(k % pool) * b:(k % pool + 1) * b],
                      batch, seq, seed=0)
    step = jax.jit(make_train_step(model, cfg, tcfg))
    if hook is not None:
        step = hook(step)
    state = init_train_state(params)
    losses = []
    for k in range(CHECKED_STEPS):
        state, m = step(state, next(it))
        losses.append(float(m["loss"]))
        if k == 0:
            grad = {p: v / (1.0 - tcfg.b1)
                    for p, v in leaf_norms(jax, state.opt.m).items()}
    delta = leaf_norms(jax, jax.tree_util.tree_map(
        lambda a, b: a - b, state.params, params))
    return Program(step, state, it, losses, grad, delta, shapes, rows, tcfg,
                   ref)


def compare(got: dict, want: dict) -> dict:
    """The three numbers `correct` compares, with the worst leaves."""
    loss = max(abs(a - b) / abs(b) for a, b in zip(got["loss"],
                                                   want["loss"]))
    grad, grad_leaf = worst_norm_gap(got["grad"], want["grad"])
    med = float(np.median(list(want["grad"].values())))
    moving = {k for k, v in want["grad"].items() if v >= 1e-3 * med}
    upd, upd_leaf = worst_norm_gap(got["delta"], want["delta"], moving)
    return {"train_loss_gap": loss, "train_grad_gap": grad,
            "train_update_gap": upd, "grad_leaf": grad_leaf,
            "update_leaf": upd_leaf,
            "left_out": sorted(set(want["grad"]) - moving)}


def run(cell, run):
    jax, seed, seconds = run.jax, run.seed, run.seconds
    mix = cell.traffic
    prog = program_steps(cell, jax, seed, run.engine_hook)
    step, state, it = prog.step, prog.state, prog.it
    setup_s = time.perf_counter() - run.t_start
    bh.log(f"set-up {setup_s:.1f}s, {run.counter.report()}; losses "
           f"{prog.losses}; window {seconds}s")

    t0 = time.perf_counter()
    end = t0 + seconds
    tracer = bench_trace.TailTrace(run.trace, run.root, t0, seconds,
                                   cell.chips)
    steps = bad = 0
    while time.perf_counter() < end:
        tracer.poll(time.perf_counter())
        with tracer.span("bench.step"):
            state, m = step(state, next(it))
            loss = float(m["loss"])
        steps += 1
        bad += not np.isfinite(loss)
    t_close = time.perf_counter()
    reduced = tracer.finish()
    win_compiles, win_hits, _ = run.counter.mark()
    window_s = t_close - t0
    rate = steps * mix["batch"] * mix["seq"] / window_s
    bh.log(f"window {window_s:.2f}s: {steps} steps, {rate:.1f} tokens/s, "
           f"last loss {loss:.4f}; compiles {win_compiles}, cache loads "
           f"{win_hits}")
    device = {k: v for k, v in run.device.items() if k != "peaks"}
    device["memory_peak_bytes"] = bh.memory_peak(jax, cell.chips)
    del state, step, prog.state, prog.step
    gc.collect()

    # ---- the check ----
    want = reference_steps(jax, prog, cell.config, seed)
    got = compare({"loss": prog.losses, "grad": prog.grad,
                   "delta": prog.delta}, want)
    bh.log(f"check: ref losses {want['loss']}; worst grad leaf "
           f"{got['grad_leaf']}; worst update leaf {got['update_leaf']}; "
           f"left out of the update: {got['left_out']}")
    checks = bh.compare(cell, {k: got[k] for k in (
        "train_loss_gap", "train_grad_gap", "train_update_gap")})
    checks["window_compiles"] = {"value": win_compiles,
                                 "limit": 0}
    result = {"correct": bh.checks_ok(checks) and not bad,
              "attempted": steps, "failed": bad, "device": device}
    e2e = {"setup_s": setup_s, "train_tokens_per_s": rate}
    ctx = TrainCtx(cell=cell, device=run.device, reduced=reduced, rates=e2e)
    bh.attach_metrics(result, cell, run.trace, e2e, ctx, reduced)
    return result, checks


# ---------------------------------------------------------------------------
# the reference's three steps
# ---------------------------------------------------------------------------

def adamw_reference(params, grads, m, v, count, tcfg, eps=1e-8):
    """One AdamW step as TrainConfig states it: clip the gradient to a
    global norm of grad_clip, linear warm-up over warmup_frac of `steps`
    then linear decay, bias-corrected moments, and decoupled weight decay
    on every leaf of two or more dimensions as stored."""
    import jax
    import jax.numpy as jnp
    leaves = jax.tree_util.tree_leaves(grads)
    norm = jnp.sqrt(sum(jnp.sum(g * g) for g in leaves))
    clip = jnp.minimum(1.0, tcfg.grad_clip / jnp.maximum(norm, 1e-9))
    grads = jax.tree_util.tree_map(lambda g: g * clip, grads)
    warm = max(1, int(tcfg.steps * tcfg.warmup_frac))
    t = float(count)
    lr = (tcfg.peak_lr * t / warm if t < warm else
          tcfg.peak_lr * max(0.0, tcfg.steps - t) / max(1, tcfg.steps - warm))
    b1, b2 = tcfg.b1, tcfg.b2
    n = count + 1
    m = jax.tree_util.tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree_util.tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v,
                               grads)

    def upd(p, a, b):
        u = (a / (1 - b1 ** n)) / (jnp.sqrt(b / (1 - b2 ** n)) + eps)
        if p.ndim >= 2:
            u = u + tcfg.weight_decay * p
        return p - lr * u

    return jax.tree_util.tree_map(upd, params, m, v), grads, m, v


def reference_steps(jax, prog: Program, config, seed, mantissa=None,
                    keep=None):
    """The reference's losses, first clipped gradient's leaf norms, and
    the parameters' change over the checked steps, on the program's first
    rows; `mantissa` rounds every matmul operand (the control), and
    `keep` rows of each batch, the mean taken over them, plants the fault
    of a step that leaves the rest of its batch out."""
    import jax.numpy as jnp
    ref, tcfg = prog.ref, prog.tcfg
    params = bench_check.make_params(jax, ref, prog.shapes, seed)
    b = tcfg.global_batch
    keep = keep or b

    def batch_loss(p, toks):
        return jnp.mean(jnp.stack([ref.loss(config, p, toks[i],
                                            mantissa=mantissa)
                                   for i in range(toks.shape[0])]))

    grad_fn = jax.jit(jax.value_and_grad(batch_loss))
    p = params
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    with jax.default_matmul_precision("highest"):
        for k in range(CHECKED_STEPS):
            loss, g = grad_fn(p, jnp.asarray(prog.rows[k * b:k * b + keep]))
            losses.append(float(loss))
            p, g, m, v = jax.jit(lambda *a, k=k: adamw_reference(
                *a, k, tcfg))(p, g, m, v)
            if k == 0:
                grad = leaf_norms(jax, g)
    delta = leaf_norms(jax, jax.tree_util.tree_map(lambda a, c: a - c, p,
                                                   params))
    return {"loss": losses, "grad": grad, "delta": delta}
