"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch gpt2s-polysketch \
      --smoke --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/run1

Wires together: config registry, model zoo, synthetic data pipeline, AdamW +
schedule, sharded train step (pjit over whatever mesh `--mesh` names),
checkpoint manager (atomic/async/keep-k/auto-resume), preemption guard and
straggler detector. On a real pod, run the same module once per host after
jax.distributed.initialize(); everything here is SPMD-safe.
"""
from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import TrainConfig, get_config
from repro.data import DataIterator, make_markov_lm
from repro.distributed.fault import PreemptionGuard, StragglerDetector
from repro.distributed.sharding import batch_shardings, shardings_for
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train import init_train_state, make_train_step

log = logging.getLogger("repro.train")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2s-polysketch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--mesh", default="",
                    help='e.g. "2x4:data,model" (default: single-device)')
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    enable_compile_cache()

    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    overrides = {}
    for kv in args.set:
        k, _, v = kv.partition("=")
        overrides[k] = type(getattr(get_config(args.arch, smoke=args.smoke), k))(v) \
            if hasattr(get_config(args.arch, smoke=args.smoke), k) else v
    cfg = get_config(args.arch, smoke=args.smoke, **overrides)
    model = build_model(cfg)
    tcfg = TrainConfig(seq_len=args.seq, global_batch=args.batch,
                       steps=args.steps, peak_lr=args.lr,
                       microbatches=args.microbatches, seed=args.seed)

    key = jax.random.PRNGKey(args.seed)
    params, axes = model.init(key)
    state = init_train_state(params)
    step_fn = make_train_step(model, cfg, tcfg)

    if args.mesh:
        shape_s, _, axes_s = args.mesh.partition(":")
        mesh = make_mesh([int(x) for x in shape_s.split("x")],
                         axes_s.split(","))
        params_sh = shardings_for(axes, params, mesh)
        state = jax.device_put(state, _state_shardings(state, params_sh, mesh))
        step_fn = jax.jit(step_fn)
    else:
        step_fn = jax.jit(step_fn)

    it = DataIterator(make_markov_lm(cfg.vocab_size, seed=args.seed + 1),
                      args.batch, args.seq, seed=args.seed)

    ckpt = None
    start_step = 0
    if args.ckpt_dir:
        ckpt = CheckpointManager(args.ckpt_dir, keep=3)
        latest, restored, extras = ckpt.restore_latest(state)
        if latest is not None:
            state, start_step = restored, latest
            it.restore(extras["data"])
            log.info("resumed from step %d", start_step)

    guard = PreemptionGuard().install()
    straggler = StragglerDetector()
    t_start = time.time()
    for i in range(start_step, args.steps):
        batch = next(it)
        straggler.start()
        state, metrics = step_fn(state, batch)
        loss = float(metrics["loss"])  # waits for the step
        straggler.stop()
        if not np.isfinite(loss):
            raise SystemExit(f"step {i + 1}: loss is {loss}")
        if (i + 1) % args.log_every == 0 or i == start_step:
            log.info("step %d loss %.4f lr %.2e grad_norm %.3f",
                     i + 1, loss, float(metrics["lr"]),
                     float(metrics["grad_norm"]))
        save_now = ckpt and args.ckpt_every and (i + 1) % args.ckpt_every == 0
        if save_now or (ckpt and guard.preempted):
            ckpt.save(i + 1, state, extras={"data": it.state()})
        if guard.preempted:
            log.warning("preempted: checkpoint written at step %d", i + 1)
            break
    if ckpt:
        ckpt.save(args.steps, state, extras={"data": it.state()}, block=True)
        ckpt.wait()
    dt = time.time() - t_start
    n = args.steps - start_step
    log.info("done: %d steps, %.2f s/step, %d flagged stragglers",
             n, dt / max(n, 1), len(straggler.flagged))
    return state


def _state_shardings(state, params_sh, mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.optim.adamw import AdamWState
    from repro.train.step import TrainState
    rep = NamedSharding(mesh, P())
    return TrainState(
        params=params_sh,
        opt=AdamWState(m=params_sh, v=params_sh, count=rep),
        step=rep)


if __name__ == "__main__":
    main()
