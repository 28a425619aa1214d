"""The readings the limits of `correct` are set from, for one cell, on
many seeds in one process (set-up is paid per seed, compiles once):

  python3 benchmarks/chip/readings.py --workload gpt2s-polysketch.chat \
      --seeds 101,102,103 --seconds 20

Per seed, one JSON line: the program's reading of each number `correct`
compares, and the control's (the plain reference one precision down, in
the program's place). Serving cells run a short window at the cell's own
load and read both on the same sampled requests; training cells read the
first steps, and the fault of a step that leaves half of its batch out,
planted in the reference (a step that returns its state unchanged reads 1
on the gradient and update gaps, and needs no run). The last line gives the lower
reading (the largest the program gave) and the upper (the smallest the
control gave) of each.
Not run by the benchmark's own runs; it refuses to run without a TPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def serve_readings(cell, jax, seed, seconds):
    import bench_check
    import bench_serve
    import bench_traffic
    srv = bench_serve.Served(cell, jax, seed)
    sched = bench_traffic.serve_schedule(srv.mix, seed, seconds,
                                         srv.cfg.vocab_size)
    w = bench_serve.measure(srv, sched, seconds)
    outs = [o for o in w.finished if o.rid in w.index]
    srv.free()
    sample = bench_check.pick_sample(outs, seed, bench_serve.SAMPLE_TOKENS,
                                     bench_serve.SAMPLE_MAX)
    chk = bench_check.Reference(jax, srv.ref, cell.config,
                                srv.mix["engine"]["max_len"])
    prog, ctrl = [], []
    for o in sample:
        prompt = sched.prompts[w.index[o.rid]]
        want = chk.reference(srv.params, prompt, o.tokens)
        prog.append(bench_check.gaps(o.logprobs, want))
        ctrl.append(bench_check.control_gaps(
            chk.control(srv.params, prompt, o.tokens), want))
    return (bench_check.widest(prog), bench_check.widest(ctrl),
            {"requests": len(sample),
             "tokens": sum(len(o.tokens) for o in sample)})


def train_readings(cell, jax, seed):
    import bench_check
    import bench_train
    prog = bench_train.program_steps(cell, jax, seed)
    got = {"loss": prog.losses, "grad": prog.grad, "delta": prog.delta}
    prog.state = prog.step = None
    gc.collect()
    want = bench_train.reference_steps(jax, prog, cell.config, seed)
    ctrl = bench_train.reference_steps(
        jax, prog, cell.config, seed, mantissa=bench_check.CONTROL_MANTISSA)
    b = prog.tcfg.global_batch
    half = bench_train.reference_steps(jax, prog, cell.config, seed,
                                       keep=b - b // 2)
    p, c = bench_train.compare(got, want), bench_train.compare(ctrl, want)
    h = bench_train.compare(half, want)
    keys = ("train_loss_gap", "train_grad_gap", "train_update_gap")
    return ({k: p[k] for k in keys}, {k: c[k] for k in keys},
            {"half_batch": {k: h[k] for k in keys},
             "program_leaves": [p["grad_leaf"], p["update_leaf"]],
             "control_leaves": [c["grad_leaf"], c["update_leaf"]],
             "left_out": p["left_out"]})


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    import jax

    import bench_harness as bh
    cell = bh.load_cell(ROOT, args.workload)
    bh.require_chips(jax, cell.chips)
    bh.enable_cache(jax)
    lower, upper = {}, {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        with jax.default_matmul_precision(cell.config["matmul_precision"]):
            if cell.traffic["kind"] == "train":
                prog, ctrl, info = train_readings(cell, jax, seed)
            else:
                prog, ctrl, info = serve_readings(cell, jax, seed,
                                                  args.seconds)
        for k, v in prog.items():
            lower[k] = max(lower.get(k, 0.0), v)
            upper[k] = min(upper.get(k, float("inf")), ctrl[k])
        print(json.dumps({"seed": seed, "program": prog, "control": ctrl,
                          **info, "s": time.perf_counter() - t}), flush=True)
        gc.collect()
    print(json.dumps({"lower": lower, "upper": upper}), flush=True)


if __name__ == "__main__":
    main()
