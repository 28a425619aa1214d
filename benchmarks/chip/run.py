"""Run one cell of the benchmark once, on the chips of this machine.

  python3 benchmarks/chip/run.py --workload gpt2s-polysketch.chat \
      --seed 1234567 --seconds 51 --trace 0

The cell (a `workloads` entry of BENCHMARK.json) names a configuration and
a traffic mix; their files, the per-layer metric readers and the limits of
the output check are found by name under this directory (bench_harness).
The run makes its weights and inputs from --seed, warms every shape up,
measures for --seconds, checks the timed path's outputs against the plain
reference, and prints one JSON line last:

  --trace 0   the cell's end-to-end metrics
  --trace 1   its per-layer metrics, read from a profiler trace of the
              window's end and from the engine's own events and counters

It refuses to run without a TPU, with fewer chips than the cell asks for,
or on a device_kind that peaks.json does not list: there is no CPU
fallback. The persistent compile cache lives in the checkout
(`.jax_cache`, or JAX_COMPILATION_CACHE_DIR), so only a checkout's first
run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


@dataclass
class Run:
    """One run's settings, handed to the module that runs its kind of cell."""
    jax: object
    root: str
    seed: int
    seconds: float
    trace: bool
    t_start: float
    device: dict
    counter: object
    engine_hook: object = None     # tests: break the timed path


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell, *, seed, seconds, trace, root=ROOT, t_start=None,
             chip_check=True, engine_hook=None):
    """Set up, measure and check one cell; returns (result, checks)."""
    import jax

    import bench_harness as bh
    device = (bh.require_chips(jax, cell.chips) if chip_check else
              {"platform": jax.devices()[0].platform,
               "kind": jax.devices()[0].device_kind, "count": cell.chips,
               "peaks": bh.read_json(os.path.join(HERE, "peaks.json"))
               ["devices"]["TPU v5 lite"]})
    cache = bh.enable_cache(jax)
    bh.log(f"{cell.name}: {device['kind']} x {device['count']}, seed "
           f"{seed}, compile cache {cache}")
    counter = bh.CompileCounter(jax)
    runner = importlib.import_module(f"bench_{cell.traffic['kind']}")
    run = Run(jax, root, seed, seconds, bool(trace),
              T_START if t_start is None else t_start, device, counter,
              engine_hook)
    # the program's matmuls at the precision the configuration states
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        return runner.run(cell, run)


def main(argv=None):
    args = parse(argv)
    import bench_harness as bh
    cell = bh.load_cell(ROOT, args.workload)
    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise SystemExit(f"no program to measure: {src}/repro is missing")
    sys.path.insert(0, src)
    result, checks = run_cell(cell, seed=args.seed, seconds=args.seconds,
                              trace=args.trace)
    bh.emit(result, checks)


if __name__ == "__main__":
    main()
