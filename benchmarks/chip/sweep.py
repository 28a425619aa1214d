"""Find a serving cell's knee: the highest arrival rate whose backlog does
not grow over the window. One engine, one window per rate, drained between
rates:

  python3 benchmarks/chip/sweep.py --workload gpt2s-polysketch.chat \
      --rates 1.5,2,2.5,3 --seconds 40 --seed 5

Per rate, one JSON line: requests due and finished, requests still waiting
for admission at the close, TTFT p50/p95 of the first and second half of
the window (a backlog that grows shows as a second half that waits
longer), ITL p95 and output tokens/s. Not run by the benchmark's own runs;
it refuses to run without a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(ROOT, "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--seed", type=int, default=5)
    args = ap.parse_args(argv)
    import jax

    import bench_harness as bh
    import bench_serve
    import bench_traffic
    cell = bh.load_cell(ROOT, args.workload)
    bh.require_chips(jax, cell.chips)
    bh.enable_cache(jax)
    jax.config.update("jax_default_matmul_precision",
                      cell.config["matmul_precision"])
    srv = bench_serve.Served(cell, jax, args.seed)
    for rate in (float(r) for r in args.rates.split(",")):
        mix = dict(srv.mix, rate_per_s=rate)
        sched = bench_traffic.serve_schedule(mix, args.seed, args.seconds,
                                             srv.cfg.vocab_size)
        w = bench_serve.measure(srv, sched, args.seconds)
        e = bench_serve.end_to_end(srv, w)
        half = sched.due_s[:e["n_due"]] < args.seconds / 2
        ttft = np.asarray(e["ttft_ms"])
        q = lambda x, p: float(np.percentile(x, p)) if len(x) else None
        print(json.dumps({
            "rate": rate, "due": e["n_due"], "finished": len(w.finished),
            "waiting_at_close": e["waiting"],
            "ttft_p50_ms": [q(ttft[half], 50), q(ttft[~half], 50)],
            "ttft_p95_ms": [q(ttft[half], 95), q(ttft[~half], 95)],
            "itl_p95_ms": e.get("itl_p95_ms"),
            "output_tokens_per_s": e["output_tokens_per_s"],
            "ticks_per_s": e["ticks"] / w.window_s}), flush=True)
        srv.drain()


if __name__ == "__main__":
    main()
