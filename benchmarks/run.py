"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV lines.

  PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME[,NAME...]]

Serving-path cells (serve/*, prefix_cache/*) are additionally persisted to
BENCH_serve.json so the perf trajectory is machine-readable across PRs.
"""
from __future__ import annotations

import argparse
import json
import sys
import traceback

from benchmarks import common
from repro.launch.compile_cache import enable_compile_cache

MODULES = [
    "sketch_error",        # Theorem 1.1
    "kernel_bench",        # S3.1 lt-mult + linear-vs-quadratic attention
    "latency_vs_context",  # Figure 1 / Table 4
    "serve_throughput",    # continuous batching; decode cost flat in ctx;
                           # tick-vs-roofline gap + telemetry overhead A/B
                           # + sampled-vs-greedy tick cost (serve/decode_*,
                           #   serve/sampling_overhead -> BENCH_serve.json)
    "prefix_cache",        # shared-prompt TTFT: snapshot cache off/cold/warm
    "quality_proxy",       # Figure 2 / Tables 2-3
    "selective_copying",   # Table 5 / Appendix F.1
    "induction_heads",     # Appendix F.2
]

SERVE_PREFIXES = ("serve/", "prefix_cache/")


def write_serve_json(path: str, *, full: bool) -> bool:
    mode = "full" if full else "fast"
    fresh = {r["name"]: {"us_per_call": r["us_per_call"],
                         "derived": r["derived"], "mode": mode}
             for r in common.RESULTS if r["name"].startswith(SERVE_PREFIXES)}
    if not fresh:
        return False
    # merge over any existing record: a filtered --only run refreshes just
    # the cells it produced instead of dropping the rest of the trajectory;
    # mode is stamped per cell so fast and full numbers stay distinguishable
    cells = {}
    try:
        with open(path) as f:
            prior = json.load(f)
        if isinstance(prior, dict) and isinstance(prior.get("cells"), dict):
            cells = prior["cells"]
    except (OSError, ValueError):
        pass
    cells.update(fresh)
    with open(path, "w") as f:
        json.dump({"schema": 1, "cells": cells}, f, indent=1, sort_keys=True)
        f.write("\n")
    return True


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale settings (slow on CPU)")
    ap.add_argument("--only", default="",
                    help="comma-separated substring filters on module names")
    ap.add_argument("--serve-json", default="BENCH_serve.json",
                    help="where to persist serve/prefix-cache cells "
                         "('' disables)")
    args = ap.parse_args()
    enable_compile_cache()
    filters = [f for f in args.only.split(",") if f]
    print("name,us_per_call,derived")
    failed = []
    for name in MODULES:
        if filters and not any(f in name for f in filters):
            continue
        mod = __import__(f"benchmarks.{name}", fromlist=["main"])
        try:
            mod.main(fast=not args.full)
        except Exception as e:  # noqa: BLE001
            failed.append(name)
            print(f"{name},0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc()
    # persist only fully-successful runs: merging a partial run's cells over
    # the committed record would mix numbers from different runs unmarked
    if (not failed and args.serve_json
            and write_serve_json(args.serve_json, full=args.full)):
        print(f"# serve cells -> {args.serve_json}", file=sys.stderr)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
