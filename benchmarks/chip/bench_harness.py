"""What every cell shares: finding its parts by name, the chip check, the
compile cache and compile counter, and the result line.

A cell is a `workloads` entry of BENCHMARK.json. Its parts are files found
by name under this directory, so a later cell adds files and entries and
edits nothing:

  configs/<config>.json     the configuration as run, with the name of its
                            plain reference ("reference": a module beside it)
  traffic/<traffic>.json    the mix, read by bench_traffic
  metrics/<metric>.py       one reader per per-layer metric: read(ctx)
                            (split names of one quantity, such as
                            idle_share.train, may share <quantity>.py)
  work/<name>.py            operations and bytes from shapes
  limits/<workload>.json    the limit of each number `correct` compares
  peaks.json                published peaks, keyed by device_kind
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def load_module(path: str):
    """Import a file by path under a private module name."""
    name = "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def read_json(path: str):
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict              # configs/<config>.json
    traffic: dict             # traffic/<traffic>.json, bases merged
    end_to_end: list          # BENCHMARK.json entries this cell reports
    per_layer: list
    limits: dict              # limits/<workload>.json
    bench_dir: str

    def part(self, *rel) -> str:
        return os.path.join(self.bench_dir, *rel)

    def reference(self):
        return load_module(self.part("configs",
                                     self.config["reference"] + ".py"))

    def work(self, name: str):
        return load_module(self.part("work", name + ".py"))

    def reader(self, metric: str):
        """metrics/<metric>.py, or, where there is none, the reader that
        the split names of one quantity share: metrics/<name up to its
        first dot>.py (idle_share.py serves idle_share.train)."""
        path = self.part("metrics", metric + ".py")
        if not os.path.exists(path):
            path = self.part("metrics", metric.split(".")[0] + ".py")
        return load_module(path)


def load_traffic(bench_dir: str, name: str) -> dict:
    mix = read_json(os.path.join(bench_dir, "traffic", name + ".json"))
    if "base" in mix:
        merged = load_traffic(bench_dir, mix.pop("base"))
        for k, v in mix.items():
            if isinstance(v, dict) and isinstance(merged.get(k), dict):
                merged[k] = {**merged[k], **v}
            else:
                merged[k] = v
        mix = merged
    return mix


def load_cell(root: str, workload: str, bench_dir: str = HERE) -> Cell:
    bench = read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = read_json(os.path.join(root, cfg_entry["file"]))
    e2e = [m for m in bench["end_to_end"]
           if workload in m.get("workloads", [workload])]
    per_layer = [m for m in bench["per_layer"]
                 if workload in m["workloads"]]
    lim_path = os.path.join(bench_dir, "limits", workload + ".json")
    limits = read_json(lim_path) if os.path.exists(lim_path) else {}
    return Cell(workload, w["chips"], config,
                load_traffic(bench_dir, w["traffic"]), e2e, per_layer,
                limits, bench_dir)


def arch_config(config: dict, **overrides):
    """The program's ArchConfig built from a config file's keys."""
    from repro.configs.base import ArchConfig
    names = {f.name for f in dataclasses.fields(ArchConfig)}
    kw = {k: v for k, v in config.items() if k in names}
    kw.update(overrides)
    return ArchConfig(**kw)


# ---------------------------------------------------------------------------
# the chip
# ---------------------------------------------------------------------------

class NoAccelerator(SystemExit):
    pass


def require_chips(jax, chips: int, bench_dir: str = HERE) -> dict:
    """The device record of the result line; refuses anything but enough
    TPU chips of a kind the peaks table knows."""
    devs = jax.devices()
    kind = devs[0].device_kind
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"no TPU: JAX found {devs[0].platform!r} "
                            f"({kind}); the benchmark measures only a chip")
    if len(devs) < chips:
        raise NoAccelerator(f"cell needs {chips} chips, {len(devs)} visible")
    peaks = read_json(os.path.join(bench_dir, "peaks.json"))["devices"]
    if kind not in peaks:
        raise NoAccelerator(f"device_kind {kind!r} is not in peaks.json")
    return {"platform": devs[0].platform, "kind": kind, "count": chips,
            "peaks": peaks[kind]}


def enable_cache(jax) -> str:
    """The program's fixed compile-cache directory, with every program
    cached however fast it compiled, so a warm run compiles nothing."""
    from repro.launch.compile_cache import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Programs built and persistent-cache hits, from JAX's monitoring
    events (the backend-compile event times a compile and a cache load
    alike, so programs compiled = built - hits). `mark()` starts a new
    count (the window's)."""

    def __init__(self, jax):
        self.compiles = self.hits = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._evt)

    def _dur(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _evt(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def report(self) -> str:
        """Describe the count so far and start a new one."""
        n, hits, secs = self.mark()
        return (f"{n - hits} programs compiled and {hits} loaded from the "
                f"compile cache ({secs:.1f}s)")

    def mark(self) -> tuple[int, int, float]:
        out = (self.compiles, self.hits, self.compile_s)
        self.compiles = self.hits = 0
        self.compile_s = 0.0
        return out


def memory_peak(jax, chips: int) -> int:
    peaks = []
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def percentile(values, q: float) -> float:
    import numpy as np
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# per-layer metrics and the result line
# ---------------------------------------------------------------------------

def read_per_layer(cell: Cell, ctx) -> dict:
    """Each metric's reader; a reader that finds nothing returns None and
    the metric is left out of the line."""
    out = {}
    for m in cell.per_layer:
        v = cell.reader(m["name"]).read(ctx)
        if v is not None:
            if not math.isfinite(v):
                raise ValueError(f"{m['name']} read {v}")
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def attach_metrics(result: dict, cell: Cell, trace: bool, e2e: dict, ctx,
                   reduced) -> dict:
    """The result's metrics: the cell's end-to-end ones from `e2e`, or with
    `trace` its per-layer ones (read from `ctx`) with the device's busy
    time and the trace's breakdown."""
    if not trace:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end if m["name"] in e2e}
        return result
    result["metrics"] = read_per_layer(cell, ctx)
    if reduced is not None:
        result["device"].update(busy_s=reduced.busy_s,
                                window_s=reduced.window_s)
        result["breakdown"] = {"device_ops": reduced.top_ops,
                               "idle_gaps": reduced.idle_gaps}
    return result


def compare(cell: Cell, readings: dict) -> dict:
    """The numbers `correct` compares: those of `readings` that the cell's
    limits file names, each beside its limit. The others are logged."""
    rest = {k: v for k, v in readings.items() if k not in cell.limits}
    if rest:
        log(f"read, not compared: {rest}")
    return {k: {"value": v, "limit": cell.limits[k]}
            for k, v in readings.items() if k in cell.limits}


def checks_ok(checks: dict) -> bool:
    """Every number within its limit, and at least one output compared."""
    return (any(k != "window_compiles" for k in checks)
            and all(c["value"] <= c["limit"] for c in checks.values()))


def emit(result: dict, checks: dict):
    """Print the checks as the last lines of stderr and the result line,
    with the checks under its last key, as the last line of stdout."""
    for name, c in checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    result = dict(result, checks=checks)
    print(json.dumps(result), flush=True)


def log(msg: str):
    print(f"[{time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr, flush=True)
