"""Profiler capture and the reduction from a device trace to numbers.

The reduction works on plain event lists, so the same code reads a
recorded fixture in the tests and the profiler's `.xplane.pb` on the chip:

  device ops   [(start_ns, end_ns, name)]   one TPU plane's "XLA Ops" line
  programs     [(start_ns, end_ns, name)]   its "XLA Modules" line
  host spans   [(start_ns, end_ns, name)]   the benchmark's own
               TraceAnnotation spans on the host plane (names "bench.*")

Busy time is the union of the op intervals, the idle share 1 - busy /
window, and a program's device time the sum of its module events.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_SPAN_PREFIX = "bench."


@dataclass
class Reduced:
    window_s: float
    busy_s: float                       # mean over the device planes
    programs: dict = field(default_factory=dict)   # name -> [calls, s]
    top_ops: list = field(default_factory=list)    # [[name, s]] by time
    idle_gaps: list = field(default_factory=list)  # [[host span, s]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, pattern: str) -> tuple[int, float]:
        """(calls, device seconds) of programs whose name matches."""
        rx = re.compile(pattern)
        calls = secs = 0
        for name, (c, s) in self.programs.items():
            if rx.search(name):
                calls += c
                secs += s
        return calls, secs


def union_ns(intervals) -> tuple[int, list]:
    """Total length of the union of (start, end) intervals, and the gaps
    between the merged intervals as (start, end)."""
    total, gaps = 0, []
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
                gaps.append((cur_e, s))
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total, gaps


def program_name(event_name: str) -> str:
    """'jit_decode_all(123)' -> 'jit_decode_all'."""
    return event_name.split("(", 1)[0].strip()


# control flow whose device event spans the ops of its body: counted in
# the busy union, left out of the list of the ops that took most time
CONTAINERS = re.compile(r"^%?(while|conditional|call)[.\d]* ")


def op_name(hlo: str) -> str:
    """'%copy.96 = f32[16,13]{1,0:T(8,128)} copy(...)' ->
    'copy.96 f32[16,13] copy': name, result shape and opcode."""
    name, _, rest = hlo.partition(" = ")
    shape = re.match(r"[^{ ]*", rest).group(0)
    opcode = re.search(r"\} ?([a-z][\w-]*)\(|^\S+ ([a-z][\w-]*)\(", rest)
    kind = next((g for g in opcode.groups() if g), "") if opcode else ""
    return f"{name.lstrip('%')} {shape} {kind}".strip()[:120]


def reduce(devices: list[dict], host_spans, window_ns: tuple[int, int],
           n_top: int = 10) -> Reduced:
    """devices: one {"ops": [...], "programs": [...]} per device plane."""
    w0, w1 = window_ns
    clip = lambda evs: [(max(s, w0), min(e, w1), n) for s, e, n in evs
                        if e > w0 and s < w1]
    busy, programs, ops_time, all_gaps = [], {}, {}, []
    for dev in devices:
        ops = clip(dev["ops"])
        b, gaps = union_ns((s, e) for s, e, _ in ops)
        if ops:
            first = min(s for s, _, _ in ops)
            last = max(e for _, e, _ in ops)
            gaps = [(w0, first)] + gaps + [(last, w1)]
        else:
            gaps = [(w0, w1)]
        busy.append(b)
        all_gaps.extend(g for g in gaps if g[1] > g[0])
        for s, e, n in ops:
            if not CONTAINERS.match(n):
                n = op_name(n)
                ops_time[n] = ops_time.get(n, 0) + (e - s)
        for s, e, n in clip(dev["programs"]):
            c = programs.setdefault(program_name(n), [0, 0.0])
            c[0] += 1
            c[1] += (e - s) * 1e-9
    spans = sorted(clip(host_spans))
    named = []
    for g0, g1 in sorted(all_gaps, key=lambda g: g[0] - g[1])[:n_top]:
        mid = (g0 + g1) // 2
        # the innermost (latest-starting) benchmark span over the gap
        over = [n for s, e, n in spans if s <= mid < e]
        named.append([over[-1] if over else "outside any benchmark span",
                      (g1 - g0) * 1e-9])
    top = sorted(ops_time.items(), key=lambda kv: -kv[1])[:n_top]
    return Reduced(
        window_s=(w1 - w0) * 1e-9,
        busy_s=(sum(busy) / len(busy) if busy else 0) * 1e-9,
        programs=programs,
        top_ops=[[n, t * 1e-9] for n, t in top],
        idle_gaps=named)


def read_xplane(path: str, n_chips: int):
    """(devices, host_spans) from a profiler `.xplane.pb` file: the first
    `n_chips` TPU planes, and every 'bench.*' span on the host planes."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            dev = {"ops": [], "programs": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "programs"}.get(
                    line.name)
                if key:
                    dev[key] = [(int(e.start_ns), int(e.end_ns), e.name)
                                for e in line.events]
            devices.append((plane.name, dev))
        elif plane.name.startswith("/host"):
            for line in plane.lines:
                host.extend((int(e.start_ns), int(e.end_ns), e.name)
                            for e in line.events
                            if e.name.startswith(HOST_SPAN_PREFIX))
    devices.sort(key=lambda d: int(d[0].rsplit(":", 1)[1]))
    return [d for _, d in devices[:n_chips]], host


class TailTrace:
    """Profiles the end of a measured window: the last `share` of it, at
    most `max_s` seconds (a trace of a whole long window is large to
    read). `poll(now)` starts the profiler once `now` passes the mark and
    says whether it runs; `finish()` stops it and reduces the trace. The
    traced window is the span of a 'bench.window' annotation; the capture
    goes to a fixed directory inside the checkout and is removed once
    read. With `enabled` False every call is a no-op."""

    def __init__(self, enabled: bool, root: str, t0: float, seconds: float,
                 chips: int, share: float = 0.6, max_s: float = 8.0):
        self.enabled, self.chips = enabled, chips
        self.dir = os.path.join(root, ".bench_trace")
        self.mark = t0 + seconds - min(max_s, seconds * share)
        self.on = False
        self._window = None

    def span(self, name: str):
        import contextlib
        import jax
        return (jax.profiler.TraceAnnotation(name) if self.on
                else contextlib.nullcontext())

    def poll(self, now: float) -> bool:
        if self.enabled and self._window is None and now >= self.mark:
            import jax
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self.on = True
            self._window = jax.profiler.TraceAnnotation("bench.window")
            self._window.__enter__()
        return self.on

    def finish(self) -> Reduced | None:
        if self._window is None:
            return None
        import jax
        self._window.__exit__(None, None, None)
        self.on = False
        jax.profiler.stop_trace()
        try:
            (path,) = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            devices, host = read_xplane(path, self.chips)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        win = [(s, e) for s, e, n in host if n == "bench.window"]
        if not win or not devices:
            raise RuntimeError("trace holds no window span or no TPU plane")
        return reduce(devices, host, win[0])
