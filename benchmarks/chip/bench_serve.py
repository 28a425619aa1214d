"""Serving cells: the program's ServeEngine driven by the benchmark's own
loop, open (arrivals on a schedule) or closed (slots kept full).

Set-up makes the weights from the seed, builds the engine, and warms every
shape the mix can use: one request of every prompt length it can draw (so
every prefill chunk, the install and the decode tick compile here), and,
in a closed loop, the first wave that fills the slots. Then the window:
nothing compiles in it, or the run fails.

End-to-end, on the host clock:
  ttft_p95_ms          due time -> first token on the host, every request
                       due in the window; one still waiting at the close
                       enters at its wait so far
  itl_p95_ms           the engine's per-token gap record (serve_itl_ms),
                       reset at window start
  output_tokens_per_s  tokens synced to the host in the window / window
"""
from __future__ import annotations

import gc
import time
from dataclasses import dataclass, field

import numpy as np

import bench_check
import bench_harness as bh
import bench_trace
import bench_traffic

SAMPLE_TOKENS, SAMPLE_MAX = 256, 8     # served tokens the check covers


@dataclass
class ServeCtx:
    """What the per-layer readers of a serving cell can read."""
    cell: object
    device: dict
    reduced: object = None               # bench_trace.Reduced (trace runs)
    queue_wait_ms: list = field(default_factory=list)
    chunk_tokens_traced: int = 0         # prompt tokens dispatched while
                                         # the trace ran
    tick_positions: list = field(default_factory=list)  # per traced tick
    counters: dict = field(default_factory=dict)
    rates: dict = field(default_factory=dict)

    @property
    def peaks(self):
        return self.device["peaks"]

    def work(self, name):
        return self.cell.work(name)


class _Events:
    """Tracer callback: stamps each request's first prefill chunk, and the
    prompt tokens dispatched while the profiler runs, on the benchmark's
    clock."""

    def __init__(self):
        self.first_chunk: dict[int, float] = {}
        self.tracing = False
        self.traced_tokens = 0

    def __call__(self, ev):
        if ev[1] != "chunk":
            return
        args = ev[5] or {}
        self.first_chunk.setdefault(args.get("rid"), time.perf_counter())
        if self.tracing:
            self.traced_tokens += int(args["end"]) - int(args["pos"])


class Served:
    """One engine for a cell, with the benchmark's weights for `seed`,
    warmed on every prompt length the mix can draw (two tokens each). The
    engine reports each served token's log-probability, as a serving API
    that returns log-probabilities does (a log-softmax over the vocabulary
    in the tick): the check compares them with the reference's."""

    def __init__(self, cell, jax, seed: int, trace: bool = False,
                 hook=None):
        from repro.models import build_model
        from repro.serve import ServeEngine
        from repro.serve.telemetry import Telemetry
        self.cell, self.jax = cell, jax
        self.mix = cell.traffic
        kw = self.mix["engine"]
        self.cfg = bh.arch_config(cell.config)
        model = build_model(self.cfg)
        self.ref = cell.reference()
        self.shapes = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0))[0])
        self.params = bench_check.make_params(jax, self.ref, self.shapes,
                                              seed)
        self.events = _Events()
        self.eng = ServeEngine(
            model, self.cfg, self.params, slots=kw["slots"],
            max_len=kw["max_len"], prefill_budget=kw.get("prefill_budget"),
            overlap=kw.get("overlap", False), logprobs=True,
            telemetry=Telemetry(trace=trace,
                                on_event=self.events if trace else None))
        if hook is not None:
            hook(self.eng)
        for p in bench_traffic.warm_prompts(self.mix, self.cfg.vocab_size):
            self.eng.submit(p, 2)
        self.drain()

    def drain(self):
        while self.eng.busy:
            self.eng.step()

    def free(self):
        """Drop the engine, its device state and its compiled programs
        (the weights stay). The engine stays alive after `del` and
        `gc.collect()`, so its attributes, and with them its device
        state, are dropped first."""
        eng = self.eng
        del self.eng
        eng.__dict__.clear()
        del eng
        gc.collect()
        self.jax.clear_caches()


@dataclass
class Window:
    """What one measured window saw, on the benchmark's clock."""
    sched: object
    closed: bool
    index: dict = field(default_factory=dict)   # rid -> schedule index
    t_sub: object = None
    finished: list = field(default_factory=list)
    nxt: int = 0
    t0: float = 0.0
    t_close: float = 0.0
    tick_pos: list = field(default_factory=list)
    reduced: object = None

    @property
    def due(self):
        return self.t0 + self.sched.due_s

    @property
    def window_s(self):
        return self.t_close - self.t0


def measure(srv: Served, sched, seconds: float, *, tracer_args=None,
            on_ready=None) -> Window:
    """Fill the first wave (closed loop), then drive the engine for
    `seconds`: submit each request when due (open loop) or when one
    retires (closed loop). `on_ready` runs just before the window opens;
    `tracer_args` (root, chips) profiles the window's end."""
    eng, ev = srv.eng, srv.events
    w = Window(sched, srv.mix["loop"] == "closed",
               t_sub=np.full(len(sched.due_s), np.nan))

    def submit(i):
        w.t_sub[i] = time.perf_counter()
        w.index[eng.submit(sched.prompts[i], int(sched.outputs[i]))] = i

    for i in range(sched.first_wave):
        submit(i)
    while any(r["phase"] != "decode" for r in eng.live_requests()):
        w.finished.extend(eng.step())
    eng.reset_stats()                   # zero the counters; jit caches
    ev.first_chunk.clear()              # steady from here
    if on_ready is not None:
        on_ready()
    w.nxt = sched.first_wave
    w.t0 = time.perf_counter()
    due, end = w.due, w.t0 + seconds
    root, chips = tracer_args or ("", 1)
    tracer = bench_trace.TailTrace(tracer_args is not None, root, w.t0,
                                   seconds, chips)
    while True:
        now = time.perf_counter()
        if now >= end:
            break
        ev.tracing = tracer.poll(now)
        if not w.closed:
            while w.nxt < len(due) and due[w.nxt] <= now:
                submit(w.nxt)
                w.nxt += 1
        if eng.busy:
            if ev.tracing:
                w.tick_pos.append([r["request"].prompt.shape[0]
                                   + len(r["emitted"])
                                   for r in eng.live_requests()
                                   if r["phase"] == "decode"])
            with tracer.span("bench.step"):
                done = eng.step()
            w.finished.extend(done)
            if w.closed:
                for _ in done:
                    if w.nxt < len(due):
                        submit(w.nxt)
                        w.nxt += 1
        else:
            wait = min(end, due[w.nxt] if w.nxt < len(due) else end) - now
            with tracer.span("bench.wait_for_arrival"):
                time.sleep(max(0.0, wait))
    w.t_close = time.perf_counter()
    ev.tracing = False
    w.reduced = tracer.finish()
    return w


def end_to_end(srv: Served, w: Window) -> dict:
    """The window's end-to-end numbers, and what the readers need."""
    eng, reg = srv.eng, srv.eng.telemetry.registry
    n_due = w.nxt if w.closed else int(np.sum(w.sched.due_s
                                              < w.window_s))
    due = w.due
    first = {}
    for o in w.finished:
        i = w.index.get(o.rid)
        if i is not None:
            first[i] = w.t_sub[i] + o.ttft_s
    for r in eng.live_requests():
        i = w.index.get(r["rid"])
        if i is not None and r["emitted"]:
            first[i] = r["request"].submit_time + r["ttft_s"]
    out = {"n_due": n_due,
           "tokens": reg.get("serve_tokens_total").value,
           "firsts": reg.get("serve_ttft_ms").count,
           "ticks": reg.get("serve_decode_ticks_total").value,
           "waiting": sum(r["phase"] in ("queued", "prefill")
                          for r in eng.live_requests()),
           "late_ms": [(w.t_sub[i] - due[i]) * 1e3 for i in range(n_due)
                       if np.isfinite(w.t_sub[i])]}
    out["output_tokens_per_s"] = out["tokens"] / w.window_s
    if not w.closed:
        out["ttft_ms"] = [(first.get(i, w.t_close) - due[i]) * 1e3
                          for i in range(n_due)]
        out["ttft_p95_ms"] = bh.percentile(out["ttft_ms"], 95)
    itl = list(reg.get("serve_itl_ms").window)
    if itl:
        out["itl_p95_ms"] = bh.percentile(itl, 95)
    chunk = srv.events.first_chunk
    out["queue_wait_ms"] = [
        ((chunk[r] if r in chunk else w.t_close) - due[i]) * 1e3
        for r, i in w.index.items() if w.sched.first_wave <= i < n_due]
    return out


def run(cell, run):
    jax, seed, seconds = run.jax, run.seed, run.seconds
    srv = Served(cell, jax, seed, run.trace, run.engine_hook)
    sched = bench_traffic.serve_schedule(srv.mix, seed, seconds,
                                         srv.cfg.vocab_size)
    setup = {}

    def ready():
        setup["s"] = time.perf_counter() - run.t_start
        bh.log(f"set-up {setup['s']:.1f}s, " + run.counter.report()
               + f"; window {seconds}s")

    w = measure(srv, sched, seconds, on_ready=ready,
                tracer_args=(run.root, cell.chips) if run.trace else None)
    win_compiles, win_hits, _ = run.counter.mark()
    retraces = srv.eng.telemetry.watchdog.retraces
    e2e = end_to_end(srv, w)
    e2e["setup_s"] = setup["s"]
    late = ("" if w.closed else "; generator late p95 "
            f"{bh.percentile(e2e['late_ms'] or [0], 95):.2f} ms")
    bh.log(f"window {w.window_s:.2f}s: {e2e['n_due']} due, "
           f"{len(w.finished)} finished, {int(e2e['tokens'])} tokens, "
           f"{int(e2e['ticks'])} ticks, {e2e['waiting']} waiting at the "
           f"close{late}; programs built {win_compiles} (cache loads "
           f"{win_hits}), retraces {retraces}")
    device = {k: v for k, v in run.device.items() if k != "peaks"}
    device["memory_peak_bytes"] = bh.memory_peak(jax, cell.chips)

    # ---- the check, once the program's state is freed ----
    outs = [o for o in w.finished if o.rid in w.index]
    srv.free()
    readings, sample = served_readings(jax, srv.ref, cell.config, srv.params,
                                       sched, w.index, outs, seed,
                                       srv.mix["engine"]["max_len"])
    checks = bh.compare(cell, bench_check.widest(readings))
    checks["window_compiles"] = {"value": win_compiles + retraces,
                                 "limit": 0}
    bh.log(f"check: {len(sample)} requests, "
           f"{sum(len(o.tokens) for o in sample)} served tokens")
    failed = sum(any(r[k] > cell.limits[k] for k in r if k in cell.limits)
                 for r in readings)
    result = {"correct": bh.checks_ok(checks),
              "attempted": e2e["n_due"], "failed": failed, "device": device}
    ctx = ServeCtx(cell=cell, device=run.device, reduced=w.reduced,
                   queue_wait_ms=e2e["queue_wait_ms"],
                   chunk_tokens_traced=srv.events.traced_tokens,
                   tick_positions=w.tick_pos,
                   counters={"tokens": e2e["tokens"],
                             "firsts": e2e["firsts"], "ticks": e2e["ticks"],
                             "slots": srv.mix["engine"]["slots"]},
                   rates=e2e)
    bh.attach_metrics(result, cell, run.trace, e2e, ctx, w.reduced)
    return result, checks


def served_readings(jax, ref, config, params, sched, index, outs, seed,
                    max_len):
    """The numbers `correct` compares, for each sampled finished request."""
    sample = bench_check.pick_sample(outs, seed, SAMPLE_TOKENS, SAMPLE_MAX)
    checker = bench_check.Reference(jax, ref, config, max_len)
    readings = []
    for o in sample:
        prompt = sched.prompts[index[o.rid]]
        want = checker.reference(params, prompt, o.tokens)
        readings.append(bench_check.gaps(o.logprobs, want))
    return readings, sample
