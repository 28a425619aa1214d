"""Serving launcher: continuous batching with the O(1)-state polysketch
cache under a simulated Poisson arrival process.

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2s-polysketch \
      --smoke --requests 8 --slots 4 --prompt-len 64 --gen 32 --rate 4

Sampled workload (per-request temperature / top-k / top-p; with
--seed-per-request every request draws an independent, reproducible
stream seeded seed+rid):

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2s-polysketch \
      --smoke --requests 8 --temperature 0.8 --top-k 40 --seed-per-request

Shared-system-prompt workload (every request shares an N-token prefix and
diverges after it) with the prefix-reuse snapshot cache:

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2s-polysketch \
      --smoke --requests 8 --prompt-len 96 --shared-prefix 64 \
      --prefix-cache-mb 8

Overlapped chunked admission (long prompts prefill incrementally across
decode ticks instead of stalling them; the stall gate fails the run if the
decode tick-gap tail blows out):

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2s-polysketch \
      --smoke --requests 8 --prompt-len 512 --gen 32 --rate 4 \
      --overlap --prefill-budget 64 --max-tick-gap-ratio 4

Observability (serve/telemetry.py): --trace-out writes a schema-validated
Chrome/Perfetto trace of the run (tick phase spans + per-slot request
timelines), --metrics-out writes the Prometheus text exposition of the
engine's metrics registry, --log-events streams every event as recorded.
--warm compiles all traces up front and arms the retrace watchdog;
--expect-no-retraces then turns any mid-serve recompile into a nonzero
exit (the CI gate):

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2s-polysketch \
      --smoke --requests 8 --overlap --prefill-budget 64 --warm \
      --trace-out /tmp/serve-trace.json --metrics-out /tmp/serve.prom \
      --expect-no-retraces

Replicated serving with chaos injection (serve/replicas.py +
serve/chaos.py): N engines behind one coordinator, block-boundary
checkpoints into the shared prefix cache, and bit-exact failover — kill a
replica mid-run and the survivors re-emit exactly the tokens the
fault-free run would have (the CI chaos gate diffs --tokens-out files):

  PYTHONPATH=src python -m repro.launch.serve --arch gpt2s-polysketch \
      --smoke --requests 8 --replicas 2 --chaos kill@6 --shed-above 8 \
      --prefix-cache-mb 8 --logprobs --tokens-out /tmp/chaos.json

SIGTERM at any point triggers a graceful drain: admissions stop, live
decode states checkpoint to the disk tier (--prefix-cache-dir), traces
and metrics flush, and the process exits 0.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.distributed.fault import PreemptionGuard
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.models import build_model
from repro.serve import (ChaosInjector, Overloaded, PrefixCache,
                         ReplicaSet, SamplingParams, ServeEngine,
                         ServePlan, Telemetry, format_event, generate,
                         validate_trace)


def _percentile(xs, p):
    return float(np.percentile(np.asarray(xs), p)) if xs else 0.0


def simulate(engine: ServeEngine, arrivals, *, quiet=False, guard=None):
    """Drive the engine under timed arrivals.

    arrivals: list of (arrival_s, prompt, max_new_tokens, eos_id, sampling)
    sorted by arrival time. Requests are submitted when the wall clock
    passes their arrival offset and admitted at the next scheduler tick —
    live slots are never re-prefilled or reset by an admission (the
    continuous-batching point). In lockstep mode each tick's decode waits
    for that tick's prefill chunks; with the engine's overlap mode the
    two are pipelined and decode cadence stays flat through admissions.

    `engine` may also be a ReplicaSet (same submit/step/busy surface):
    a shed submission (Overloaded) is requeued shortly later — client
    backoff — so every request is eventually served. With a
    `PreemptionGuard`, SIGTERM stops admissions and exits the loop (the
    caller then drains and flushes).
    """
    pending = list(arrivals)
    outs = []
    t0 = time.perf_counter()
    while pending or engine.busy:
        if guard is not None and guard.preempted:
            pending.clear()  # admissions stop; caller drains and exits 0
            break
        now = time.perf_counter() - t0
        while pending and pending[0][0] <= now:
            item = pending[0]
            _, prompt, gen, eos, sampling = item
            try:
                engine.submit(prompt, gen, eos, sampling=sampling)
            except Overloaded:
                # load shed: back off and retry this arrival shortly
                pending[0] = (now + 0.05,) + tuple(item[1:])
                pending.sort(key=lambda x: x[0])
                break
            pending.pop(0)
        if engine.busy:
            for out in engine.step():
                outs.append(out)
                if not quiet:
                    print(f"  req{out.rid}: len={out.prompt_len} "
                          f"+{len(out.tokens)} tok ({out.finish_reason}) "
                          f"ttft={out.ttft_s * 1e3:.0f}ms "
                          f"latency={out.latency_s * 1e3:.0f}ms")
        elif pending:
            time.sleep(min(0.005, max(0.0, pending[0][0] - now)))
    return outs, time.perf_counter() - t0


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gpt2s-polysketch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="mean request arrivals per second (Poisson); "
                         "0 = all requests queued at t=0")
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop generation at this token id (-1 = never)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = off)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling threshold (1.0 = off)")
    ap.add_argument("--seed-per-request", action="store_true",
                    help="request i samples with seed --seed+i (independent "
                         "reproducible streams); default: all share --seed")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="tokens of a shared prompt prefix across ALL "
                         "requests (system-prompt workload); 0 = "
                         "independent random prompts")
    ap.add_argument("--prefix-cache-mb", type=float, default=0.0,
                    help="prefix-reuse snapshot cache byte budget in MiB "
                         "(0 = cache off)")
    ap.add_argument("--prefix-cache-dir", default=None,
                    help="persist prefix-cache snapshots under this "
                         "directory (survives restarts; shareable)")
    ap.add_argument("--min-snapshot-blocks", type=int, default=1,
                    help="prefix-cache admission floor: only snapshot "
                         "prefixes of at least this many blocks")
    ap.add_argument("--expect-disk-hits", action="store_true",
                    help="exit nonzero unless at least one snapshot was "
                         "loaded from --prefix-cache-dir (restart smoke)")
    ap.add_argument("--block-size", type=int, default=0,
                    help="override cfg.lt_block_size (the snapshot / "
                         "resumed-prefill grid); 0 = config default")
    ap.add_argument("--overlap", action="store_true",
                    help="pipeline admission prefill with the decode tick "
                         "(async dispatch, tokens synced one tick late; "
                         "emitted tokens are bit-identical to lockstep)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="max admission-prefill tokens dispatched per "
                         "decode tick (0 = unlimited); bounds the decode "
                         "stall a long prompt can cause")
    ap.add_argument("--max-tick-gap-ratio", type=float, default=0.0,
                    help="exit nonzero if p95(decode tick gap) exceeds "
                         "this multiple of the median gap (0 = no gate); "
                         "the CI stall gate for --overlap runs")
    ap.add_argument("--logprobs", action="store_true",
                    help="report per-token logprobs of the sampled tokens "
                         "(computed inside the jitted decode tick)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome/Perfetto trace.json of the run "
                         "(tick phases + per-slot request timelines; open "
                         "at ui.perfetto.dev); the trace is schema-"
                         "validated before writing")
    ap.add_argument("--metrics-out", default=None,
                    help="write the engine's metrics registry as "
                         "Prometheus text exposition to this path")
    ap.add_argument("--log-events", action="store_true",
                    help="print every telemetry event as it is recorded "
                         "(implies tracing; very verbose)")
    ap.add_argument("--warm", action="store_true",
                    help="run one warm-up request per prompt-length bucket "
                         "(plus a few decode ticks) and reset stats before "
                         "the timed workload: compiles land up front and "
                         "the retrace watchdog arms")
    ap.add_argument("--expect-no-retraces", action="store_true",
                    help="exit nonzero if any jitted entry point "
                         "recompiled mid-serve (requires --warm so the "
                         "watchdog has a steady baseline)")
    ap.add_argument("--mesh", default="1x1", metavar="DxM",
                    help="serving mesh as data x model device counts "
                         "(e.g. 4x2); needs d*m visible devices — on CPU "
                         "set XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=<d*m>. Default 1x1 (single device)")
    ap.add_argument("--shard-model", action="store_true",
                    help="tensor-parallel params over the mesh's 'model' "
                         "axis (heads/ffn/vocab output dims via spec_for); "
                         "off = params replicated on every device")
    ap.add_argument("--replicas", type=int, default=0,
                    help="run N replicated engines behind one coordinator "
                         "with checkpointed bit-exact failover (0 = the "
                         "single-engine path); each replica gets its own "
                         "mesh slice when enough devices are visible")
    ap.add_argument("--chaos", default="none", metavar="SPEC",
                    help="fault-injection schedule for --replicas, e.g. "
                         "kill@12, hang@8:r1:s0.6, slow-tick@5:x8, "
                         "drop-snapshot@0, disk-flake@0:x2; comma-joined; "
                         "'none' disables (see serve/chaos.py)")
    ap.add_argument("--shed-above", type=int, default=0,
                    help="load-shedding gate: refuse submissions past this "
                         "many outstanding requests PER LIVE REPLICA "
                         "(0 = off); shed arrivals are retried with "
                         "backoff, so every request is still served")
    ap.add_argument("--hang-timeout", type=float, default=0.0,
                    help="declare a replica dead when one tick exceeds "
                         "this many seconds (0 = off); its tick is "
                         "discarded atomically and its requests fail over")
    ap.add_argument("--checkpoint-blocks", type=int, default=1,
                    help="checkpoint live slots every N state blocks "
                         "(failover restore depth granularity)")
    ap.add_argument("--tokens-out", default=None,
                    help="write every request's emitted tokens (and "
                         "logprobs with --logprobs) as JSON keyed by rid; "
                         "the CI mesh bit-parity gate diffs these files")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()
    if args.expect_no_retraces and not args.warm:
        raise SystemExit("--expect-no-retraces requires --warm (without a "
                         "warm-up pass every compile is expected, so the "
                         "gate would be vacuous)")

    replica_mode = args.replicas > 0
    if args.chaos not in ("", "none") and not replica_mode:
        raise SystemExit("--chaos needs --replicas (faults are injected "
                         "per replica)")
    plan = None
    if replica_mode:
        if args.mesh != "1x1" or args.shard_model:
            raise SystemExit("--replicas builds one mesh slice per replica "
                             "itself; drop --mesh/--shard-model")
    else:
        try:
            mesh_d, mesh_m = (int(x) for x in args.mesh.lower().split("x"))
        except ValueError:
            raise SystemExit(f"--mesh wants DxM (e.g. 4x2), got {args.mesh!r}")
        mesh = make_serving_mesh(mesh_d * mesh_m, model_parallel=mesh_m)
        plan = ServePlan.from_mesh(mesh, shard_model=args.shard_model)
        print(f"mesh: {plan.describe()} ({plan.n_devices} devices, "
              f"params {'sharded' if args.shard_model else 'replicated'})")

    overrides = {"lt_block_size": args.block_size} if args.block_size else {}
    cfg = get_config(args.arch, smoke=args.smoke, **overrides)
    model = build_model(cfg)
    key = jax.random.PRNGKey(args.seed)
    params, param_axes = model.init(key)

    prefix_cache = (PrefixCache(int(args.prefix_cache_mb * 2 ** 20),
                                save_dir=args.prefix_cache_dir)
                    if args.prefix_cache_mb > 0 else None)
    if args.expect_disk_hits and (prefix_cache is None
                                  or args.prefix_cache_dir is None):
        raise SystemExit("--expect-disk-hits needs --prefix-cache-mb and "
                         "--prefix-cache-dir")
    trace_on = bool(args.trace_out or args.log_events)
    telemetry = Telemetry(
        trace=trace_on,
        memory=bool(trace_on or args.metrics_out),
        on_event=(lambda ev: print(format_event(ev))) if args.log_events
        else None)
    if replica_mode:
        chaos = (ChaosInjector(args.chaos, seed=args.seed)
                 if args.chaos not in ("", "none") else None)
        engine = ReplicaSet(model, cfg, params, n_replicas=args.replicas,
                            slots=args.slots,
                            max_len=args.prompt_len + args.gen,
                            prefix_cache=prefix_cache,
                            min_snapshot_blocks=args.min_snapshot_blocks,
                            logprobs=args.logprobs,
                            prefill_budget=args.prefill_budget or None,
                            overlap=args.overlap,
                            checkpoint_blocks=args.checkpoint_blocks,
                            hang_timeout_s=args.hang_timeout or None,
                            shed_above=args.shed_above or None,
                            chaos=chaos, telemetry=telemetry)
        armed = ", ".join(s.describe() for s in chaos.armed) if chaos else "none"
        print(f"replicas: {args.replicas} x {args.slots} slots "
              f"(checkpoint every {args.checkpoint_blocks} block(s), "
              f"shed_above={args.shed_above or 'off'}, "
              f"hang_timeout={args.hang_timeout or 'off'}, chaos: {armed})")
    else:
        engine = ServeEngine(model, cfg, params, slots=args.slots,
                             max_len=args.prompt_len + args.gen,
                             prefix_cache=prefix_cache,
                             min_snapshot_blocks=args.min_snapshot_blocks,
                             logprobs=args.logprobs,
                             prefill_budget=args.prefill_budget or None,
                             overlap=args.overlap,
                             telemetry=telemetry,
                             plan=plan, param_axes=param_axes)
    rng = np.random.default_rng(args.seed)

    eos = None if args.eos_id < 0 else args.eos_id
    if args.shared_prefix:
        if not 0 < args.shared_prefix < args.prompt_len:
            raise SystemExit("--shared-prefix must be in (0, prompt_len)")
        shared = rng.integers(0, cfg.vocab_size, size=args.shared_prefix)
        suffix_len = args.prompt_len - args.shared_prefix
        def make_prompt():
            # host np.int32 on purpose: submit keeps prompts host-resident
            # and the scheduler does one h2d per chunk — a device array
            # here would round-trip back through the host at admission
            sfx = rng.integers(0, cfg.vocab_size, size=suffix_len)
            return np.concatenate([shared, sfx]).astype(np.int32)
    else:
        # A few fixed prompt-length buckets (not a continuum) keeps the
        # per-length prefill retrace count bounded while still exercising
        # mixed-length admission.
        buckets = sorted({max(1, args.prompt_len // 2),
                          max(1, 3 * args.prompt_len // 4), args.prompt_len})
        def make_prompt():
            plen = int(rng.choice(buckets))
            return rng.integers(0, cfg.vocab_size,
                                size=plen).astype(np.int32)
    sampled = args.temperature > 0
    if not sampled and (args.top_k != 0 or args.top_p != 1.0
                        or args.seed_per_request):
        # SamplingParams(temperature=0) is greedy and would silently drop
        # the filters the user asked for
        raise SystemExit("--top-k/--top-p/--seed-per-request require "
                         "--temperature > 0 (temperature 0 is greedy)")
    def make_sampling(rid):
        return SamplingParams(
            temperature=args.temperature, top_k=args.top_k, top_p=args.top_p,
            seed=args.seed + rid if args.seed_per_request else args.seed)

    if args.warm:
        # One request per prompt-length bucket compiles every prefill /
        # chunk / install / decode trace the workload will need (chunk
        # splits are deterministic per (length, budget)); the stats reset
        # afterwards also calls the watchdog's mark_steady(), so any jit
        # cache growth during the timed run below counts as a mid-serve
        # retrace. Warm prompts come from an independent stream: the
        # workload's prompt sequence is identical with and without --warm.
        wrng = np.random.default_rng(args.seed + 104729)

        def warm_prompts():
            if args.shared_prefix:
                # three prompts sharing a warm-up prefix of their own: the
                # third hits the prefix cache, so the restore path compiles
                # here and not on the workload's first hit
                wshared = wrng.integers(0, cfg.vocab_size,
                                        size=args.shared_prefix)
                return [np.concatenate([wshared, wrng.integers(
                    0, cfg.vocab_size,
                    size=args.prompt_len - args.shared_prefix)]
                ).astype(np.int32) for _ in range(3)]
            return [wrng.integers(0, cfg.vocab_size,
                                  size=plen).astype(np.int32)
                    for plen in sorted({max(1, args.prompt_len // 2),
                                        max(1, 3 * args.prompt_len // 4),
                                        args.prompt_len})]

        # every replica compiles its own traces (engines do not share jit
        # caches), so each one warms directly — the coordinator's chaos
        # tick counter never advances during warm-up
        for eng in (engine.engines if replica_mode else [engine]):
            prompts = warm_prompts()
            for prompt in prompts:
                eng.submit(prompt, min(4, args.gen), None)
            eng.run()
        engine.reset_stats()
        print(f"warm-up: {len(prompts)} requests (lengths "
              f"{sorted({len(p) for p in prompts})}), watchdog armed")

    t = 0.0
    arrivals = []
    for rid in range(args.requests):
        if args.rate > 0:
            t += float(rng.exponential(1.0 / args.rate))
        arrivals.append((t, make_prompt(), args.gen, eos, make_sampling(rid)))

    def flush_observability():
        if args.trace_out:
            trace = telemetry.export_trace()
            errs = validate_trace(trace)
            if errs:
                raise SystemExit("trace schema violations:\n  "
                                 + "\n  ".join(errs[:10]))
            with open(args.trace_out, "w") as f:
                json.dump(trace, f)
            print(f"trace: {len(trace['traceEvents'])} events -> "
                  f"{args.trace_out} (schema valid; open at "
                  "ui.perfetto.dev)")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                f.write(telemetry.render_prometheus())
            print(f"metrics: {len(telemetry.registry.names())} series -> "
                  f"{args.metrics_out}")

    guard = PreemptionGuard().install()
    # flushed on purpose: "serving" is the SIGTERM-safe sentinel — from
    # here on a SIGTERM is caught by the guard and drains cleanly
    # (the subprocess drain test keys on it through a pipe)
    print(f"serving: {args.requests} requests, rate={args.rate}/s",
          flush=True)
    outs, wall = simulate(engine, arrivals, guard=guard)
    guard.uninstall()
    if guard.preempted:
        # graceful drain: admissions already stopped inside simulate();
        # persist every live slot's decode state to the disk tier, flush
        # observability, and exit 0 — the orchestrator's SIGTERM contract
        paths = engine.drain_checkpoints()
        print(f"SIGTERM: drained — {len(outs)} requests served, "
              f"{len(paths)} checkpoint file(s) persisted; exiting cleanly")
        flush_observability()
        return outs
    stats = engine.stats()
    ttfts = [o.ttft_s for o in outs]
    lats = [o.latency_s for o in outs]
    if replica_mode:
        gen_tokens = sum(len(o.tokens) for o in outs)
        engs = stats["engines"]
        n_requests = stats["requests"]
        n_sampled = sum(e["sampled_requests"] for e in engs.values())
        decode_tok_s = sum(e["decode_tok_per_s"] for e in engs.values())
        print(f"served {n_requests} requests, {gen_tokens} tokens "
              f"in {wall:.2f}s ({gen_tokens / wall:.1f} tok/s wall, "
              f"{decode_tok_s:.1f} tok/s decode across {stats['alive']}"
              f"/{stats['replicas']} live replicas)")
        print(f"fleet: deaths={stats['deaths']} "
              f"failovers={stats['failovers']} "
              f"checkpoints={stats['checkpoints']}"
              f"(+{stats['checkpoints_dropped']} chaos-dropped) "
              f"shed={stats['shed']} "
              f"recovered={stats['recovered_installs']} "
              f"straggler_flags={stats['straggler_flags']}"
              + "".join(f"\n  death: {d}" for d in stats["death_log"]))
        # the no-lost-requests gate: across deaths, failovers and
        # shedding, every arrival is served exactly once
        rids = [o.rid for o in outs]
        if (len(outs) != args.requests or len(set(rids)) != len(rids)
                or stats["duplicate_outputs"]):
            raise SystemExit(
                f"lost/duplicated requests: served {len(outs)} of "
                f"{args.requests} (duplicate outputs: "
                f"{stats['duplicate_outputs']})")
    else:
        n_requests = stats["requests"]
        n_sampled = stats["sampled_requests"]
        print(f"served {stats['requests']} requests, "
              f"{stats['generated_tokens']} tokens in {wall:.2f}s "
              f"({stats['generated_tokens'] / wall:.1f} tok/s wall, "
              f"{stats['decode_tok_per_s']:.1f} tok/s decode)")
    print(f"ttft    p50={_percentile(ttfts, 50) * 1e3:.0f}ms "
          f"p95={_percentile(ttfts, 95) * 1e3:.0f}ms")
    print(f"latency p50={_percentile(lats, 50) * 1e3:.0f}ms "
          f"p95={_percentile(lats, 95) * 1e3:.0f}ms")
    gap_stats = ([(f"replica{i}", e["tick_gap_ms"])
                  for i, e in stats["engines"].items()] if replica_mode
                 else [("engine", stats["tick_gap_ms"])])
    if not replica_mode:
        itl, gap = stats["itl_ms"], stats["tick_gap_ms"]
        print(f"itl     p50={itl['p50']:.1f}ms p95={itl['p95']:.1f}ms "
              f"p99={itl['p99']:.1f}ms")
        print(f"tick gap median={gap['median']:.1f}ms p95={gap['p95']:.1f}ms "
              f"max={gap['max']:.1f}ms | scheduler: "
              f"{stats['scheduler']['chunks']} chunks, "
              f"{stats['scheduler']['coalesced']} coalesced, "
              f"{stats['scheduler']['promote_splits']} promote splits")
    if args.max_tick_gap_ratio > 0:
        # stall gate: a synchronous admission prefill stalls whole decode
        # ticks, pushing the gap tail far above the median; the overlapped
        # scheduler must keep the tail tight. p95-vs-median is robust to
        # the isolated scheduler-noise spikes CI machines produce (a
        # lockstep engine admitting long prompts fails this by ~an order
        # of magnitude, which is the regression this gate exists to catch).
        # In replica mode the gate applies to every surviving replica.
        for who, gap in gap_stats:
            if gap["median"] > 0 and gap["p95"] > args.max_tick_gap_ratio * gap["median"]:
                raise SystemExit(
                    f"decode stalled ({who}): tick-gap p95 "
                    f"{gap['p95']:.1f}ms > {args.max_tick_gap_ratio:.1f}x "
                    f"median {gap['median']:.1f}ms")
    if sampled:
        seed_desc = (f"{args.seed}+rid" if args.seed_per_request
                     else str(args.seed))
        print(f"sampling: temperature={args.temperature} top_k={args.top_k} "
              f"top_p={args.top_p} seed={seed_desc} "
              f"({n_sampled}/{n_requests} requests sampled)")
        # smoke gate: every served output must be non-empty and in-range,
        # and a short probe generation must not produce NaN/Inf logits
        # (a spot check — the engine doesn't retain per-step logits)
        bad = [o.rid for o in outs
               if len(o.tokens) == 0
               or np.any(np.asarray(o.tokens) < 0)
               or np.any(np.asarray(o.tokens) >= cfg.vocab_size)]
        if bad:
            raise SystemExit(f"sampled run produced empty/out-of-range "
                             f"outputs for requests {bad}")
        probe = generate(model, cfg, params, arrivals[0][1][None], 2,
                         sampling=make_sampling(0))
        if not np.all(np.isfinite(np.asarray(probe.logits_last))):
            raise SystemExit("sampled run hit NaN/Inf logits")
    if args.logprobs:
        lps = np.concatenate([o.logprobs for o in outs if o.logprobs is not None])
        print(f"logprobs: mean={lps.mean():.3f} min={lps.min():.3f} "
              f"({lps.size} tokens)")
        if not (np.all(np.isfinite(lps)) and np.all(lps <= 0.0)):
            raise SystemExit("logprobs outside (-inf, 0] — sampler/model "
                             "distribution mismatch")
    if prefix_cache is not None:
        pc = prefix_cache.stats()
        print(f"prefix cache: {pc['hits']}/{pc['lookups']} hits, "
              f"{pc['hit_tokens']} prompt tokens restored, "
              f"{pc['entries']} entries / {pc['bytes'] / 2**20:.2f} MiB "
              f"({pc['evictions']} evictions, {pc['disk_loads']} disk "
              f"loads, {pc['disk_writes']} disk writes)")
        if (args.shared_prefix >= cfg.lt_block_size and args.requests >= 3
                and pc["hits"] == 0):
            # requests 3+ of a shared-prefix workload must hit (req 2
            # promotes the shared boundary) — a zero here is a regression
            raise SystemExit("prefix cache: expected hits in shared-prefix "
                             "workload, got none")
        if args.expect_disk_hits and pc["disk_loads"] == 0:
            raise SystemExit("prefix cache: expected disk loads from "
                             f"{args.prefix_cache_dir}, got none")
    if args.tokens_out:
        # float(np.float32) goes through float64, and JSON round-trips
        # float64 exactly — so diffing two tokens-out files is a BIT
        # comparison of tokens and logprobs (the mesh-parity CI gate)
        payload = {
            str(o.rid): {
                "tokens": [int(t) for t in o.tokens],
                "prompt_len": o.prompt_len,
                "finish_reason": o.finish_reason,
                **({"logprobs": [float(x) for x in o.logprobs]}
                   if o.logprobs is not None else {}),
            } for o in outs}
        with open(args.tokens_out, "w") as f:
            # the "mesh" key names the placement; tokens must not depend
            # on it (the CI parity gates strip it before diffing)
            mesh_desc = (f"replicas={args.replicas}" if replica_mode
                         else plan.describe())
            json.dump({"mesh": mesh_desc, "arch": args.arch,
                       "outputs": payload}, f, sort_keys=True)
        print(f"tokens: {len(payload)} requests -> {args.tokens_out}")
    flush_observability()
    if telemetry.memory is not None:
        reg = telemetry.registry
        rss = reg.get("serve_host_rss_peak_bytes").value / 2**20
        dev = reg.get("serve_device_peak_bytes").value / 2**20
        print(f"memory: host rss peak {rss:.0f} MiB"
              + (f", device peak {dev:.0f} MiB" if dev else
                 " (device allocator stats unavailable on this backend)"))
    if args.warm:
        if replica_mode:
            # summed over SURVIVOR watchdogs only — recovery installs on
            # survivors re-arm their watchdogs, so failover compiles are
            # expected and real mid-serve retraces still count
            retr = stats["retraces"]
            print(f"retraces: {retr} mid-serve recompiles across "
                  f"{stats['alive']} surviving replicas")
        else:
            sizes = telemetry.watchdog.cache_sizes()
            retr = telemetry.watchdog.retraces
            print(f"retraces: {retr} mid-serve recompiles (jit cache: "
                  + ", ".join(f"{k}={v}" for k, v in sizes.items()) + ")")
        if args.expect_no_retraces and retr > 0:
            raise SystemExit(
                f"{retr} jitted entry points recompiled mid-serve (jit "
                "cache grew after the warm-up baseline) — a compile "
                "stalled a live decode tick")
    return outs


if __name__ == "__main__":
    main()
