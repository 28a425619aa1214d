"""On-chip smoke test: the serving and training entry points of
gpt2s-polysketch at its published widths (13 layers, d 768, 12x64 heads,
d_ff 3072, vocab 32000, sketch r 32, block 1024), random weights from a
seed, on one TPU.

  python3 chip_smoke.py             # one chip: serve, reference, kernel, train
  python3 chip_smoke.py --chips 4   # four chips: 4 replicas vs one engine

Each phase prints what it found and how long it took: wall seconds, the
seconds JAX spent compiling, and how many programs it loaded from the
persistent compilation cache instead. Any failed phase makes the script
exit non-zero; on
success the last line of stdout is one JSON object naming the device.
It refuses to run anywhere but on a TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))
OUT = os.path.join(ROOT, "chiprun_out", "smoke")

ARCH = "gpt2s-polysketch"
SEED = 0

# Reference tolerance, as a fraction of the reference's largest |logit|.
# The serve path runs at the chip's default matmul precision, where an f32
# matmul takes one bf16 pass: each operand keeps 8 significant bits
# (relative error up to 2^-9). About 7 matmuls per layer over 13 layers,
# plus the degree-4 power of the local attention weights (which multiplies
# their relative error by 4), put the expected drift at a few 1e-2 of the
# logit scale. A wrong fold, position or state moves logits by O(1) of it.
REF_TOL = 5e-2
# Kernel tolerance, as a fraction of the XLA result's largest magnitude:
# the same single-bf16-pass bound on the attention matmuls, one layer deep.
KERNEL_TOL = 2e-2


def _phase(results, name, fn, compile_log):
    """Run one phase; record ok/error, wall and compile seconds, and how
    many programs came from the persistent compilation cache."""
    compile_log.clear()
    t0 = time.perf_counter()
    try:
        fn()
        ok, err = True, None
    except (Exception, SystemExit) as e:  # noqa: BLE001 — report every phase
        ok, err = False, f"{type(e).__name__}: {e}"
        traceback.print_exc()
    wall = time.perf_counter() - t0
    comp = sum(s for s in compile_log if s is not None)
    hits = sum(s is None for s in compile_log)
    results.append({"phase": name, "ok": ok, "wall_s": wall,
                    "compile_s": comp, "cache_hits": hits, "error": err})
    print(f"[{name}] {'ok' if ok else 'FAILED'}: wall {wall:.1f}s, "
          f"compile {comp:.1f}s, {hits} programs from the compile cache"
          + (f" — {err}" if err else ""), flush=True)
    gc.collect()  # engines hold device buffers inside reference cycles


def _metric(path, name):
    """One unlabelled series from a Prometheus text exposition file."""
    with open(path) as f:
        for line in f:
            parts = line.split()
            if len(parts) == 2 and parts[0] == name:
                return float(parts[1])
    raise KeyError(f"{name} not in {path}")


def serve_phase(serve_main, common):
    """Two runs of the serving launcher; the second shares a 2048-token
    prefix across requests and must restore it from the prefix cache."""
    os.makedirs(OUT, exist_ok=True)
    runs = {"plain": [], "shared_prefix": ["--shared-prefix", "2048",
                                           "--prefix-cache-mb", "512"]}
    for tag, extra in runs.items():
        prom = os.path.join(OUT, f"serve_{tag}.prom")
        outs = serve_main(common + extra + ["--metrics-out", prom])
        n_req = int(common[common.index("--requests") + 1])
        gen = int(common[common.index("--gen") + 1])
        if len(outs) != n_req or any(len(o.tokens) != gen for o in outs):
            raise RuntimeError(f"{tag}: served {len(outs)} of {n_req} "
                               "requests, or some came back short")
        peak = _metric(prom, "serve_device_peak_bytes")
        hits = _metric(prom, "serve_prefix_cache_hits_total") if extra else 0
        print(f"serve[{tag}]: {len(outs)} requests x {gen} tokens, device "
              f"peak {int(peak)} bytes, prefix-cache hits {int(hits)}",
              flush=True)
        if peak <= 0:
            raise RuntimeError(f"{tag}: device peak bytes not reported")
        if extra and hits < 1:
            raise RuntimeError(f"{tag}: no prefix-cache hit")


def reference_phase(jax, np, cfg, model, params, prompt_len, steps):
    """generate()'s last logits against a plain f32 forward of the same
    tokens at the highest matmul precision, and the engine's greedy tokens
    against generate()'s."""
    from repro.serve import ServeEngine, generate
    rng = np.random.default_rng(SEED + 1)
    prompt = rng.integers(0, cfg.vocab_size, size=prompt_len).astype(np.int32)
    res = jax.jit(lambda p, x: generate(model, cfg, p, x, steps))(
        params, prompt[None])
    toks = np.asarray(res.tokens[0])
    full = np.concatenate([prompt, toks])[None]

    def last_logits(p, x):
        return model.apply(p, {"tokens": x}, mode="train",
                           impl="xla")[0][:, -1]

    with jax.default_matmul_precision("highest"):
        ref = np.asarray(jax.jit(last_logits)(params, full), np.float64)
    plain = np.asarray(jax.jit(last_logits)(params, full), np.float64)
    got = np.asarray(res.logits_last, np.float64)
    scale = np.abs(ref).max()
    err = np.abs(got - ref).max() / scale
    plain_err = np.abs(plain - ref).max() / scale
    print(f"reference: {prompt_len}+{steps} tokens, max|logit| "
          f"{scale:.4g}; serve path error {err:.3e}, plain forward at "
          f"default precision {plain_err:.3e} (tolerance {REF_TOL:g}); "
          f"argmax agrees: {bool(np.argmax(got) == np.argmax(ref))}",
          flush=True)
    if not np.all(np.isfinite(got)) or err > REF_TOL:
        raise RuntimeError(f"serve-path logits off the f32 reference: "
                           f"{err:.3e} > {REF_TOL:g}")

    eng = ServeEngine(model, cfg, params, slots=1,
                      max_len=prompt_len + steps)
    eng.submit(prompt, steps)
    (out,) = eng.run()
    same = bool(np.array_equal(out.tokens, toks))
    print(f"reference: engine greedy tokens equal generate's: {same}",
          flush=True)


def kernel_phase(jax, jnp, np, cfg):
    """The fused Pallas kernel against the XLA path, cold and resumed."""
    from repro.core.sketches import init_sketch, sketch_half
    from repro.kernels import ops
    h, r, blk = cfg.resolved_head_dim, cfg.sketch_size, cfg.lt_block_size
    heads, n = cfg.n_heads, 2 * blk
    scale = cfg.attn_scale
    ks = jax.random.split(jax.random.PRNGKey(SEED + 2), 5)
    sk, _ = init_sketch(ks[0], h, r, cfg.poly_degree, cfg.learned_sketch)
    q, k, v = (jax.random.normal(kk, (1, heads, 2 * n, h), jnp.float32)
               for kk in ks[1:4])
    qm = sketch_half(sk, q * scale ** 0.5, cfg.poly_degree,
                     cfg.learned_sketch)
    km = sketch_half(sk, k * scale ** 0.5, cfg.poly_degree,
                     cfg.learned_sketch)
    kw = dict(degree=cfg.poly_degree, scale=scale, block_size=blk)
    first = lambda x: x[..., :n, :]
    rest = lambda x: x[..., n:, :]

    def run(impl, xs, z0=None):
        fn = jax.jit(lambda *a: ops.polysketch_attention(
            *a[:5], z0=a[5], impl=impl, return_state=True, **kw))
        return fn(*xs, z0)

    # prefix state after the first n tokens, from the f32 reference
    with jax.default_matmul_precision("highest"):
        _, z0 = run("xla", [first(x) for x in (qm, km, q, k, v)])
    xs = [rest(x) for x in (qm, km, q, k, v)]
    worst = 0.0
    for tag, z in (("cold", None), ("resumed", z0)):
        with jax.default_matmul_precision("highest"):
            want, zw = run("xla", xs, z)
        got, zg = run("pallas", xs, z)
        for what, a, b in (("out", got, want), ("state", zg, zw)):
            a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
            err = np.abs(a - b).max() / np.abs(b).max()
            worst = max(worst, err)
            print(f"kernel[{tag}] {what}: max error {err:.3e} of "
                  f"max|{what}| {np.abs(b).max():.4g}", flush=True)
            if not np.all(np.isfinite(a)):
                raise RuntimeError(f"kernel[{tag}] {what}: not finite")
    print(f"kernel: (1, {heads}, {n}, {h}), r={r}, block {blk}: worst "
          f"error {worst:.3e} (tolerance {KERNEL_TOL:g})", flush=True)
    if worst > KERNEL_TOL:
        raise RuntimeError(f"Pallas kernel off the XLA path: {worst:.3e}")


def train_phase(jax, np, train_main, model, params0):
    """Three steps of the training launcher; the launcher itself exits
    non-zero on a non-finite loss."""
    state = train_main(["--arch", ARCH, "--steps", "3", "--batch", "4",
                        "--seq", "2048", "--set", "remat=full",
                        "--log-every", "1", "--seed", str(SEED)])
    new = jax.tree_util.tree_leaves(state.params)
    old = jax.tree_util.tree_leaves(params0)
    finite = all(bool(np.all(np.isfinite(np.asarray(x)))) for x in new)
    moved = sum(not np.array_equal(np.asarray(a), np.asarray(b))
                for a, b in zip(new, old))
    print(f"train: 3 steps, batch 4 x 2048 tokens; {moved}/{len(new)} "
          f"parameter arrays changed, all finite: {finite}", flush=True)
    if not finite or moved == 0:
        raise RuntimeError("training left parameters unchanged or non-finite")


def replicas_phase(jax, np, cfg, model, params):
    """ReplicaSet with one replica per chip against one ServeEngine on one
    chip, on the same greedy requests: equal tokens, no deaths, every
    replica serving, every replica's arrays on its own device."""
    from repro.serve import ReplicaSet, ServeEngine
    devs = jax.devices()[:4]
    rng = np.random.default_rng(SEED + 3)
    lens = [1536, 2304, 3072]
    prompts = [rng.integers(0, cfg.vocab_size, size=lens[i % 3])
               .astype(np.int32) for i in range(8)]
    gen, max_len = 32, 3072 + 32
    kw = dict(slots=2, max_len=max_len, overlap=True, prefill_budget=1024)

    one = ServeEngine(model, cfg, params, **kw)
    rids = [one.submit(p, gen) for p in prompts]
    base = {o.rid: o.tokens for o in one.run()}
    base = [base[r] for r in rids]
    del one
    gc.collect()

    rs = ReplicaSet(model, cfg, params, n_replicas=4, **kw)
    gids = [rs.submit(p, gen) for p in prompts]
    got = {o.rid: o.tokens for o in rs.run()}
    st = rs.stats()
    equal = all(np.array_equal(got.get(g), b) for g, b in zip(gids, base))
    per = {i: e["requests"] for i, e in st["engines"].items()}
    print(f"replicas: {len(got)}/{len(prompts)} served, per replica {per}, "
          f"deaths {st['deaths']} {st['death_log']}, tokens equal to one "
          f"engine: {equal}", flush=True)
    homes = []
    for i, eng in enumerate(rs.engines):
        sets = {frozenset(x.sharding.device_set) for tree in
                (eng.params, eng._slot_caches)
                for x in jax.tree_util.tree_leaves(tree)}
        homes.append(sets)
        print(f"replicas: replica {i} arrays on "
              f"{sorted(str(d) for s in sets for d in s)}", flush=True)
    placed = all(s == {frozenset([d])} for s, d in zip(homes, devs))
    if not (equal and placed and not st["deaths"] and len(got) == 8
            and len(per) == 4 and min(per.values()) >= 1):
        raise RuntimeError(f"replica check failed: tokens equal {equal}, "
                           f"one device per replica {placed}, stats {st}")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs only the replicated-serving phase, one "
                         "replica per chip")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.serve import main as serve_main
    from repro.launch.train import main as train_main
    from repro.models import build_model

    cache_dir = enable_compile_cache()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, JAX found "
                         f"{dev.platform!r}")
    n_dev = len(jax.devices())
    if n_dev < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but "
                         f"{n_dev} visible")
    print(f"device: {dev.device_kind} x {n_dev}; compile cache "
          f"{cache_dir}", flush=True)

    # backend compile seconds, and None for each persistent-cache hit
    compile_log: list[float | None] = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_log.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda event, **_: compile_log.append(None)
        if event == "/jax/compilation_cache/cache_hits" else None)

    cfg = get_config(ARCH)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(SEED))
    results = []
    if args.chips == 4:
        _phase(results, "replicas",
               lambda: replicas_phase(jax, np, cfg, model, params),
               compile_log)
    else:
        common = ["--arch", ARCH, "--requests", "16", "--slots", "8",
                  "--prompt-len", "3072", "--gen", "32", "--overlap",
                  "--prefill-budget", "1024", "--warm",
                  "--expect-no-retraces", "--logprobs", "--seed", str(SEED)]
        _phase(results, "serve", lambda: serve_phase(serve_main, common),
               compile_log)
        _phase(results, "reference",
               lambda: reference_phase(jax, np, cfg, model, params,
                                       prompt_len=2040, steps=16),
               compile_log)
        _phase(results, "kernel", lambda: kernel_phase(jax, jnp, np, cfg),
               compile_log)
        _phase(results, "train",
               lambda: train_phase(jax, np, train_main, model, params),
               compile_log)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, f"phases_{args.chips}chip.json"), "w") as f:
        json.dump(results, f, indent=1)
    failed = [r["phase"] for r in results if not r["ok"]]
    if failed:
        raise SystemExit(f"chip_smoke: failed phases: {', '.join(failed)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": n_dev}}))


if __name__ == "__main__":
    main()
