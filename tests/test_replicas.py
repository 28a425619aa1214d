"""Replicated serving with chaos injection: bit-exact snapshot failover
(tokens AND logprobs identical to the fault-free run across kill ticks and
state families), no request lost or duplicated, load shedding, hang /
straggler / drop-snapshot fault kinds, and the SIGTERM graceful-drain
contract of the launcher (subprocess)."""
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import (ChaosInjector, ChaosSpec, Overloaded, PrefixCache,
                         ReplicaKilled, ReplicaSet, SamplingParams,
                         parse_chaos, replica_plans)

# -- chaos spec parsing / injector mechanics (host-only, fast) -----------


def test_parse_chaos_specs():
    specs = parse_chaos("kill@12, hang@8:r1:s0.4, slow-tick@5:x8")
    assert [s.kind for s in specs] == ["kill", "hang", "slow-tick"]
    assert specs[0].tick == 12 and specs[0].replica is None
    assert specs[1] == ChaosSpec("hang", 8, replica=1, seconds=0.4)
    assert specs[2].count == 8
    assert parse_chaos("none") == [] and parse_chaos("") == []
    assert parse_chaos("kill@3").__len__() == 1


def test_parse_chaos_roundtrips_describe():
    for text in ("kill@12:r0", "hang@8:r1:x2:s0.4", "disk-flake@0:r1:x2"):
        (spec,) = parse_chaos(text)
        assert parse_chaos(spec.describe()) == [spec]


@pytest.mark.parametrize("bad", ["kill", "kill@x", "frob@3", "kill@-1",
                                 "kill@3:q7"])
def test_parse_chaos_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_chaos(bad)


def test_injector_arm_is_seed_deterministic():
    picks = {ChaosInjector("kill@5", seed=42).arm(8)[0].replica
             for _ in range(5)}
    assert len(picks) == 1  # same seed -> same victim every time
    inj = ChaosInjector("kill@5:r3", seed=0)
    with pytest.raises(ValueError):
        inj.arm(2)  # explicit replica out of range


def test_injector_kill_fires_at_exact_tick():
    inj = ChaosInjector("kill@5:r1")
    inj.arm(2)
    inj.before_tick(1, 4)      # not yet
    inj.before_tick(0, 5)      # wrong replica
    with pytest.raises(ReplicaKilled):
        inj.before_tick(1, 5)
    assert inj.fired == ["kill@5:r1"]


def test_injector_drop_snapshot_window():
    inj = ChaosInjector("drop-snapshot@4:r0:x3")
    inj.arm(2)
    assert not inj.drops_snapshot(0, 3)
    assert all(inj.drops_snapshot(0, t) for t in (4, 5, 6))
    assert not inj.drops_snapshot(0, 7)
    assert not inj.drops_snapshot(1, 5)  # other replica unaffected


def test_injector_io_fault_hook_counts_down():
    inj = ChaosInjector("disk-flake@0:x2")
    inj.arm(1)
    hook = inj.io_fault_hook()
    for _ in range(2):
        with pytest.raises(OSError):
            hook("write")
    hook("write")  # budget exhausted: passes
    assert ChaosInjector("kill@3").io_fault_hook() is None


def test_replica_plans_single_device_fallback():
    plans = replica_plans(3)  # more replicas than devices on CPU CI
    assert len(plans) == 3


_PLACEMENT_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.configs import get_config
from repro.models import build_model
from repro.serve import ReplicaSet

cfg = get_config("gpt2s-polysketch", smoke=True)
model = build_model(cfg)
params, _ = model.init(jax.random.PRNGKey(0))
rs = ReplicaSet(model, cfg, params, n_replicas=4, slots=2, max_len=64)
rng = np.random.default_rng(0)
for _ in range(4):
    rs.submit(rng.integers(0, cfg.vocab_size, 20).astype(np.int32), 4)
outs = rs.run()
devs = jax.devices()
for i, eng in enumerate(rs.engines):
    for tree in (eng.params, eng._slot_caches):
        for leaf in jax.tree_util.tree_leaves(tree):
            assert leaf.sharding.device_set == {devs[i]}, (i, leaf.sharding)
st = rs.stats()
assert st["deaths"] == {} and len(outs) == 4, st
assert all(e["requests"] >= 1 for e in st["engines"].values()), st
print("PLACEMENT_OK")
"""


def test_replica_plans_one_device_per_replica():
    """With one device per replica, each engine's parameters and caches
    live on its own device (read from the arrays, not from the plan)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _PLACEMENT_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "PLACEMENT_OK" in out.stdout


# -- failover bit-parity across kill ticks and state families ------------
#
# The acceptance gate: kill a replica mid-decode and the recovered
# requests' tokens AND logprobs must equal the fault-free run bitwise.
# Parametrized over >=3 kill ticks x two state families (polysketch
# block-resumable; mamba2 SSD token-resumable) with mixed greedy/sampled
# requests and overlapped admission.

_FAMILIES = {
    "polysketch": ("gpt2s-polysketch", {}),
    "ssd": ("mamba2-780m", {"lt_block_size": 16}),
}


@pytest.fixture(scope="module", params=sorted(_FAMILIES))
def family(request):
    arch, overrides = _FAMILIES[request.param]
    cfg = get_config(arch, smoke=True)
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    blk = cfg.lt_block_size
    rng = np.random.default_rng(0)
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, n), np.int32)
               for n in (2 * blk + 5, 7, blk + 3)]
    sps = [SamplingParams(),
           SamplingParams(temperature=0.8, top_k=40, seed=7),
           SamplingParams(temperature=1.0, top_p=0.9, seed=11)]
    return request.param, model, cfg, params, prompts, sps


def _run_fleet(family, chaos=None, cache=True, steps=20, **kw):
    _, model, cfg, params, prompts, sps = family
    pc = PrefixCache(1 << 28) if cache else None
    rs = ReplicaSet(model, cfg, params, n_replicas=2, slots=2, max_len=512,
                    prefix_cache=pc, logprobs=True, overlap=True,
                    chaos=chaos, **kw)
    gids = [rs.submit(p, steps, sampling=sp) for p, sp in zip(prompts, sps)]
    outs = {o.rid: o for o in rs.run()}
    return gids, outs, rs


@pytest.fixture(scope="module")
def fleet_baseline(family):
    gids, outs, rs = _run_fleet(family)
    assert set(gids) == set(outs)
    assert rs.stats()["deaths"] == {}
    return gids, outs


def _assert_bit_identical(gids, outs, gids0, outs0, ctx):
    assert set(gids) == set(outs), ctx  # every submission served once
    for g, g0 in zip(gids, gids0):
        a, b = outs[g], outs0[g0]
        assert np.array_equal(a.tokens, b.tokens), (ctx, g, a.tokens,
                                                    b.tokens)
        assert np.array_equal(a.logprobs, b.logprobs), (ctx, g)


@pytest.mark.parametrize("kill_tick", [2, 5, 9])
def test_failover_bit_identical(family, fleet_baseline, kill_tick):
    gids0, outs0 = fleet_baseline
    gids, outs, rs = _run_fleet(
        family, chaos=ChaosInjector(f"kill@{kill_tick}:r0"))
    st = rs.stats()
    assert st["deaths"] == {"kill": 1}
    assert st["failovers"] >= 1
    assert st["duplicate_outputs"] == 0
    assert st["recovered_installs"] >= 1
    _assert_bit_identical(gids, outs, gids0, outs0,
                          (family[0], f"kill@{kill_tick}"))


def test_failover_without_checkpoints(family, fleet_baseline):
    """cache=None: no checkpoints exist, so recovery falls back to full
    prompt prefill + decode-path token replay — still bit-exact."""
    gids0, outs0 = fleet_baseline
    gids, outs, rs = _run_fleet(family, chaos=ChaosInjector("kill@5:r0"),
                                cache=False)
    st = rs.stats()
    assert st["checkpoints"] == 0 and st["failovers"] >= 1
    _assert_bit_identical(gids, outs, gids0, outs0,
                          (family[0], "kill@5 no-cache"))


def test_drop_snapshot_fault_still_bit_identical(family, fleet_baseline):
    """drop-snapshot suppresses the victim's checkpoint writes; failover
    then replays from further back but must emit the same tokens."""
    gids0, outs0 = fleet_baseline
    # replica 0's slots cross checkpoint boundaries at ticks 10 and 12 in
    # this workload (deterministic: the tick schedule is host-timing-free);
    # the kill at 14 lands after both writes were suppressed
    gids, outs, rs = _run_fleet(
        family, chaos=ChaosInjector("drop-snapshot@0:r0,kill@14:r0"))
    st = rs.stats()
    assert st["checkpoints_dropped"] >= 1
    _assert_bit_identical(gids, outs, gids0, outs0,
                          (family[0], "drop-snapshot+kill@14"))


# -- remaining fault kinds / fleet mechanics (one family is enough) ------


@pytest.fixture(scope="module")
def psk():
    arch, _ = _FAMILIES["polysketch"]
    cfg = get_config(arch, smoke=True)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    blk = cfg.lt_block_size
    prompts = [np.asarray(rng.integers(0, cfg.vocab_size, n), np.int32)
               for n in (2 * blk + 5, 7, blk + 3)]
    sps = [SamplingParams(),
           SamplingParams(temperature=0.8, top_k=40, seed=7),
           SamplingParams(temperature=1.0, top_p=0.9, seed=11)]
    return "polysketch", model, cfg, params, prompts, sps


@pytest.fixture(scope="module")
def psk_baseline(psk):
    gids, outs, _ = _run_fleet(psk)
    return gids, outs


def test_hang_timeout_declares_death_and_fails_over(psk, psk_baseline):
    gids0, outs0 = psk_baseline
    # hang at tick 10: past the fleet's cold compiles, so the blown
    # deadline is attributed to the hang, not to a compile stall (ticks
    # that grow a jit cache are exempt from the hang deadline)
    gids, outs, rs = _run_fleet(
        psk, chaos=ChaosInjector("hang@10:r0:s0.8"), hang_timeout_s=0.4)
    st = rs.stats()
    assert st["deaths"] == {"hang": 1}
    # the hung tick's outputs were discarded atomically, yet nothing is
    # lost or duplicated and tokens still match the fault-free run
    _assert_bit_identical(gids, outs, gids0, outs0, "hang@4")


def test_slow_tick_is_straggler_not_death(psk, psk_baseline):
    """slow-tick fires but only slows the replica: no death, no failover,
    and outputs are untouched (straggler *flagging* is statistical —
    mu + 3*sigma over a warm window — and unit-tested in
    test_distributed.py; compile-time outliers make it unreliable to
    assert on in a cold fleet run)."""
    gids0, outs0 = psk_baseline
    chaos = ChaosInjector("slow-tick@3:r0:x6:s0.05")
    gids, outs, rs = _run_fleet(psk, chaos=chaos)
    st = rs.stats()
    assert st["deaths"] == {} and st["failovers"] == 0
    assert any(f.startswith("slow-tick") for f in chaos.fired)
    _assert_bit_identical(gids, outs, gids0, outs0, "slow-tick")


def test_crash_keeps_exception_in_death_log(psk):
    """A tick that raises something nobody injected is a death whose
    exception type and message stay readable in stats()."""
    _, model, cfg, params, prompts, _ = psk
    rs = ReplicaSet(model, cfg, params, n_replicas=2, slots=2, max_len=512)
    eng0 = rs.engines[0]

    def broken_step():
        raise RuntimeError("device 0 fell over")

    eng0.step = broken_step
    gids = [rs.submit(p, 4) for p in prompts]
    outs = rs.run()
    st = rs.stats()
    assert st["deaths"] == {"crash": 1}
    assert st["death_log"] == ["replica0 crash: RuntimeError: device 0 "
                               "fell over"]
    assert sorted(o.rid for o in outs) == sorted(gids)


def test_shed_above_raises_overloaded(psk):
    _, model, cfg, params, prompts, _ = psk
    rs = ReplicaSet(model, cfg, params, n_replicas=2, slots=2, max_len=512,
                    shed_above=1)
    for p in prompts[:2]:  # 2 outstanding == 1 * 2 live replicas
        rs.submit(p, 4)
    with pytest.raises(Overloaded):
        rs.submit(prompts[2], 4)
    assert rs.stats()["shed"] == 1
    outs = rs.run()
    assert len(outs) == 2  # shed request was never admitted
    rs.submit(prompts[2], 4)  # capacity is back after drain
    assert len(rs.run()) == 1


def test_stats_surface(psk):
    gids, outs, rs = _run_fleet(psk, chaos=ChaosInjector("kill@5:r1"))
    st = rs.stats()
    assert st["replicas"] == 2 and st["alive"] == 1
    assert st["failovers"] >= 1
    assert st["requests"] == len(outs) == len(gids)
    assert set(st["engines"]) == {0}  # survivors only
    assert st["retraces"] == 0
    assert len(st["heartbeat_age_s"]) == 2


def test_drain_checkpoints_persists_to_disk(tmp_path, psk):
    _, model, cfg, params, prompts, _ = psk
    pc = PrefixCache(1 << 28, save_dir=str(tmp_path))
    rs = ReplicaSet(model, cfg, params, n_replicas=2, slots=2, max_len=512,
                    prefix_cache=pc)
    for p in prompts:
        rs.submit(p, 64)
    for _ in range(3):
        rs.step()
    paths = rs.drain_checkpoints()
    assert paths and all(os.path.exists(p) for p in paths)
    # one checkpoint per request still in flight at drain time
    assert len(paths) >= 1


# -- SIGTERM graceful drain of the launcher (subprocess) -----------------


@pytest.mark.slow
def test_launcher_sigterm_drains_and_exits_zero(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve",
         "--arch", "gpt2s-polysketch", "--smoke", "--requests", "8",
         "--slots", "2", "--prompt-len", "32", "--gen", "500",
         "--rate", "2", "--prefix-cache-mb", "8",
         "--prefix-cache-dir", str(tmp_path)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True)
    # wait for the launcher's flushed "serving:" sentinel — printed right
    # after the PreemptionGuard installs, so the SIGTERM is guaranteed to
    # be caught (a fixed sleep races engine-build time under suite load)
    lines = []
    deadline = time.monotonic() + 180
    for line in proc.stdout:
        lines.append(line)
        if line.startswith("serving:") or time.monotonic() > deadline:
            break
    assert any(ln.startswith("serving:") for ln in lines), "".join(lines)
    time.sleep(6)  # first requests admitted, slots live
    proc.send_signal(signal.SIGTERM)
    try:
        out = "".join(lines) + proc.stdout.read()
    finally:
        proc.stdout.close()
    assert proc.wait(timeout=120) == 0, out
    assert "SIGTERM: drained" in out, out
    assert "checkpoint file(s) persisted" in out, out
