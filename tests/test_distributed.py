"""Multi-device tests run in a subprocess with 8 host-platform devices
(XLA device count is locked at first init, so the flag must be set in a
fresh interpreter)."""
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.distributed.compression import int8_allreduce_mean, tree_psum_mean

mesh = make_mesh((8,), ("data",))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 1000)) * 3.0

def f_exact(xs):
    return jax.lax.pmean(xs, "data")

def f_int8(xs):
    return int8_allreduce_mean(xs, "data", 8)

exact = jax.shard_map(f_exact, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)
comp = jax.shard_map(f_int8, mesh=mesh, in_specs=P("data"), out_specs=P("data"))(x)
err = float(jnp.abs(exact - comp).max() / jnp.abs(exact).max())
assert err < 0.05, f"int8 allreduce rel err {err}"
print("INT8_OK", err)

# manual-DP train step with compression runs and syncs params identically
from repro.configs import TrainConfig, get_config
from repro.models import build_model
from repro.train import init_train_state
from repro.train.step import make_manual_dp_train_step
cfg = get_config("gpt2s-polysketch", smoke=True)
model = build_model(cfg)
params, _ = model.init(jax.random.PRNGKey(0))
batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size)}
for compression in ("none", "int8"):
    tcfg = TrainConfig(seq_len=32, global_batch=8, steps=4,
                       grad_compression=compression)
    step = make_manual_dp_train_step(model, cfg, tcfg, mesh)
    state = init_train_state(params)
    state, m = jax.jit(step)(state, batch)
    assert np.isfinite(float(m["loss"])), compression
    print("DP_STEP_OK", compression, float(m["loss"]))

# the int8-compressed collective moves ~4x fewer bytes (HLO inspection)
import re
def coll_bytes(fn):
    lowered = jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=P("data"),
                                out_specs=P("data"))).lower(x)
    from repro.launch.hlo import parse_collectives
    return parse_collectives(lowered.compile().as_text(), 8)["total_bytes"]
b_exact, b_int8 = coll_bytes(f_exact), coll_bytes(f_int8)
print("COLL_BYTES", b_exact, b_int8)
assert b_int8 < b_exact, (b_exact, b_int8)
"""


@pytest.mark.slow
def test_int8_compression_and_manual_dp():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "INT8_OK" in out.stdout
    assert "DP_STEP_OK int8" in out.stdout


@pytest.mark.slow
def test_small_mesh_dryrun_cell():
    """A reduced dry-run cell (smoke config, 2x4 mesh) end to end."""
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from repro.configs import get_config, TrainConfig
from repro.distributed.sharding import (activation_sharding, batch_shardings,
                                        shardings_for, replicated)
from repro.launch.mesh import make_mesh
from repro.launch.hlo import parse_collectives
from repro.launch.dryrun import abstract_init, _f32_like
from repro.models import build_model
from repro.optim.adamw import AdamWState
from repro.train.step import TrainState, make_train_step

mesh = make_mesh((2, 4), ("data", "model"))
cfg = get_config("qwen3-14b", smoke=True)
model = build_model(cfg)
params_sds, axes = abstract_init(model)
params_sh = shardings_for(axes, params_sds, mesh)
specs = {"tokens": jax.ShapeDtypeStruct((4, 65), jnp.int32)}
bsh = batch_shardings(mesh, specs)
tcfg = TrainConfig(seq_len=64, global_batch=4, steps=10)
step = make_train_step(model, cfg, tcfg)
state_sh = TrainState(params=params_sh,
                      opt=AdamWState(m=params_sh, v=params_sh,
                                     count=replicated(mesh)),
                      step=replicated(mesh))
state_sds = TrainState(params=params_sds,
                       opt=AdamWState(m=_f32_like(params_sds),
                                      v=_f32_like(params_sds),
                                      count=jax.ShapeDtypeStruct((), jnp.int32)),
                       step=jax.ShapeDtypeStruct((), jnp.int32))
with mesh, activation_sharding(mesh):
    lowered = jax.jit(step, in_shardings=(state_sh, bsh)).lower(state_sds, specs)
compiled = lowered.compile()
print("MEM", compiled.memory_analysis().temp_size_in_bytes)
coll = parse_collectives(compiled.as_text(), 8)
print("COLL", coll["total_bytes"], sorted(coll["per_op"]))
assert coll["total_bytes"] > 0  # FSDP/TP must communicate
print("DRYRUN_OK")
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "DRYRUN_OK" in out.stdout


# ---------------------------------------------------------------------------
# distributed/fault.py (host-side, no devices needed)
# ---------------------------------------------------------------------------

def test_with_retries_backoff_then_success(monkeypatch):
    from repro.distributed import fault

    sleeps = []
    monkeypatch.setattr(fault.time, "sleep", sleeps.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise IOError("transient")
        return "ok"

    assert fault.with_retries(flaky, retries=3, backoff=0.5)() == "ok"
    assert calls["n"] == 3
    assert sleeps == [0.5, 1.0]  # backoff * 2**attempt


def test_with_retries_exhaustion(monkeypatch):
    from repro.distributed import fault

    sleeps = []
    monkeypatch.setattr(fault.time, "sleep", sleeps.append)
    calls = {"n": 0}

    def broken():
        calls["n"] += 1
        raise OSError("down")

    with pytest.raises(OSError):
        fault.with_retries(broken, retries=2, backoff=0.25)()
    assert calls["n"] == 3           # initial try + 2 retries
    assert sleeps == [0.25, 0.5]     # no sleep after the final failure


def test_with_retries_unlisted_exception_propagates(monkeypatch):
    from repro.distributed import fault

    sleeps = []
    monkeypatch.setattr(fault.time, "sleep", sleeps.append)
    calls = {"n": 0}

    def bug():
        calls["n"] += 1
        raise ValueError("not transient")

    with pytest.raises(ValueError):
        fault.with_retries(bug, retries=3)()
    assert calls["n"] == 1 and sleeps == []


def test_with_retries_preserves_wrapped_metadata():
    from repro.distributed import fault

    def load_shard(path):
        """Read one data shard."""
        return path

    wrapped = fault.with_retries(load_shard)
    assert wrapped.__name__ == "load_shard"
    assert wrapped.__doc__ == "Read one data shard."


def test_with_retries_jitter_stretches_backoff(monkeypatch):
    from repro.distributed import fault

    sleeps = []
    monkeypatch.setattr(fault.time, "sleep", sleeps.append)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 4:
            raise IOError("transient")
        return "ok"

    assert fault.with_retries(flaky, retries=3, backoff=0.5,
                              jitter=0.5)() == "ok"
    # each sleep is base * u with u uniform in [1, 1+jitter]
    for base, got in zip([0.5, 1.0, 2.0], sleeps):
        assert base <= got <= base * 1.5


def test_with_retries_on_retry_hook(monkeypatch):
    from repro.distributed import fault

    monkeypatch.setattr(fault.time, "sleep", lambda _s: None)
    seen = []
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError(f"boom {calls['n']}")
        return "ok"

    fault.with_retries(flaky, retries=3, backoff=0.1,
                       on_retry=lambda a, e: seen.append((a, str(e))))()
    assert seen == [(1, "boom 1"), (2, "boom 2")]  # 1-based attempt index


def test_straggler_detector_stop_without_start_raises():
    from repro.distributed import fault

    det = fault.StragglerDetector()
    with pytest.raises(RuntimeError):
        det.stop()


def test_straggler_detector_flags_outlier(monkeypatch):
    from repro.distributed import fault

    clock = {"t": 0.0}
    monkeypatch.setattr(fault.time, "perf_counter", lambda: clock["t"])
    det = fault.StragglerDetector(window=50, z=3.0, min_steps=10)

    def step(dt):
        det.start()
        clock["t"] += dt
        return det.stop()

    # identical steps: variance ~0, nothing flags
    for _ in range(20):
        assert step(0.10) is False
    assert det.flagged == []
    # a 2x step against a zero-variance baseline must flag
    assert step(0.20) is True
    assert len(det.flagged) == 1
    flagged_step, flagged_dt = det.flagged[0]
    assert abs(flagged_dt - 0.20) < 1e-9


def test_straggler_detector_warmup_never_flags(monkeypatch):
    from repro.distributed import fault

    clock = {"t": 0.0}
    monkeypatch.setattr(fault.time, "perf_counter", lambda: clock["t"])
    det = fault.StragglerDetector(window=50, z=3.0, min_steps=10)
    # below min_steps even a wild outlier is warm-up, not a straggler
    for dt in (0.1, 0.1, 0.1, 5.0):
        det.start()
        clock["t"] += dt
        assert det.stop() is False
    assert det.flagged == []


def test_preemption_guard_install_uninstall():
    import signal

    from repro.distributed import fault

    prev = signal.getsignal(signal.SIGTERM)
    guard = fault.PreemptionGuard()
    assert guard.preempted is False
    guard.install()
    try:
        assert signal.getsignal(signal.SIGTERM) is not prev
        os.kill(os.getpid(), signal.SIGTERM)
        assert guard.preempted is True
    finally:
        guard.uninstall()
    # the previous handler must be restored exactly
    assert signal.getsignal(signal.SIGTERM) is prev
