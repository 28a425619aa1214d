"""The measurement the program carries for a profiler: named scopes on the
model's layers, found in the compiled programs' op metadata (forward,
recompute and backward alike), and the engine's tick phases as
`serve.<phase>` host spans in a profiler trace while its tracer is on."""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import TrainConfig
from repro.data import DataIterator
from repro.models import build_model
from repro.serve import ServeEngine, Telemetry
from repro.serve.engine import make_decode_tick
from repro.serve.plan import ServePlan
from repro.serve.sampling import init_slot_keys, init_slot_sampling
from repro.train import init_train_state, make_train_step

SLOTS, MAX_LEN = 2, 48
OP_NAME = re.compile(r'op_name="([^"]*)"')


def op_paths(hlo_text: str) -> list[str]:
    return OP_NAME.findall(hlo_text)


def in_scope(path: str, scope: str) -> bool:
    """The scope is a component of the path: `/fold/`, or wrapped by a
    transformation as in `transpose(jvp(mixer))`."""
    return any(part == scope or f"({scope})" in part
               for part in path.split("/"))


def scopes_seen(paths) -> set[str]:
    return {s for s in ("embed", "mixer", "sketch", "fold", "ffn", "logits",
                        "sampler", "loss", "optimizer")
            if any(in_scope(p, s) for p in paths)}


def _model(arch, **overrides):
    cfg = get_config(arch, smoke=True)
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def tick_hlo(**overrides) -> str:
    """The compiled text of the engine's decode tick over every slot."""
    cfg, model, params = _model("gpt2s-polysketch", **overrides)
    st, plan = model.state, ServePlan.single_device()
    caches = st.broadcast_slots(st.init_slot(params, MAX_LEN), SLOTS)
    tick = make_decode_tick(st, plan, logprobs=True)
    with plan.activation_context():
        return jax.jit(tick).lower(
            params, jnp.zeros((SLOTS, 1, 1), jnp.int32),
            jnp.zeros((SLOTS,), jnp.int32), init_slot_keys(SLOTS),
            init_slot_sampling(SLOTS), caches,
            jnp.ones((SLOTS,), bool)).compile().as_text()


def test_decode_tick_names_every_layer():
    seen = scopes_seen(op_paths(tick_hlo()))
    assert {"embed", "mixer", "sketch", "fold", "ffn", "logits",
            "sampler"} <= seen


def test_softmax_tick_has_no_fold():
    seen = scopes_seen(op_paths(tick_hlo(attention="softmax")))
    assert {"mixer", "ffn", "logits", "sampler"} <= seen
    assert "fold" not in seen and "sketch" not in seen


@pytest.mark.parametrize("remat", ["none", "full"])
def test_train_step_names_forward_backward_and_optimizer(remat):
    cfg, model, params = _model("gpt2s-polysketch", remat=remat)
    step = make_train_step(model, cfg, TrainConfig(seq_len=32,
                                                   global_batch=2))
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}
    paths = op_paths(jax.jit(step).lower(init_train_state(params),
                                         batch).compile().as_text())
    assert {"mixer", "sketch", "ffn", "loss", "optimizer"} <= scopes_seen(
        paths)
    # the backward pass keeps the scope under its transformation
    assert any("transpose(jvp(" in p and in_scope(p, "mixer")
               for p in paths)


def _host_events(logdir):
    """(start_ns, end_ns, name) of every host-plane event in the capture."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{logdir}/plugins/profile/*/*.xplane.pb")
    return [(e.start_ns, e.end_ns, e.name)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host")
            for line in plane.lines for e in line.events]


@pytest.mark.parametrize("trace", [True, False])
def test_tick_phases_reach_the_profiler_only_while_tracing(tmp_path, trace):
    cfg, model, params = _model("gpt2s-polysketch")
    eng = ServeEngine(model, cfg, params, slots=SLOTS, max_len=MAX_LEN,
                      telemetry=Telemetry(trace=trace))
    rng = np.random.default_rng(0)
    for n in (5, 9):
        eng.submit(jnp.asarray(rng.integers(0, cfg.vocab_size, n),
                               jnp.int32), 6)
    eng.step()                  # compiles outside the capture
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
        eng.step()
    finally:
        jax.profiler.stop_trace()
    spans = [ev for ev in _host_events(tmp_path)
             if ev[2].startswith("serve.")]
    if not trace:
        assert spans == []
        return
    ticks = [ev for ev in spans if ev[2] == "serve.tick"]
    syncs = [ev for ev in spans if ev[2] == "serve.host_sync"]
    assert len(ticks) == 2 and len(syncs) == 2
    for s, e, _ in syncs:       # each sync nests inside a tick
        assert any(t0 <= s and e <= t1 for t0, t1, _ in ticks)
    assert {n for _, _, n in spans} <= {
        "serve.tick", "serve.plan", "serve.chunk_dispatch",
        "serve.decode_dispatch", "serve.host_sync", "serve.retire"}


def test_feed_is_a_profiler_span(tmp_path):
    it = DataIterator(lambda b, s, k: np.full((b, s + 1), k, np.int32), 2, 8)
    jax.profiler.start_trace(str(tmp_path))
    try:
        next(it)
        next(it)
    finally:
        jax.profiler.stop_trace()
    assert [n for _, _, n in _host_events(tmp_path)].count("train.feed") == 2
