"""The reduction of a device trace by the program's named scopes and host
spans (bench_scopes), on recorded and hand-made traces. CPU only."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_harness as bh  # noqa: E402
import bench_scopes  # noqa: E402
import bench_trace  # noqa: E402


def _fixture(name):
    return bh.read_json(os.path.join(HERE, "fixtures", name))


def _same(a: bench_trace.Reduced, b: bench_trace.Reduced):
    assert (a.window_s, a.busy_s, a.programs, a.top_ops, a.idle_gaps) == (
        b.window_s, b.busy_s, b.programs, b.top_ops, b.idle_gaps)


@pytest.mark.parametrize("path, scopes", [
    ("jit(decode_all)/vmap()/while/body/closed_call/mixer/fold/eq",
     ("mixer", "fold")),
    ("jit(train_step)/transpose(jvp(mixer))/sketch/dot_general",
     ("mixer", "sketch")),
    ("jit(decode_all)/vmap(embed)/lt", ("embed",)),
    ("jit(decode_all)/sampler/cond/branch_0_fun/vmap(jit(_gumbel))/add",
     ("sampler",)),
    ("jit(step)/vmap(fold_in)/folded/dynamic_slice", ()),
    ("", ()),
])
def test_scopes_are_path_components(path, scopes):
    assert bench_scopes.scopes_of(path) == scopes


def test_recorded_trace_without_scopes_reduces_as_before():
    """The parent program's trace (no paths, no program spans): every
    field bench_trace gives is unchanged, everything lands outside, and a
    per-call scope reading finds nothing."""
    fx = _fixture("trace_small.json")
    devices = [dict(d, paths=[""] * len(d["ops"])) for d in fx["devices"]]
    got = bench_scopes.reduce(devices, fx["host"], fx["window"])
    _same(got.reduced, bench_trace.reduce(fx["devices"], fx["host"],
                                          fx["window"]))
    assert set(got.scopes["jit_decode_all"]) == {bench_scopes.OUTSIDE}
    assert got.spans == {}
    assert got.per_call_ms("jit_decode_all", "fold") is None
    assert got.per_call_ms("jit_decode_all", "outside") == pytest.approx(
        sum(min(e, fx["window"][1]) - max(s, fx["window"][0])
            for s, e, n in fx["devices"][0]["ops"]
            if not bench_trace.CONTAINERS.match(n)) * 1e-6)


def test_recorded_trace_with_scopes_and_spans():
    """1.25 ms recorded on a v5e in the chat cell, from a tick's start
    through an idle gap, each op's path read from the profile's programs:
    every number against a count by hand (a 1 ns timeline, a scan of the
    programs), and the fields bench_trace gave before unchanged but for
    the gaps' names."""
    import numpy as np
    fx = _fixture("trace_scopes.json")
    w0, w1 = fx["window"]
    got = bench_scopes.reduce(fx["devices"], fx["host"], fx["window"])
    dev = fx["devices"][0]
    want = {}
    for (s, e, name), path in zip(dev["ops"], dev["paths"]):
        s, e = max(s, w0), min(e, w1)
        if e <= s or name.startswith("%while"):
            continue
        prog = [n for p0, p1, n in dev["programs"] if p0 <= s < p1]
        scopes = [sc for sc in bench_scopes.SCOPES
                  if f"/{sc}/" in f"/{path}/" or f"({sc})" in path]
        acc = want.setdefault(prog[0].split("(")[0], {})
        for sc in scopes or ["outside"]:
            acc[sc] = acc.get(sc, 0) + (e - s)
    assert {p: {k: round(v * 1e9) for k, v in d.items()}
            for p, d in got.scopes.items()} == want
    # the prefill chunk's end: sketch within mixer, then the FFN and head
    assert want["jit_prefill_resume"] == {
        "mixer": 440949, "sketch": 211964, "ffn": 198703, "logits": 140805,
        "outside": 362301}
    assert want["jit_install_slot"] == {"outside": 70057}
    assert {k: [c, round(v * 1e9)] for k, (c, v) in got.spans.items()} == {
        "serve.tick": [1, 76872623], "serve.plan": [1, 6030],
        "serve.chunk_dispatch": [1, 6480],
        "serve.decode_dispatch": [1, 1123420],
        "serve.host_sync": [1, 74626723]}
    # gaps in the device timeline, named after the innermost span
    timeline = np.zeros(w1 - w0, bool)
    for s, e, _ in dev["ops"]:
        timeline[max(s, w0) - w0:min(e, w1) - w0] = True
    assert got.reduced.busy_s == pytest.approx(timeline.sum() * 1e-9)
    assert [n for n, _ in got.reduced.idle_gaps] == [
        "serve.decode_dispatch"] * len(got.reduced.idle_gaps)
    assert got.reduced.idle_gaps[0][1] == pytest.approx(20653e-9)
    old = bench_trace.reduce(
        fx["devices"], [h for h in fx["host"] if h[2].startswith("bench.")],
        fx["window"])
    assert [n for n, _ in old.idle_gaps] == ["bench.step"] * len(
        old.idle_gaps)
    assert [g for _, g in old.idle_gaps] == [
        g for _, g in got.reduced.idle_gaps]
    assert (old.window_s, old.busy_s, old.programs, old.top_ops) == (
        got.reduced.window_s, got.reduced.busy_s, got.reduced.programs,
        got.reduced.top_ops)


def test_hand_made_trace():
    devices = [{"ops": [(0, 10, "%while.1 = (f32[2]) while(x)"),
                        (0, 4, "%a = f32[2] add(x)"),
                        (4, 9, "%b = f32[2] mul(x)"),
                        (9, 10, "%c = f32[2] copy(x)"),
                        (20, 26, "%d = f32[2] add(x)"),
                        (40, 44, "%e = f32[2] mul(x)")],
                "paths": ["jit(decode_all)/while",
                          "jit(decode_all)/while/body/mixer/fold/add",
                          "jit(decode_all)/while/body/mixer/mul",
                          "jit(decode_all)/while/body/copy",
                          "jit(decode_all)/vmap(logits)/add",
                          "jit(prefill_one)/ffn/mul"],
                "programs": [(0, 10, "jit_decode_all(1)"),
                             (20, 26, "jit_decode_all(1)"),
                             (40, 50, "jit_prefill_one(2)")]}]
    host = [(0, 50, "bench.window"), (0, 30, "bench.step"),
            (2, 28, "serve.tick"), (12, 24, "serve.host_sync"),
            (30, 50, "bench.step"), (31, 48, "serve.tick"),
            (31, 33, "serve.plan"), (60, 70, "serve.tick")]
    got = bench_scopes.reduce(devices, host, (0, 50))
    ns = 1e-9
    assert got.scopes == {
        "jit_decode_all": {"mixer": pytest.approx(9 * ns),
                           "fold": pytest.approx(4 * ns),
                           "outside": pytest.approx(1 * ns),
                           "logits": pytest.approx(6 * ns)},
        "jit_prefill_one": {"ffn": pytest.approx(4 * ns)}}
    assert got.per_call_ms("jit_decode_all", "fold") == pytest.approx(2e-6)
    # program spans that start in the window, whole
    assert got.spans == {"serve.tick": [2, pytest.approx(43 * ns)],
                         "serve.host_sync": [1, pytest.approx(12 * ns)],
                         "serve.plan": [1, pytest.approx(2 * ns)]}
    # gaps (26, 40), (10, 20), (44, 50), longest first, each named after
    # the innermost span over its middle
    assert got.reduced.idle_gaps == [
        ["serve.tick", pytest.approx(14 * ns)],
        ["serve.host_sync", pytest.approx(10 * ns)],
        ["serve.tick", pytest.approx(6 * ns)]]
    _same(got.reduced, bench_trace.reduce(devices, host, (0, 50)))


def test_host_spans_are_read_from_a_profile(tmp_path):
    """On the CPU the profile has no TPU plane; the host spans of both
    kinds are read, and no others."""
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("serve.tick"):
                with jax.profiler.TraceAnnotation("serve.host_sync"):
                    pass
            with jax.profiler.TraceAnnotation("train.feed"):
                pass
            with jax.profiler.TraceAnnotation("other.span"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(tmp_path)
               for f in fs if f.endswith(".xplane.pb")]
    devices, host = bench_scopes.read_xplane(path, 1)
    assert devices == []
    assert sorted(n for _, _, n in host) == [
        "bench.window", "serve.host_sync", "serve.tick", "train.feed"]
    spans = {n: (s, e) for s, e, n in host}
    assert (spans["bench.window"][0] <= spans["serve.tick"][0]
            <= spans["serve.host_sync"][0] <= spans["serve.host_sync"][1]
            <= spans["serve.tick"][1])


def test_paths_come_from_the_programs_the_profile_carries(tmp_path):
    """Each compiled instruction's op_name, read from the HloProto the
    profile holds for its program, equals the compiled text's."""
    import re

    import jax
    import jax.numpy as jnp

    def f(x):
        with jax.named_scope("mixer"):
            y = jnp.sin(x) @ x
            with jax.named_scope("fold"):
                y = jax.lax.cond(y[0, 0] > 0, lambda z: z * 2, lambda z: z,
                                 y)
        with jax.named_scope("ffn"):
            return jnp.tanh(y).sum()

    g = jax.jit(jax.grad(f))
    x = jnp.ones((16, 16))
    g(x).block_until_ready()
    want = {}
    for line in g.lower(x).compile().as_text().splitlines():
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = ", line)
        if m:
            name = re.search(r'op_name="([^"]*)"', line)
            want[m.group(1)] = name.group(1) if name else ""
    jax.profiler.start_trace(str(tmp_path))
    try:
        g(x).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    (path,) = [os.path.join(d, n) for d, _, fs in os.walk(tmp_path)
               for n in fs if n.endswith(".xplane.pb")]
    with open(path, "rb") as fh:
        programs = bench_scopes.program_op_names(fh.read())
    (got,) = [v for k, v in programs.items() if k.startswith("jit_f(")]
    assert got == want
    assert {bench_scopes.scopes_of(p) for p in got.values()} >= {
        ("mixer",), ("mixer", "fold"), ("ffn",)}


def test_an_op_takes_the_path_of_its_own_program():
    """Instruction names repeat across programs: each op is looked up in
    the program whose event holds its start."""
    names = {"jit_a(1)": {"fusion.1": "jit(a)/mixer/fold/add"},
             "jit_b(2)": {"fusion.1": "jit(b)/ffn/mul"}}
    ops = [(0, 5, "%fusion.1 = f32[2] fusion(x)"),
           (10, 15, "%fusion.1 = f32[2] fusion(y)"),
           (20, 25, "%fusion.1 = f32[2] fusion(z)")]
    programs = [(10, 16, "jit_b(2)"), (0, 6, "jit_a(1)")]
    assert bench_scopes._paths(ops, programs, names) == [
        "jit(a)/mixer/fold/add", "jit(b)/ffn/mul", ""]
