"""Serve-layer observability contracts: metrics registry typing and
Prometheus exposition, TTFT histogram le-bucket semantics (locked against
the legacy np.searchsorted formula), tracer span/instant recording and
Perfetto export schema validity, the retrace watchdog's steady-state
gating, memory watermarks, engine stats() backward compatibility, and
telemetry-on vs telemetry-off token bit-parity."""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serve import (Histogram, MemorySampler, MetricsRegistry,
                         RetraceWatchdog, SamplingParams, ServeEngine,
                         Telemetry, Tracer, format_event, validate_trace)
from repro.serve.engine import ServeEngine as _Eng


def _setup(seed=0, **overrides):
    cfg = get_config("gpt2s-polysketch", smoke=True)
    if overrides:
        cfg = cfg.replace(**overrides)
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(seed))
    return model, cfg, params


def _prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.integers(0, cfg.vocab_size, n), jnp.int32)
            for n in lens]


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

def test_registry_get_or_create_and_type_mismatch():
    reg = MetricsRegistry()
    c = reg.counter("requests_total")
    assert reg.counter("requests_total") is c  # get-or-create
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        reg.gauge("requests_total")  # kind mismatch
    with pytest.raises(ValueError):
        reg.counter("requests_total", labels=("reason",))  # label mismatch
    with pytest.raises(ValueError):
        reg.counter("bad name")  # invalid metric name


def test_registry_labels_and_collector_rules():
    reg = MetricsRegistry()
    fam = reg.counter("finished_total", labels=("reason",))
    fam.labels(reason="length").inc(3)
    fam.labels(reason="eos").inc()
    assert fam.labels(reason="length").value == 3
    assert fam.total == 4
    with pytest.raises(ValueError):
        fam.labels(cause="length")  # wrong label name
    # collector callbacks: registered once, never rebound, no labels
    box = {"v": 7.0}
    g = reg.gauge("live_slots", fn=lambda: box["v"])
    assert g.value == 7.0
    box["v"] = 9.0
    assert g.value == 9.0
    with pytest.raises(ValueError):
        reg.gauge("live_slots", fn=lambda: 0.0)  # rebind forbidden
    with pytest.raises(ValueError):
        reg.counter("labelled_fn", labels=("a",), fn=lambda: 0.0)
    # reset zeroes values but keeps registrations (collectors untouched)
    reg.reset()
    assert fam.total == 0
    assert g.value == 9.0
    assert set(reg.names()) >= {"finished_total", "live_slots"}


def test_render_prometheus_format():
    reg = MetricsRegistry()
    reg.counter("reqs_total", "requests seen").inc(2)
    reg.gauge("depth").set(1.5)
    h = reg.histogram("lat_ms", edges=(1.0, 10.0))
    h.observe(0.5)
    h.observe(5.0)
    h.observe(100.0)
    text = reg.render_prometheus()
    assert "# HELP reqs_total requests seen\n# TYPE reqs_total counter" in text
    assert "\nreqs_total 2\n" in text
    assert "# TYPE depth gauge" in text and "\ndepth 1.5\n" in text
    # histogram buckets are cumulative and end at +Inf
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="10"} 2' in text
    assert 'lat_ms_bucket{le="+Inf"} 3' in text
    assert "lat_ms_sum 105.5" in text and "lat_ms_count 3" in text


# ---------------------------------------------------------------------------
# histogram le-semantics (locks the TTFT bucket contract)
# ---------------------------------------------------------------------------

def test_histogram_edge_semantics_lock():
    edges = _Eng.TTFT_EDGES_MS
    h = Histogram(edges)
    assert h.edges[-1] == math.inf and len(h.edges) == len(edges)
    # a value exactly on an edge falls in the bucket that edge bounds
    h.observe(5.0)
    assert h.counts[list(h.edges).index(5.0)] == 1
    # beyond the last finite edge lands in the +Inf bucket
    h.observe(1e9)
    assert h.counts[-1] == 1
    # empty percentiles are zeros, not NaN
    empty = Histogram(edges)
    assert empty.percentiles() == {"p50": 0.0, "p95": 0.0, "p99": 0.0}
    assert empty.count == 0 and empty.sum == 0.0 and empty.max == 0.0
    with pytest.raises(ValueError):
        Histogram(())
    with pytest.raises(ValueError):
        Histogram((2.0, 1.0))  # must be strictly increasing
    with pytest.raises(ValueError):
        Histogram((1.0, 1.0))


def test_histogram_matches_legacy_searchsorted_formula():
    """The engine's pre-registry ttft_hist was
    np.bincount(np.searchsorted(edges[:-1], vals, side="left"), ...);
    Histogram must reproduce it bucket-for-bucket on adversarial values
    (exact edges, just-below, just-above, 0, and overflow)."""
    edges = np.asarray(_Eng.TTFT_EDGES_MS)
    rng = np.random.default_rng(0)
    vals = np.concatenate([
        edges[:-1], edges[:-1] - 1e-9, edges[:-1] + 1e-9,
        [0.0, 1e-12, 5e6], rng.uniform(0, 2000, 200)])
    legacy = np.bincount(np.searchsorted(edges[:-1], vals, side="left"),
                         minlength=len(edges))
    h = Histogram(tuple(edges))
    for v in vals:
        h.observe(float(v))
    np.testing.assert_array_equal(np.asarray(h.counts), legacy)
    assert h.count == len(vals)
    assert h.max == vals.max()


def test_histogram_window_percentiles():
    h = Histogram((10.0,), window=4)
    for v in (100.0, 1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    # the window holds only the last 4 values; count/max are since-reset
    assert h.percentiles((50,))["p50"] == 2.5
    assert h.count == 5 and h.max == 100.0
    h.reset()
    assert h.count == 0 and list(h.window) == []


# ---------------------------------------------------------------------------
# tracer + perfetto export
# ---------------------------------------------------------------------------

def test_tracer_spans_instants_and_export(tmp_path):
    tr = Tracer()
    tr.begin("tick", "tick", n=1)
    tr.begin("tick", "plan")
    tr.end("tick")
    tr.instant("queue", "submit", rid=0)
    tr.begin("slot0", "prefill", rid=0)
    tr.end("slot0", chunks=2)
    tr.end("tick", retired=0)
    path = tmp_path / "trace.json"
    trace = tr.export(str(path))
    assert validate_trace(trace) == []
    on_disk = json.loads(path.read_text())
    assert validate_trace(on_disk) == []
    names = {(e["ph"], e["name"]) for e in on_disk["traceEvents"]}
    assert {("X", "tick"), ("X", "plan"), ("X", "prefill"),
            ("i", "submit")} <= names
    tracks = {e["args"]["name"] for e in on_disk["traceEvents"]
              if e.get("name") == "thread_name"}
    assert tracks == {"tick", "queue", "slot0"}
    # begin args merge with end args on the completed span
    pf = next(e for e in on_disk["traceEvents"] if e["name"] == "prefill")
    assert pf["args"] == {"rid": 0, "chunks": 2}
    # unbalanced end is dropped, not an exception
    tr.end("never-opened")
    # open spans flush as unterminated
    tr.begin("slot0", "decode", rid=1)
    flushed = tr.export()
    dec = next(e for e in flushed["traceEvents"] if e["name"] == "decode")
    assert dec["args"]["unterminated"] is True
    assert format_event(("i", "submit", 0, 1234.5, 0.0, {"rid": 3}))


def test_tracer_disabled_is_inert_and_bounded_ring():
    tr = Tracer(enabled=False)
    assert not tr
    tr.instant("queue", "submit")
    tr.begin("tick", "tick")
    tr.end("tick")
    assert len(tr) == 0
    ring = Tracer(max_events=4)
    for i in range(10):
        ring.instant("queue", "submit", rid=i)
    assert len(ring) == 4  # bounded: oldest events dropped


def test_validate_trace_rejects_schema_drift():
    bad_name = {"traceEvents": [
        {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
         "args": {"name": "tick"}},
        {"ph": "X", "pid": 1, "tid": 0, "name": "nonsense", "ts": 0.0,
         "dur": 1.0}]}
    assert any("schema" in e for e in validate_trace(bad_name))
    orphan = {"traceEvents": [
        {"ph": "i", "pid": 1, "tid": 9, "name": "submit", "ts": 1.0,
         "s": "t"}]}
    assert any("thread_name" in e for e in validate_trace(orphan))
    overlap = {"traceEvents": [
        {"ph": "M", "pid": 1, "tid": 0, "name": "thread_name",
         "args": {"name": "tick"}},
        {"ph": "X", "pid": 1, "tid": 0, "name": "tick", "ts": 0.0,
         "dur": 100.0},
        {"ph": "X", "pid": 1, "tid": 0, "name": "plan", "ts": 50.0,
         "dur": 100.0}]}
    assert any("nest" in e for e in validate_trace(overlap))
    assert validate_trace({"nope": 1}) == [
        "trace must be a dict with a traceEvents list"]


# ---------------------------------------------------------------------------
# watchdog + memory
# ---------------------------------------------------------------------------

def test_watchdog_counts_only_steady_growth():
    reg, tr = MetricsRegistry(), Tracer()
    wd = RetraceWatchdog(reg, tr)

    @jax.jit
    def f(x):
        return x * 2

    assert wd.register("f", f) is True
    f(jnp.zeros((2,)))        # warm-up compile
    wd.check()
    assert wd.retraces == 0   # pre-steady growth is expected
    wd.mark_steady()
    wd.check()
    assert wd.retraces == 0
    f(jnp.zeros((3,)))        # new shape => mid-serve retrace
    wd.check()
    assert wd.retraces == 1
    assert any(e[1] == "recompile" for e in tr._events)
    assert wd.cache_sizes()["f"] >= 2
    wd.check()                # no further growth => no further counts
    assert wd.retraces == 1
    # a callable without cache introspection is ignored, not fatal
    assert wd.register("plain", lambda x: x) is False


def test_memory_sampler_host_watermark():
    reg = MetricsRegistry()
    ms = MemorySampler(reg)
    tr = Tracer()
    ms.sample(tr)
    rss = reg.get("serve_host_rss_bytes").value
    assert rss > 0
    assert reg.get("serve_host_rss_peak_bytes").value >= rss
    assert any(e[0] == "C" and e[1] == "memory" for e in tr._events)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def test_engine_stats_compat_and_registry_view():
    model, cfg, params = _setup()
    eng = ServeEngine(model, cfg, params, slots=2, max_len=48)
    for p in _prompts(cfg, [5, 9]):
        eng.submit(p, 6)
    outs = eng.run()
    st = eng.stats()
    assert st["requests"] == len(outs) == 2
    assert st["prefills"] == 2 and st["decode_steps"] > 0
    # one percentile path: median IS p50, on the same histogram
    assert st["tick_gap_ms"]["median"] == st["tick_gap_ms"]["p50"]
    assert st["tick_gap_ms"]["max"] >= st["tick_gap_ms"]["p50"] > 0
    assert st["itl_ms"]["p50"] > 0 and st["ttft_ms"]["p50"] > 0
    assert sum(st["ttft_hist"]["counts"]) == 2
    assert st["retraces"] == 0
    # the registry sees the same numbers stats() reports
    reg = eng.telemetry.registry
    assert reg.get("serve_prefills_total").value == 2
    assert reg.get("serve_decode_ticks_total").value == st["decode_steps"]
    assert reg.get("serve_requests_finished_total").total == 2
    text = eng.telemetry.render_prometheus()
    assert "serve_ttft_ms_bucket" in text and "serve_slots 2" in text
    # legacy attribute surface still works (benchmarks use these)
    assert eng.decode_steps == st["decode_steps"]
    assert len(eng._tick_gaps) == reg.get("serve_tick_gap_ms").count > 0
    eng.reset_stats()
    assert eng.stats()["decode_steps"] == 0
    assert eng.telemetry.watchdog.steady


def test_tokens_bit_identical_with_and_without_tracing(tmp_path):
    model, cfg, params = _setup(seed=2)
    sp = SamplingParams(temperature=0.9, top_k=12, seed=5)
    runs = []
    # the traced engine runs while a profiler captures, so its tick phases
    # also enter profiler spans
    jax.profiler.start_trace(str(tmp_path))
    try:
        for tel in (None, Telemetry(trace=True, memory=True,
                                    memory_every=1)):
            eng = ServeEngine(model, cfg, params, slots=2, max_len=48,
                              telemetry=tel)
            for p in _prompts(cfg, [7, 13], seed=4):
                eng.submit(p, 8, sampling=sp)
            runs.append({o.rid: np.asarray(o.tokens) for o in eng.run()})
    finally:
        jax.profiler.stop_trace()
    for rid in runs[0]:
        np.testing.assert_array_equal(runs[0][rid], runs[1][rid])


def test_engine_trace_export_is_schema_valid():
    model, cfg, params = _setup()
    tel = Telemetry(trace=True)
    eng = ServeEngine(model, cfg, params, slots=2, max_len=48, telemetry=tel)
    for p in _prompts(cfg, [5, 9]):
        eng.submit(p, 5)
    eng.run()
    trace = tel.export_trace()
    assert validate_trace(trace) == []
    names = {(e["ph"], e["name"]) for e in trace["traceEvents"]
             if e["ph"] in ("X", "i")}
    assert {("X", "tick"), ("X", "prefill"), ("X", "decode"),
            ("i", "submit"), ("i", "first_token"), ("i", "token"),
            ("i", "retire")} <= names
    # per-slot timelines exist alongside the tick phase track
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e.get("name") == "thread_name"}
    assert {"tick", "queue", "slot0", "slot1"} <= tracks
    assert json.dumps(trace)  # round-trippable as-is
