"""Work functions against hand counts at smoke shapes, and the trace
reduction on a small recorded trace. CPU only."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_harness as bh  # noqa: E402
import bench_trace  # noqa: E402

flops = bh.load_module(os.path.join(HERE, "work", "model_flops.py"))
tick = bh.load_module(os.path.join(HERE, "work", "decode_tick.py"))

SMOKE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
             d_ff=128, vocab_size=128, sketch_size=8, lt_block_size=16)


def _cfg(name):
    c = bh.read_json(os.path.join(HERE, "configs", name + ".json"))
    c.update(SMOKE)
    return c


PSK, SMX = _cfg("gpt2s-polysketch"), _cfg("gpt2s-softmax")
PEAKS = {"flops_bf16": 1e12, "hbm_bytes_per_s": 1e9}
MATMUL = 180224


def test_matmul_flops_by_hand():
    # per layer: q,k,v 3*64*64, out 64*64, GLU 3*64*128 -> 40960 params;
    # 2 layers, 2 FLOPs each, + tied head 2*64*128
    assert flops.matmul_flops_per_token(PSK) == 2 * 2 * 40960 + 16384 \
        == MATMUL


def test_polysketch_forward_flops_by_hand():
    # per head: sketch nets 2 vectors x 2 nets x 2*(16*64+64*8+8*64+64*8)
    # = 20480; readout + update 2 * 2*64*17 = 4352; local block at its
    # causal average (16+1)/2 keys: (2*16 + 2*17) * 8.5 = 561
    per_head = 20480 + 4352 + 561
    assert flops.forward_flops_per_token(PSK, 16) == pytest.approx(
        MATMUL + 2 * 4 * per_head)
    assert flops.train_flops_per_token(PSK, 16) == pytest.approx(
        3 * (MATMUL + 2 * 4 * per_head))


def test_softmax_flops_by_hand():
    # q.k and weights.v over the causal average of (32+1)/2 keys
    per_head = 2 * 16 * 16.5 * 2
    assert flops.forward_flops_per_token(SMX, 32) == pytest.approx(
        MATMUL + 2 * 4 * per_head)
    assert flops.decode_flops(SMX, 9) == pytest.approx(
        MATMUL + 2 * 4 * (2 * 16 * 10 * 2))


def test_param_count_is_the_programs():
    import jax
    from repro.models import build_model
    for c in (PSK, SMX):
        model = build_model(bh.arch_config(c))
        shapes = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
        n = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
        assert tick.param_count(c) == n


def test_decode_tick_bytes_by_hand():
    # polysketch at pos 20 (block 16): z 8*8*17 = 1088, one row 2*16+8 =
    # 40, 4 filled rows, 1/16 of a fold (16*(16+8) + 2*1088) / 16 = 160
    per_head = 1088 + 4 * 40 + 40 + 160
    assert tick.slot_bytes(PSK, 20) == 2 * 4 * per_head * 4
    # softmax at pos 20: read 20 keys and values, write one row
    assert tick.slot_bytes(SMX, 20) == 2 * 4 * (2 * 16 * 20 + 2 * 16) * 4
    w = tick.necessary(PSK, [20, 20], PEAKS)
    assert w["bytes"] == tick.param_count(PSK) * 4 + 2 * tick.slot_bytes(
        PSK, 20)
    assert w["bound_by"] == "bytes"
    assert w["bound_s"] == pytest.approx(w["bytes"] / 1e9)


def test_union_and_gaps():
    total, gaps = bench_trace.union_ns([(0, 10), (5, 20), (30, 40),
                                        (35, 38)])
    assert total == 30 and gaps == [(20, 30)]


def test_reduce_recorded_trace():
    """30 ms recorded on a v5e inside one decode tick of the chat cell."""
    import numpy as np
    fx = bh.read_json(os.path.join(HERE, "fixtures", "trace_small.json"))
    red = bench_trace.reduce(fx["devices"], fx["host"], fx["window"])
    a, b = fx["window"]
    timeline = np.zeros(b - a, bool)        # 1 ns resolution
    for s, e, _ in fx["devices"][0]["ops"]:
        timeline[max(s, a) - a:min(e, b) - a] = True
    assert red.window_s == pytest.approx(0.03)
    assert red.busy_s == pytest.approx(timeline.sum() * 1e-9)
    assert red.idle_share == pytest.approx(1 - timeline.mean(), abs=1e-12)
    assert red.program(r"^jit_decode_all$") == (1, pytest.approx(0.03))
    # the fold, computed for every slot on every tick, leads the list
    assert red.top_ops[0][0].startswith("add_select_fusion.3")
    assert not any(n.startswith("while") for n, _ in red.top_ops)


def test_reduce_names_idle_gaps_by_host_span():
    devices = [{"ops": [(0, 10, "%a = f32[2] add(x)"),
                        (5, 20, "%b = f32[2] mul(x)"),
                        (30, 40, "%while.1 = (f32[2]) while(x)"),
                        (30, 36, "%c = f32[2] add(x)")],
                "programs": [(0, 20, "jit_decode_all(1)"),
                             (30, 40, "jit_prefill_one(2)")]}]
    host = [(0, 50, "bench.window"), (0, 25, "bench.step"),
            (25, 50, "bench.wait_for_arrival")]
    red = bench_trace.reduce(devices, host, (0, 50))
    assert red.busy_s == pytest.approx(30e-9)
    assert red.program(r"^jit_prefill_") == (1, pytest.approx(10e-9))
    assert [n for n, _ in red.idle_gaps] == ["bench.wait_for_arrival"] * 2
    assert [n for n, _ in red.top_ops] == ["b f32[2] mul", "a f32[2] add",
                                           "c f32[2] add"]
