"""CPU tests of the benchmark harness: discovery by name, the traffic
generator, and the entry's refusal to measure without a TPU. Nothing here
touches a TPU."""
import json
import os
import re
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_harness as bh  # noqa: E402
import bench_traffic  # noqa: E402

BENCH = bh.read_json(os.path.join(ROOT, "BENCHMARK.json"))
WORKLOADS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("workload", WORKLOADS)
def test_every_cell_finds_its_parts(workload):
    cell = bh.load_cell(ROOT, workload)
    assert cell.config["name"] == workload.split(".")[0]
    assert callable(cell.reference().forward)
    assert os.path.exists(cell.part(f"bench_{cell.traffic['kind']}.py"))
    assert cell.per_layer, "every cell reports a per-layer metric"
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    for m in cell.per_layer:
        assert callable(cell.reader(m["name"]).read)
        assert m["moves"] in names


def test_benchmark_json_names_and_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    every = ([c["name"] for c in BENCH["configs"]] + WORKLOADS
             + [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]])
    assert len(every) == len(set(every))
    assert all(NAME.match(n) for n in every)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert all(len(x) <= 200 for x in layers)


@pytest.mark.parametrize("name,attr", [("gpt2s-polysketch",
                                        "GPT2_SMALL_POLYSKETCH"),
                                       ("gpt2s-softmax",
                                        "GPT2_SMALL_SOFTMAX")])
def test_config_files_are_the_published_configs(name, attr):
    from repro.configs import gpt2_paper
    cfg = bh.read_json(os.path.join(HERE, "configs", name + ".json"))
    assert bh.arch_config(cfg) == getattr(gpt2_paper, attr)


def test_new_parts_are_found_without_editing_any(tmp_path):
    """A later cell adds a configuration, a mix, a metric and a limits
    file as files of their own plus entries in BENCHMARK.json."""
    root = tmp_path / "checkout"
    bench_dir = root / "benchmarks" / "chip"
    shutil.copytree(HERE, bench_dir,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench_dir.rglob("*") if p.is_file()}
    cfg = json.loads((bench_dir / "configs/gpt2s-polysketch.json")
                     .read_text())
    cfg["name"] = "gpt2s-polysketch-r64"
    cfg["sketch_size"] = 64
    (bench_dir / "configs/gpt2s-polysketch-r64.json").write_text(
        json.dumps(cfg))
    (bench_dir / "traffic/chat-burst.json").write_text(json.dumps(
        {"base": "chat", "rate_per_s": 3.0}))
    (bench_dir / "metrics/burst_depth.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    (bench_dir / "limits/gpt2s-polysketch-r64.chat-burst.json").write_text(
        json.dumps({"served_logit_gap": 0.5}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "gpt2s-polysketch-r64", "source": "x",
                             "file": "benchmarks/chip/configs/"
                                     "gpt2s-polysketch-r64.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "gpt2s-polysketch-r64.chat-burst",
                               "config": "gpt2s-polysketch-r64",
                               "traffic": "chat-burst", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "burst_depth", "unit": "ms",
                               "better": "lower", "source": "program_span",
                               "layer": "engine and scheduler",
                               "moves": "ttft_p95_ms",
                               "workloads": ["gpt2s-polysketch-r64."
                                             "chat-burst"]})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "itl_p95_ms"):
            m["workloads"].append("gpt2s-polysketch-r64.chat-burst")
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = bh.load_cell(str(root), "gpt2s-polysketch-r64.chat-burst",
                        bench_dir=str(bench_dir))
    assert cell.config["sketch_size"] == 64
    assert cell.traffic["rate_per_s"] == 3.0
    assert cell.traffic["engine"]["slots"] == 16     # from the base mix
    assert [m["name"] for m in cell.per_layer] == ["burst_depth"]
    assert bh.load_module(cell.part("metrics", "burst_depth.py")).read(
        None) == 7.0
    assert cell.limits == {"served_logit_gap": 0.5}
    after = {p: p.read_bytes() for p in before}
    assert after == before, "an existing file changed"


@pytest.mark.parametrize("mix", ["chat", "decode-batch"])
def test_schedule_repeats_for_a_seed_and_reorders_across_seeds(mix):
    m = bh.load_traffic(HERE, mix)
    big = 2 ** 33 + 17                    # seeds pass 32 bits
    a = bench_traffic.serve_schedule(m, big, 51, 32000)
    b = bench_traffic.serve_schedule(m, big, 51, 32000)
    c = bench_traffic.serve_schedule(m, big + 1, 51, 32000)
    assert np.array_equal(a.due_s, b.due_s)
    assert np.array_equal(a.outputs, b.outputs)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, b.prompts))
    assert not np.array_equal(a.outputs, c.outputs)
    assert not all(np.array_equal(x, y)
                   for x, y in zip(a.prompts, c.prompts))
    # the same work in another order
    assert sorted(a.outputs) == sorted(c.outputs)
    assert sorted(map(len, a.prompts)) == sorted(map(len, c.prompts))
    assert np.all(np.diff(a.due_s) >= 0) and a.due_s[-1] < 51
    lens = bench_traffic.lengths(m["prompt"])
    assert set(map(len, a.prompts)) <= set(lens)
    assert [len(p) for p in bench_traffic.warm_prompts(m, 32000)] == lens
    eng = m["engine"]
    assert max(lens) + m["output"]["max"] <= eng["max_len"]


@pytest.mark.parametrize("n", [20, 23])
def test_deal_puts_one_value_of_each_slice_in_every_run(n):
    values = np.arange(n)
    rng = np.random.default_rng(2 ** 40 + 3)
    got = bench_traffic.deal(values, rng)
    assert sorted(got) == list(values)
    slice_of = {int(v): g for g, part in
                enumerate(np.array_split(values, bench_traffic.STRATA))
                for v in part}
    k = bench_traffic.STRATA
    for j in range(0, n, k):
        run = [slice_of[int(v)] for v in got[j:j + k]]
        assert len(set(run)) == len(run)
    again = bench_traffic.deal(values, np.random.default_rng(2 ** 40 + 3))
    assert np.array_equal(got, again)
    assert not np.array_equal(got, bench_traffic.deal(
        values, np.random.default_rng(5)))


def test_chat_lengths_follow_the_mix():
    m = bh.load_traffic(HERE, "chat")
    s = bench_traffic.serve_schedule(m, 5, 51, 32000)
    lens = np.array([len(p) for p in s.prompts])
    assert len(lens) == round(m["rate_per_s"] * 51)
    assert lens.min() >= 256 and lens.max() <= 4096
    assert np.all(lens % 256 == 0)
    assert 1280 <= np.median(lens) <= 1792
    assert 16 <= s.outputs.min() and s.outputs.max() <= 512


def test_markov_rows_repeat_and_differ():
    a = bench_traffic.markov_rows(100, 4, 64, 2 ** 40)
    b = bench_traffic.markov_rows(100, 4, 64, 2 ** 40)
    assert a.shape == (4, 65) and a.dtype == np.int32
    assert np.array_equal(a, b)
    assert len({r.tobytes() for r in a}) == 4
    assert not np.array_equal(a, bench_traffic.markov_rows(100, 4, 64, 3))


def _run_entry(cwd, env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         WORKLOADS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(out: str) -> bool:
    return not any(line.startswith("{") for line in out.splitlines())


def test_entry_refuses_without_a_tpu():
    p = _run_entry(ROOT, {})
    assert p.returncode != 0
    assert _no_result(p.stdout)
    assert "no TPU" in p.stderr


def test_entry_refuses_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(HERE, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_entry(str(tmp_path), {"PYTHONPATH": ""})
    assert p.returncode != 0
    assert _no_result(p.stdout)


class _Dev:
    def __init__(self, platform, kind):
        self.platform, self.device_kind = platform, kind


class _Jax:
    def __init__(self, *devs):
        self._devs = list(devs)

    def devices(self):
        return self._devs


@pytest.mark.parametrize("devs,why", [
    ([_Dev("cpu", "cpu")], "no TPU"),
    ([_Dev("tpu", "TPU v9 imaginary")], "not in peaks.json"),
])
def test_require_chips_refuses(devs, why):
    with pytest.raises(bh.NoAccelerator, match=why):
        bh.require_chips(_Jax(*devs), 1)


def test_require_chips_counts_and_names_the_device():
    v5e = _Dev("tpu", "TPU v5 lite")
    with pytest.raises(bh.NoAccelerator, match="needs 4 chips"):
        bh.require_chips(_Jax(v5e), 4)
    got = bh.require_chips(_Jax(v5e, v5e), 1)
    assert (got["platform"], got["kind"], got["count"]) == (
        "tpu", "TPU v5 lite", 1)
    assert got["peaks"]["flops_bf16"] == 197e12


def test_only_numbers_with_a_limit_are_compared():
    cell = bh.load_cell(ROOT, "gpt2s-polysketch.train-8k")
    cell.limits = {"train_grad_gap": 0.03}
    checks = bh.compare(cell, {"train_grad_gap": 0.01,
                               "train_loss_gap": 5.0})
    assert checks == {"train_grad_gap": {"value": 0.01, "limit": 0.03}}
    assert bh.checks_ok(checks)
    assert not bh.checks_ok({"window_compiles": {"value": 0, "limit": 0}})
    assert not bh.checks_ok({"train_grad_gap": {"value": 0.05,
                                                "limit": 0.03}})
