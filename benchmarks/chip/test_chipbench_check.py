"""The comparison that decides `correct`, driven through a whole run at
smoke sizes on the CPU (the harness's look for a chip is skipped): a sound
run passes; the timed path broken underneath fails; the control (the
reference one precision down) in the program's place fails."""
import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
for p in (HERE, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import bench_check  # noqa: E402
import bench_harness as bh  # noqa: E402
import run as entry  # noqa: E402

CACHE_FLAGS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_compile_time_secs",
               "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture(autouse=True, scope="module")
def _restore_compile_cache_flags():
    """A run turns JAX's persistent compile cache on for the process; put
    the flags back so later tests in this worker are not affected."""
    saved = {k: getattr(jax.config, k) for k in CACHE_FLAGS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


SMOKE = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, head_dim=16,
             d_ff=128, vocab_size=128, sketch_size=8, lt_block_size=16)
# deep and wide enough that the control's rounding reads against the
# program as it does at full size (2 layers of width 64 hide it for softmax)
CONTROL_SIZE = dict(n_layers=4, d_model=256, head_dim=64, d_ff=1024)
SEED = 2 ** 32 + 7


def serve_cell(workload):
    cell = bh.load_cell(ROOT, workload)
    cell.config.update(SMOKE)
    t = cell.traffic
    t["prompt"] = {"dist": "uniform", "min": 16, "max": 48, "multiple": 16}
    if t["loop"] == "open":
        # outputs long enough that decode crosses a local block (a fold)
        t.update(rate_per_s=6.0, output={"dist": "uniform", "min": 8,
                                          "max": 24})
    else:
        t.update(pool=8, output={"dist": "uniform", "min": 8, "max": 24},
                 first_wave_output={"dist": "uniform", "min": 1, "max": 24})
    t["engine"].update(slots=3, max_len=80, prefill_budget=16)
    return cell


def train_cell():
    cell = bh.Cell("smoke.train", 1,
                   bh.read_json(os.path.join(HERE, "configs",
                                             "gpt2s-polysketch.json")),
                   bh.load_traffic(HERE, "train-8k"),
                   [{"name": "train_tokens_per_s", "unit": "tokens/s"}], [],
                   bh.read_json(os.path.join(
                       HERE, "limits", "gpt2s-polysketch.train-8k.json")),
                   HERE)
    cell.config.update(SMOKE)
    cell.traffic.update(seq=48, pool=4)
    return cell


def run(cell, hook=None):
    return entry.run_cell(cell, seed=SEED, seconds=1.0, trace=0,
                          chip_check=False, engine_hook=hook,
                          t_start=time.perf_counter())


def alter_tokens(eng):
    """A token altered where it is produced: the decode tick's output."""
    decode = eng._decode
    vocab = eng.cfg.vocab_size

    def broken(*args):
        out, lps, toks, pos, keys, caches = decode(*args)
        out = (out + 1) % vocab
        return out, lps, out[:, None, None], pos, keys, caches
    eng._decode = broken


def freeze_state(eng):
    """A decode step that returns its state unchanged."""
    decode = eng._decode

    def broken(*args):
        keep = jax.tree_util.tree_map(jnp.copy, args[5])
        out = decode(*args)
        return out[:5] + (keep,)
    eng._decode = broken


SERVE = ["gpt2s-polysketch.chat", "gpt2s-softmax.chat",
         "gpt2s-polysketch.decode-batch"]


@pytest.mark.parametrize("workload", SERVE)
def test_sound_serving_run_is_correct(workload):
    result, checks = run(serve_cell(workload))
    assert result["correct"], checks
    assert checks["window_compiles"]["value"] == 0


@pytest.mark.parametrize("fault", [alter_tokens, freeze_state])
@pytest.mark.parametrize("workload", SERVE)
def test_broken_serving_path_is_not_correct(workload, fault):
    result, checks = run(serve_cell(workload), fault)
    assert not result["correct"], checks
    assert any(c["value"] > c["limit"] for k, c in checks.items()
               if k != "window_compiles")


def test_sound_training_run_is_correct():
    result, checks = run(train_cell())
    assert result["correct"], checks


def test_training_step_that_keeps_its_state_is_not_correct():
    def frozen(step):
        return lambda state, batch: (state, step(state, batch)[1])
    result, checks = run(train_cell(), frozen)
    assert not result["correct"]
    assert checks["train_update_gap"]["value"] == pytest.approx(1.0)
    assert checks["train_grad_gap"]["value"] == pytest.approx(1.0)


def test_training_step_on_half_its_batch_is_not_correct():
    """A step that leaves half of its batch out, the mean taken over the
    rest."""
    def half(step):
        def broken(state, batch):
            b = batch["tokens"].shape[0]
            return step(state, {"tokens": batch["tokens"][:b - b // 2]})
        return broken
    result, checks = run(train_cell(), half)
    assert not result["correct"], checks


def _served_and_control(workload):
    """A sound run's sampled requests, read as the program served them
    and with the control (the reference one precision down) in the
    program's place."""
    import bench_serve
    import bench_traffic
    cell = serve_cell(workload)
    cell.config.update(CONTROL_SIZE)
    srv = bench_serve.Served(cell, jax, SEED)
    sched = bench_traffic.serve_schedule(srv.mix, SEED, 1.0,
                                         srv.cfg.vocab_size)
    w = bench_serve.measure(srv, sched, 1.0)
    outs = [o for o in w.finished if o.rid in w.index]
    srv.free()
    chk = bench_check.Reference(jax, srv.ref, cell.config,
                                srv.mix["engine"]["max_len"])
    prog, ctrl = [], []
    for o in outs:
        prompt = sched.prompts[w.index[o.rid]]
        want = chk.reference(srv.params, prompt, o.tokens)
        prog.append(bench_check.gaps(o.logprobs, want))
        ctrl.append(bench_check.control_gaps(
            chk.control(srv.params, prompt, o.tokens), want))
    return cell, bench_check.widest(prog), bench_check.widest(ctrl)


@pytest.mark.parametrize("workload", ["gpt2s-polysketch.chat",
                                      "gpt2s-softmax.chat"])
def test_control_reads_above_the_program(workload):
    """The control reads wider log-probability gaps at the served tokens
    than the float32 program."""
    _, p, c = _served_and_control(workload)
    assert all(c[k] > 3 * p[k] for k in p if k.startswith("logprob")), (p, c)


@pytest.mark.parametrize("workload", SERVE)
def test_control_in_the_programs_place_is_not_correct(workload):
    """Through the harness's own comparison and limits, the control fails
    and the program passes."""
    cell, p, c = _served_and_control(workload)
    assert bh.checks_ok(bh.compare(cell, p)), p
    assert not bh.checks_ok(bh.compare(cell, c)), c


def test_training_control_is_not_correct():
    """The reference's training steps one precision down, compared in the
    program's place, fail the cell's limits; the program passes them."""
    import bench_train
    cell = train_cell()
    with jax.default_matmul_precision(cell.config["matmul_precision"]):
        prog = bench_train.program_steps(cell, jax, SEED)
        want = bench_train.reference_steps(jax, prog, cell.config, SEED)
        ctrl = bench_train.reference_steps(
            jax, prog, cell.config, SEED,
            mantissa=bench_check.CONTROL_MANTISSA)
    keys = ("train_loss_gap", "train_grad_gap", "train_update_gap")
    got = bench_train.compare({"loss": prog.losses, "grad": prog.grad,
                               "delta": prog.delta}, want)
    assert bh.checks_ok(bh.compare(cell, {k: got[k] for k in keys})), got
    c = bench_train.compare(ctrl, want)
    assert not bh.checks_ok(bh.compare(cell, {k: c[k] for k in keys})), c
