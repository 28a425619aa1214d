"""Compile the main path's kernels and serving programs at the published
widths of gpt2s-polysketch for a described (not attached) TPU v5e chip.

Nothing runs: the chip's compiler refuses here what it would refuse on the
chip — a vector layout Mosaic cannot lower, more VMEM than a kernel may
use, or a program larger than the chip's memory. The topology is described
inside a fixture, so importing this file loads no TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.configs.gpt2_paper import GPT2_SMALL_SOFTMAX
from repro.kernels.lt_mult import lt_mult_pallas
from repro.kernels.poly_flash import poly_flash_pallas
from repro.kernels.polysketch_causal import polysketch_causal_pallas
from repro.models import build_model
from repro.serve.engine import make_decode_tick
from repro.serve.plan import ServePlan
from repro.serve.sampling import init_slot_keys, init_slot_sampling

HBM_BYTES = 16 * 10**9          # one v5e chip
MAX_LEN, CHUNK = 3072 + 32, 1024
# the serving cells' ticks: slots and max_len of benchmarks/chip/traffic
TICKS = {"gpt2s-polysketch": (16, 4608), "gpt2s-softmax": (8, 4608)}


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2 topology, with the persistent compilation cache
    off: a compile for a described chip is written to the cache but cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _published(cfg, one_chip):
    """A config at published widths: model, state protocol, abstract
    parameters placed on the described chip, and a placement."""
    model = build_model(cfg)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))[0])
    mesh = Mesh(np.asarray(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    return cfg, model, _on(params, one_chip), ServePlan.from_mesh(mesh)


@pytest.fixture(scope="module")
def model_shapes(one_chip):
    """gpt2s-polysketch at published widths."""
    return _published(get_config("gpt2s-polysketch"), one_chip)


def _on(tree, sharding):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _fits_one_chip(compiled):
    m = compiled.memory_analysis()
    used = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert used < HBM_BYTES, used


@pytest.mark.parametrize("return_state", [False, True])
def test_polysketch_causal_compiles(one_chip, return_state):
    bh, n, r, h = 12, 2048, 32, 64
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    seq = (f32(bh, n, r), f32(bh, n, r), f32(bh, n, h), f32(bh, n, h),
           f32(bh, n, h))
    state = (f32(bh, r * r, h), f32(bh, 1, r * r)) if return_state else ()

    def fn(*a):
        return polysketch_causal_pallas(*a, degree=4, scale=1.0 / h,
                                        block_size=1024,
                                        return_state=return_state)

    compiled = jax.jit(fn).lower(*seq, *state).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_poly_flash_compiles(one_chip):
    x = jax.ShapeDtypeStruct((12, 2048, 64), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda q, k, v: poly_flash_pallas(
        q, k, v, degree=4, scale=1.0 / 64)).lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_lt_mult_compiles(one_chip):
    ab = jax.ShapeDtypeStruct((12, 2048, 32), jnp.float32, sharding=one_chip)
    c = jax.ShapeDtypeStruct((12, 2048, 64), jnp.float32, sharding=one_chip)
    compiled = jax.jit(lambda a, b, c: lt_mult_pallas(a, b, c)).lower(
        ab, ab, c).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _compile_tick(one_chip, shapes):
    """The engine's decode tick at its serving cell's slots and max_len,
    compiled for the chip; returns (compiled, slot stack shapes)."""
    cfg, model, params, plan = shapes
    slots, max_len = TICKS[cfg.name]
    st = model.state
    caches = _on(jax.eval_shape(
        lambda p: st.broadcast_slots(st.init_slot(p, max_len), slots),
        params), one_chip)
    s = lambda shp, dt: jax.ShapeDtypeStruct(shp, dt, sharding=one_chip)
    keys = _on(jax.eval_shape(lambda: init_slot_keys(slots)), one_chip)
    samp = _on(jax.eval_shape(lambda: init_slot_sampling(slots)), one_chip)
    tick = make_decode_tick(st, plan, logprobs=True)
    with plan.activation_context():
        return jax.jit(tick, donate_argnums=(5,)).lower(
            params, s((slots, 1, 1), jnp.int32), s((slots,), jnp.int32),
            keys, samp, caches, s((slots,), jnp.bool_)).compile(), caches


@pytest.fixture(scope="module")
def ticks(one_chip, model_shapes):
    """Both serving configurations' compiled ticks, compiled once."""
    return {"gpt2s-polysketch": _compile_tick(one_chip, model_shapes),
            "gpt2s-softmax": _compile_tick(
                one_chip, _published(GPT2_SMALL_SOFTMAX, one_chip))}


def _conditional_paths(hlo_text):
    """op_name of every conditional instruction in a compiled program."""
    return [m.group(1) for line in hlo_text.splitlines()
            if " conditional(" in line
            for m in [re.search(r'op_name="([^"]*)"', line)] if m]


def test_engine_decode_tick_compiles(ticks):
    compiled, _ = ticks["gpt2s-polysketch"]
    _fits_one_chip(compiled)
    text = compiled.as_text()
    # the layers' named scopes survive the chip's compiler in the ops'
    # metadata, where a device trace reads them
    paths = re.findall(r'op_name="([^"]*)"', text)
    for scope in ("mixer", "sketch", "fold", "ffn", "logits", "sampler"):
        assert any(f"/{scope}/" in p or f"({scope})" in p
                   for p in paths), scope
    # the block fold stays a branch, taken on the ticks where some slot
    # completes a block, and not a select that folds every slot on every
    # tick
    assert any("/fold/" in p for p in _conditional_paths(text))


def test_engine_softmax_decode_tick_compiles(ticks):
    compiled, _ = ticks["gpt2s-softmax"]
    _fits_one_chip(compiled)
    paths = re.findall(r'op_name="([^"]*)"', compiled.as_text())
    assert not any("/fold/" in p for p in paths)


_BYTES = {"f32": 4, "bf16": 2, "s32": 4, "u32": 4, "pred": 1}


def _computations(hlo_text):
    """{computation name: its instruction lines} of a compiled program."""
    comps, cur = {}, None
    for line in hlo_text.splitlines():
        m = re.match(r"^(?:ENTRY )?%([\w.\-]+) ", line)
        if m:
            cur = comps.setdefault(m.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    return comps


def _fold_computations(comps):
    """Every computation that runs inside the fold's conditional."""
    todo = []
    for lines in comps.values():
        for line in lines:
            if " conditional(" in line and "/fold/" in line:
                todo += re.findall(
                    r"%([\w.\-]+)",
                    re.search(r"branch_computations=\{([^}]*)\}",
                              line).group(1))
    seen = set()
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += re.findall(r"(?:calls|body|condition|to_apply)=%([\w.\-]+)",
                               line)
            b = re.search(r"branch_computations=\{([^}]*)\}", line)
            if b:
                todo += re.findall(r"%([\w.\-]+)", b.group(1))
    return seen


def _copies(lines):
    """(name, bytes) of every copy instruction among `lines`."""
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\]\S* copy\(",
                     line)
        if m and m.group(2) in _BYTES:
            n = int(np.prod([int(d) for d in m.group(3).split(",") if d]))
            yield m.group(1), n * _BYTES[m.group(2)]


@pytest.mark.parametrize("name", list(TICKS))
def test_decode_tick_moves_no_whole_slot_cache(ticks, name):
    """The tick writes each slot's token into the layer-major slot stack
    in place: its temporaries stay below the stack's own bytes, and no
    copy outside the fold's branch is as large as one layer of any of the
    stack's caches (the stack is not relaid, and no layer is written
    back whole)."""
    compiled, caches = ticks[name]
    leaves = [x for x in jax.tree_util.tree_leaves(caches) if x.ndim > 2]
    state = sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in leaves)
    assert compiled.memory_analysis().temp_size_in_bytes < state
    layer = min(int(np.prod(x.shape[1:])) * x.dtype.itemsize
                for x in leaves)
    comps = _computations(compiled.as_text())
    fold = _fold_computations(comps)
    big = [(c, n, b) for c, lines in comps.items() if c not in fold
           for n, b in _copies(lines) if b >= layer]
    assert not big, big


def test_engine_prefill_chunk_compiles(one_chip, model_shapes):
    cfg, model, params, plan = model_shapes
    st = model.state
    cache = _on(jax.eval_shape(lambda p: st.init_slot(p, MAX_LEN), params),
                one_chip)
    with plan.activation_context():
        compiled = jax.jit(st.resume).lower(
            params,
            jax.ShapeDtypeStruct((1, CHUNK), jnp.int32, sharding=one_chip),
            cache, jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip),
        ).compile()
    _fits_one_chip(compiled)
