"""Logical-axis sharding rules -> PartitionSpec.

Every parameter carries a tuple of logical axis names (one per dim). A
rules table maps each logical name to an ordered list of candidate mesh
axes; assignment is greedy per tensor: the first candidate that (a) exists
in the mesh, (b) is not already used by another dim of the same tensor, and
(c) divides the dimension size, wins. This makes sharding hillclimbs a
one-line rules edit and automatically degrades (e.g. kv_heads=1 simply
stays replicated).

Defaults implement FSDP("data") x TP("model") with DP over ("pod","data"):
  - embed dim       -> data   (FSDP/ZeRO-3: params+opt state sharded; XLA
                               emits all-gather on use / reduce-scatter on
                               gradients)
  - heads/mlp/vocab/experts/rnn -> model (TP/EP)
  - head_dim        -> model fallback when heads don't divide (e.g. 40H/16)
"""
from __future__ import annotations

from typing import Any

import jax
import numpy as np
from jax.experimental.layout import Layout, with_layout_constraint
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DEFAULT_RULES: dict[str | None, tuple[str, ...]] = {
    None: (),
    "batch": ("pod", "data"),       # special-cased: multi-axis
    "seq": (),                      # flip to ("data",) for sequence parallel
    "vocab": ("model",),
    "embed": ("data",),
    "q_heads": ("model",),
    "kv_heads": ("model",),
    "head_dim": ("model",),
    "mlp": ("model",),
    # pre-contraction activation gather points (models/attention.py _out,
    # models/layers.py glu_ffn_apply): training keeps them sharded on
    # "model" (Megatron: contract the sharded axis, psum after); serving
    # rules map them to () so the contraction runs on gathered operands
    # and FP summation order never depends on the mesh (bit-parity)
    "act_heads": ("model",),
    "act_mlp": ("model",),
    "experts": ("model",),
    "rnn": ("model",),
    "rnn2": (),
    "sketch": (),
    "sketch_hidden": (),
    "layers": (),
    "state": (),
}


def mesh_axis_sizes(mesh: Mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def spec_for(names: tuple, shape: tuple, mesh: Mesh,
             rules: dict | None = None) -> P:
    rules = rules or DEFAULT_RULES
    sizes = mesh_axis_sizes(mesh)
    used: set[str] = set()
    out: list[Any] = []
    for name, dim in zip(names, shape):
        cands = rules.get(name, ())
        if name == "batch":
            axes = [a for a in cands if a in sizes and a not in used]
            group: list[str] = []
            prod = 1
            for a in axes:
                if dim % (prod * sizes[a]) == 0:
                    group.append(a)
                    prod *= sizes[a]
            used.update(group)
            out.append(tuple(group) if len(group) > 1 else (group[0] if group else None))
            continue
        pick = None
        for a in cands:
            if a in sizes and a not in used and dim % sizes[a] == 0:
                pick = a
                break
        if pick:
            used.add(pick)
        out.append(pick)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def _is_names(x):
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


def shardings_for(axes_tree, shapes_tree, mesh: Mesh, rules=None):
    """Tree of NamedShardings for a params tree.

    axes_tree mirrors shapes_tree with tuples of logical names as leaves.
    """
    def one(names, shaped):
        return NamedSharding(mesh, spec_for(names, shaped.shape, mesh, rules))

    flat_axes = jax.tree_util.tree_flatten(axes_tree, is_leaf=_is_names)[0]
    flat_shapes, treedef = jax.tree_util.tree_flatten(shapes_tree)
    assert len(flat_axes) == len(flat_shapes), \
        (len(flat_axes), len(flat_shapes))
    leaves = [one(a, s) for a, s in zip(flat_axes, flat_shapes)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def batch_spec(mesh: Mesh, batch_size: int, ndim: int = 2,
               rules=None) -> P:
    rules = rules or DEFAULT_RULES
    sizes = mesh_axis_sizes(mesh)
    group: list[str] = []
    prod = 1
    for a in rules["batch"]:
        if a in sizes and batch_size % (prod * sizes[a]) == 0:
            group.append(a)
            prod *= sizes[a]
    lead = tuple(group) if len(group) > 1 else (group[0] if group else None)
    return P(lead, *([None] * (ndim - 1)))


def batch_shardings(mesh: Mesh, specs: dict, rules=None) -> dict:
    return {k: NamedSharding(
        mesh, batch_spec(mesh, v.shape[0], v.ndim, rules))
        for k, v in specs.items()}


def replicated(mesh: Mesh):
    return NamedSharding(mesh, P())


# ---------------------------------------------------------------------------
# Activation sharding constraints (XLA's propagation gives up inside layer
# scans and replicates; explicit constraints at block boundaries keep every
# intermediate partitioned — the MaxText pattern).
# ---------------------------------------------------------------------------
import contextlib

_ACT_CTX: list = []


@contextlib.contextmanager
def activation_sharding(mesh: Mesh, rules: dict | None = None):
    _ACT_CTX.append((mesh, rules or DEFAULT_RULES))
    try:
        yield
    finally:
        _ACT_CTX.pop()


def shard_act(x, *logical_names):
    """Constrain an activation to the logical spec; no-op outside the
    activation_sharding context (single-device tests)."""
    if not _ACT_CTX or not hasattr(x, "shape"):
        return x
    mesh, rules = _ACT_CTX[-1]
    names = tuple(logical_names) + (None,) * (x.ndim - len(logical_names))
    spec = spec_for(names, x.shape, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def device_layouts(tree, axes_tree):
    """The layout in which the active mesh's device holds each leaf's
    shard by default, where that device tiles its arrays (TPU); None for
    other leaves, and None outside the activation context. A loop that
    carries such leaves keeps them in it (`keep_layouts`): left to choose,
    the compiler may carry them in another layout, and relay the whole
    tree at the loop's entry and exit."""
    if not _ACT_CTX:
        return None
    mesh, rules = _ACT_CTX[-1]
    dev = mesh.devices.flat[0]

    def one(names, x):
        shard = NamedSharding(mesh, spec_for(names, x.shape, mesh, rules)
                              ).shard_shape(x.shape)
        lay = Layout.from_pjrt_layout(
            dev.client.get_default_layout(np.dtype(x.dtype), shard, dev))
        return lay if lay.tiling else None

    flat_axes = jax.tree_util.tree_flatten(axes_tree, is_leaf=_is_names)[0]
    flat, treedef = jax.tree_util.tree_flatten(tree)
    return jax.tree_util.tree_unflatten(
        treedef, [one(a, x) for a, x in zip(flat_axes, flat)])


def keep_layouts(tree, layouts):
    """Constrain each leaf of `tree` to its layout in `layouts` (from
    `device_layouts`; None leaves, or None, constrain nothing)."""
    if layouts is None:
        return tree
    return jax.tree_util.tree_map(
        lambda x, lay: x if lay is None else with_layout_constraint(x, lay),
        tree, layouts, is_leaf=lambda x: x is None)
