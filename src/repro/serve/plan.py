"""ServePlan: the mesh-aware placement contract for the serving stack.

One object answers every "where does this tensor live?" question the
engine has: how params are partitioned (tensor-parallel on the "model"
axis), how the layer-major slot stack and per-slot sampling state spread over
the "data" axis, and what sharding each jitted entry point's inputs and
outputs carry. Single-device serving is the trivial 1x1 plan — the same
code path, with every spec degrading to replicated — so the engine has
no behavior forks.

Bit-parity contract
-------------------
Emitted tokens and logprobs must be bit-identical to the 1-device
engine on every mesh shape. That rules out any sharding that changes a
floating-point reduction's operand order:

* SERVING_RULES shards only the batch/slot dim ("data") and the head
  dims ("model"); every other logical name — including the Megatron
  gather points "act_heads"/"act_mlp" and all contracted dims
  (embed, head_dim, mlp, sketch, vocab) — resolves to () so
  contractions, softmaxes and sketch reductions always run on gathered
  (replicated) operands in a mesh-independent order.
* PARAM_RULES shards only output dims of dense weights (first logical
  axis "embed" after an optional "layers" stacking prefix): wq/wk/wv on
  heads, GLU wi/wg on mlp, lm_head on vocab. Weights whose *input* dim
  would shard (wo, GLU wo, embedding table) stay replicated — XLA would
  otherwise partial-sum the contraction and psum, reordering the FP
  accumulation.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.state import state_shard_axes
from repro.distributed.sharding import (
    activation_sharding, shardings_for, spec_for)

# Logical-name -> mesh-axis candidates for serving-time activations and
# decode state. Anything absent defaults to () (replicated) via
# spec_for's rules.get(name, ()).
SERVING_RULES: dict[str | None, tuple[str, ...]] = {
    None: (),
    "batch": ("data",),
    "q_heads": ("model",),
    "kv_heads": ("model",),
}

# Logical-name -> mesh-axis candidates for parameter tensors (applied
# only to leading-"embed" weights; see param_shardings).
PARAM_RULES: dict[str | None, tuple[str, ...]] = {
    None: (),
    "q_heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "vocab": ("model",),
}


def _is_names(x):
    return isinstance(x, tuple) and all(
        isinstance(e, (str, type(None))) for e in x)


@dataclass(frozen=True)
class ServePlan:
    """Mesh + sharding rules for every jitted serving entry point."""
    mesh: Mesh
    shard_model: bool = False

    # -- construction -----------------------------------------------------

    @classmethod
    def from_mesh(cls, mesh: Mesh, *, shard_model: bool = False):
        if tuple(mesh.axis_names) != ("data", "model"):
            raise ValueError(
                "ServePlan needs a ('data', 'model') mesh, got axes "
                f"{tuple(mesh.axis_names)}; build one with "
                "launch.mesh.make_serving_mesh")
        return cls(mesh=mesh, shard_model=shard_model)

    @classmethod
    def build(cls, data: int = 1, model: int = 1, *,
              shard_model: bool = False):
        devs = np.asarray(jax.devices()[:data * model]).reshape(data, model)
        return cls(mesh=Mesh(devs, ("data", "model")),
                   shard_model=shard_model)

    @classmethod
    def single_device(cls):
        return cls.build(1, 1)

    # -- introspection ----------------------------------------------------

    @property
    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def describe(self) -> str:
        s = self.axis_sizes
        return f"{s['data']}x{s['model']}"

    @property
    def n_devices(self) -> int:
        return self.mesh.devices.size

    # -- shardings --------------------------------------------------------

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def param_shardings(self, params, axes):
        """NamedSharding tree for the params tree.

        Tensor-parallel only when shard_model is set, the "model" axis
        has >1 device, and the logical axes tree is available; only
        weights whose first logical axis (after an optional "layers"
        stacking prefix) is "embed" are candidates — those are the dense
        projections whose *output* dim can split without touching a
        contraction (see module docstring).
        """
        rep = self.replicated()
        msize = self.axis_sizes["model"]
        if not self.shard_model or msize <= 1 or axes is None:
            return jax.tree_util.tree_map(lambda _: rep, params)

        def one(names, w):
            body = names[1:] if names and names[0] == "layers" else names
            if body and body[0] == "embed":
                return NamedSharding(
                    self.mesh,
                    spec_for(names, w.shape, self.mesh, PARAM_RULES))
            return rep

        flat_axes = jax.tree_util.tree_flatten(axes, is_leaf=_is_names)[0]
        flat_w, treedef = jax.tree_util.tree_flatten(params)
        assert len(flat_axes) == len(flat_w), (len(flat_axes), len(flat_w))
        return jax.tree_util.tree_unflatten(
            treedef, [one(a, w) for a, w in zip(flat_axes, flat_w)])

    def state_shardings(self, state):
        """NamedSharding tree for a model cache pytree (a batch-1 cache or
        the engine's slot stack, whose slots spread over "data")."""
        axes = state_shard_axes(state)
        return shardings_for(axes, state, self.mesh, SERVING_RULES)

    def slot_sharding(self, x) -> NamedSharding:
        """Leading-slot-axis tensor (slot tokens/pos/keys/sampling)."""
        names = ("batch",) + (None,) * (np.ndim(x) - 1)
        return NamedSharding(
            self.mesh, spec_for(names, np.shape(x), self.mesh,
                                SERVING_RULES))

    def constrain_logits(self, logits):
        """Pin decode logits to (data-sharded, replicated-vocab) before
        softmax/argmax so the vocab reduction order is mesh-independent."""
        names = ("batch",) + (None,) * (logits.ndim - 1)
        spec = spec_for(names, logits.shape, self.mesh, SERVING_RULES)
        return jax.lax.with_sharding_constraint(
            logits, NamedSharding(self.mesh, spec))

    # -- jit integration --------------------------------------------------

    def activation_context(self):
        """Context manager installing SERVING_RULES for shard_act calls
        inside traced model code."""
        return activation_sharding(self.mesh, SERVING_RULES)

    def wrap(self, jitted):
        """Call-through wrapper entering the activation context on every
        call, so model-code shard_act constraints resolve against this
        plan's mesh at trace time. Forwards the jit cache-size probe the
        RetraceWatchdog relies on."""
        def call(*args, **kwargs):
            with self.activation_context():
                return jitted(*args, **kwargs)

        call._inner = jitted
        probe = getattr(jitted, "_cache_size", None)
        if callable(probe):
            call._cache_size = probe
        return call


__all__ = ["PARAM_RULES", "SERVING_RULES", "ServePlan"]
