"""DecodeState protocol: a family-agnostic cache/prefill/snapshot API.

PolySketchFormer's serving story rests on one property: the decode state is
constant-size in context length (the r^2 x (h+1) sketch prefix state). But
that property is not unique to polysketch — SSM / RG-LRU recurrent states
are constant-size too, and even the O(n)/O(W) KV caches share the same
*lifecycle* (init, prefill, decode step, slot stacking). This module makes
that lifecycle a first-class protocol so the serve engine, `generate`, and
the prefix cache never branch on model family or mechanism name:

  - ``StateSpec`` (registry, keyed by state *kind*): one entry per decode
    state kind — ``polysketch``, ``kv_full``, ``poly_kv``, ``kv_ring``,
    ``ssd``, ``rglru`` — declaring how to build the per-layer cache node
    and what it supports (snapshot granularity, resumable prefill). Core
    registers the attention-state kinds below; ``models/ssm.py`` and
    ``models/rglru.py`` register the recurrent kinds on import (the specs
    need their cfg-specific shapes).

  - Node-level snapshot ops, keyed by cache-node *type* (PolysketchCache /
    RecurrentCache / KVCache): ``snapshot_state`` / ``restore_state`` walk
    any model cache pytree and dispatch per node, so a hybrid model's
    cache snapshots correctly with zero model-specific code.

  - ``DecodeState``: the model-level facade (built by ``model_zoo``)
    exposing ``init / init_slot / prefill / resume / decode_step /
    fold_due / snapshot / restore / serialize / deserialize`` plus the
    slot helpers.
    Everything the serve stack needs, independent of family.

Snapshot granularity semantics (per kind, composed over a model's kinds):

  - ``"block"``  — a snapshot of the post-prefill state is valid at the
    last lt_block_size boundary: the partial tail lives in a buffer the
    snapshot simply omits (polysketch).
  - ``"token"`` — the state covers exactly the tokens prefilled so far
    (no tail buffer), so taking a snapshot at a block boundary requires
    *splitting* the prefill there (SSM / RG-LRU / ring KV, whose O(W)
    window is a constant-size suffix state). Snapshots are only
    bit-reproducible at the lt_block_size chunk grid the resumable
    prefills scan over.
  - ``None``    — no constant-size snapshot exists (full KV).

A model mixing kinds gets the weakest member: any ``None`` disables
snapshots; any ``"token"`` member forces the split-at-boundary behavior.
"""
from __future__ import annotations

import io
from dataclasses import dataclass
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.core import decode as dec


# ---------------------------------------------------------------------------
# node-level snapshot ops (dispatch by cache-node type)
# ---------------------------------------------------------------------------

class NodeOps(NamedTuple):
    granularity: str | None          # "block" | "token" | None
    snapshot: Callable | None        # node -> constant-size snapshot pytree
    restore: Callable | None         # (fresh_node, snapshot, n_tokens) -> node
    fold_due: Callable | None = None  # node -> scalar bool; None: never folds
    # (node, fold_due) -> node: after a decode step, fold what it completed
    fold: Callable | None = None


def _psk_snapshot(node: dec.PolysketchCache):
    # valid at block-aligned positions, where the buffers are empty by
    # construction: the folded prefix state is the whole story
    return node.z


def _psk_restore(fresh: dec.PolysketchCache, z, n_tokens):
    pos = jnp.broadcast_to(jnp.asarray(n_tokens, fresh.pos.dtype),
                           fresh.pos.shape)
    return fresh._replace(z=z.astype(fresh.z.dtype), pos=pos)


def _rec_snapshot(node: dec.RecurrentCache):
    # the whole node is constant-size; h covers exactly pos tokens
    return node


def _rec_restore(fresh: dec.RecurrentCache, snap: dec.RecurrentCache,
                 n_tokens):
    del n_tokens  # position lives with the caller, not the node
    return dec.RecurrentCache(h=snap.h.astype(fresh.h.dtype),
                              conv=snap.conv.astype(fresh.conv.dtype))


def _ring_snapshot(node: dec.RingKVCache):
    # the whole node is O(W): the ring holds exactly the last min(pos, W)
    # tokens, which is the entire state a sliding-window resume needs
    return node


def _ring_restore(fresh: dec.RingKVCache, snap: dec.RingKVCache, n_tokens):
    return dec.RingKVCache(
        k=snap.k.astype(fresh.k.dtype), v=snap.v.astype(fresh.v.dtype),
        pos=jnp.broadcast_to(jnp.asarray(n_tokens, fresh.pos.dtype),
                             fresh.pos.shape))


NODE_OPS: dict[type, NodeOps] = {
    dec.PolysketchCache: NodeOps("block", _psk_snapshot, _psk_restore,
                                 dec.polysketch_fold_due,
                                 dec.polysketch_fold),
    dec.RecurrentCache: NodeOps("token", _rec_snapshot, _rec_restore),
    dec.RingKVCache: NodeOps("token", _ring_snapshot, _ring_restore),
    dec.KVCache: NodeOps(None, None, None),
}

_NODE_TYPES = tuple(NODE_OPS)


def is_state_node(x) -> bool:
    return isinstance(x, _NODE_TYPES)


def fold_blocks(state, fold_due=None):
    """After a decode step, fold what it completed in every node of a
    cache pytree (per-node dispatch; kinds with no fold pass through).
    `fold_due`: one unbatched predicate gating every fold (`fold_due` of
    the whole state before the step); None folds on each node's own
    positions."""
    def one(node):
        fn = NODE_OPS[type(node)].fold
        return node if fn is None else fn(node, fold_due)
    return jax.tree_util.tree_map(one, state, is_leaf=is_state_node)


def snapshot_state(state):
    """Constant-size snapshot of a model cache pytree (per-node dispatch).

    Raises for node types with no snapshot support (KV caches)."""
    def snap(node):
        ops = NODE_OPS[type(node)]
        if ops.snapshot is None:
            raise ValueError(
                f"{type(node).__name__} decode state does not support "
                "constant-size snapshots")
        return ops.snapshot(node)
    return jax.tree_util.tree_map(snap, state, is_leaf=is_state_node)


def restore_state(fresh_state, snapshot, n_tokens):
    """Rebuild a cache pytree from a snapshot; `fresh_state` supplies the
    structure/zeros, `n_tokens` the restored position (block-aligned for
    block-granularity nodes)."""
    def rest(node, snap):
        return NODE_OPS[type(node)].restore(node, snap, n_tokens)
    return jax.tree_util.tree_map(rest, fresh_state, snapshot,
                                  is_leaf=is_state_node)


# ---------------------------------------------------------------------------
# per-node sharding axes (mesh-aware serving)
# ---------------------------------------------------------------------------
#
# Each cache-node type declares, per leaf, the tuple of logical axis names
# distributed/sharding.py can partition. The contract is bit-parity under
# resharding: only axes the decode/prefill math never REDUCES over may be
# named (leading batch/slot axes, and the kv-head axis for attention-state
# nodes — every polysketch/KV reduction runs within one head). Everything
# else stays None (replicated), so emitted tokens are bit-identical on any
# mesh shape.

def heads_shard_axes(node):
    """("batch", "kv_heads", ...) for the (B, Hkv, ...) leaves of an
    attention-state node; batch-only for lower-rank leaves; () for the
    scalar pos."""
    def one(x):
        nd = jnp.ndim(x)
        if nd == 0:
            return ()
        if nd >= 4:
            return ("batch", "kv_heads") + (None,) * (nd - 2)
        return ("batch",) + (None,) * (nd - 1)
    return jax.tree_util.tree_map(one, node)


def batch_shard_axes(node):
    """Leading-batch-only axes: the conservative declaration for recurrent
    states whose channel mixing (conv over d_inner+2n channels) crosses
    what a per-head split would cut."""
    def one(x):
        nd = jnp.ndim(x)
        return ("batch",) + (None,) * (nd - 1) if nd else ()
    return jax.tree_util.tree_map(one, node)


# node type -> (node -> same-structure pytree of logical-name tuples);
# populated by register_state from each StateSpec's shard_axes
NODE_SHARD_AXES: dict[type, Callable] = {}


def state_shard_axes(state):
    """Logical-axes pytree mirroring a model cache pytree (per-node
    dispatch through the kind registry's declarations; leaves are tuples
    of logical names, consumable by distributed.sharding.shardings_for).

    A batch-1 cache and the engine's slot stack share one naming: the
    stack's slot axis sits where the batch axis does
    (core.decode.broadcast_slot_caches), so it takes the node's "batch"
    name (slots spread over the "data" mesh axis, while a batch-1 dim
    degrades to replicated via spec_for's divisibility test), and the
    stack's per-slot positions are named like a (batch,) leaf. The layer
    axis of a layer-stacked node is "layers" (replicated)."""
    def node_axes(path, node):
        fn = NODE_SHARD_AXES.get(type(node), batch_shard_axes)
        lead = dec.slot_axis(path)
        if not lead:
            return fn(node)
        # the helpers key off leaf rank, so show them one layer's leaves,
        # then name the layer axis
        inner = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape[lead:], x.dtype), node)

        def is_names(t):
            # NB: cache nodes are NamedTuples (tuples themselves), so the
            # leaf test must check the *elements* are axis names
            return isinstance(t, tuple) and not isinstance(t, type(node)) \
                and all(isinstance(e, (str, type(None))) for e in t)

        return jax.tree_util.tree_map(
            lambda names: ("layers",) * lead + tuple(names),
            fn(inner), is_leaf=is_names)
    return jax.tree_util.tree_map_with_path(node_axes, state,
                                            is_leaf=is_state_node)


# ---------------------------------------------------------------------------
# the kind registry
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class StateSpec:
    """One decode-state kind: how to build it and what it supports."""
    kind: str
    node_type: type
    granularity: str | None     # see module docstring
    resumable: bool             # prefill can continue from a prior state
    init: Callable              # (cfg, batch, max_len, dtype) -> cache node
    # (node) -> same-structure pytree of logical-axis-name tuples naming
    # the partitionable dims (see state_shard_axes); None = batch-only
    shard_axes: Callable | None = None


REGISTRY: dict[str, StateSpec] = {}


def register_state(spec: StateSpec) -> StateSpec:
    REGISTRY[spec.kind] = spec
    if spec.shard_axes is not None:
        NODE_SHARD_AXES[spec.node_type] = spec.shard_axes
    return spec


def get_spec(kind: str) -> StateSpec:
    if kind not in REGISTRY:
        raise KeyError(f"unknown decode-state kind {kind!r}; "
                       f"registered: {sorted(REGISTRY)}")
    return REGISTRY[kind]


register_state(StateSpec(
    kind="polysketch", node_type=dec.PolysketchCache,
    granularity="block", resumable=True,
    init=lambda cfg, batch, max_len, dtype: dec.init_polysketch_cache(
        batch, cfg.n_kv_heads, cfg.resolved_head_dim, cfg.sketch_size,
        cfg.lt_block_size, dtype),
    shard_axes=heads_shard_axes))

register_state(StateSpec(
    kind="kv_full", node_type=dec.KVCache,
    granularity=None, resumable=False,
    init=lambda cfg, batch, max_len, dtype: dec.init_kv_cache(
        batch, cfg.n_kv_heads, cfg.resolved_head_dim, max_len, dtype),
    shard_axes=heads_shard_axes))

register_state(StateSpec(
    kind="poly_kv", node_type=dec.KVCache,
    granularity=None, resumable=False,
    init=lambda cfg, batch, max_len, dtype: dec.init_kv_cache(
        batch, cfg.n_kv_heads, cfg.resolved_head_dim, max_len, dtype),
    shard_axes=heads_shard_axes))

register_state(StateSpec(
    kind="kv_ring", node_type=dec.RingKVCache,
    granularity="token", resumable=True,
    init=lambda cfg, batch, max_len, dtype: dec.init_ring_cache(
        batch, cfg.n_kv_heads, cfg.resolved_head_dim,
        min(cfg.sliding_window, max_len), dtype),
    shard_axes=heads_shard_axes))


def mixer_state_kind(cfg, mixer: str) -> str:
    """The decode-state kind a mixer contributes under this config."""
    if mixer == "attn":
        return {"polysketch": "polysketch", "polynomial": "poly_kv",
                "softmax": "kv_full"}[cfg.attention]
    if mixer == "local_attn":
        return "kv_ring"
    if mixer in ("rglru", "ssd"):
        return mixer
    raise ValueError(f"unknown mixer kind {mixer!r}")


def state_kinds(cfg) -> tuple[str, ...]:
    """Distinct decode-state kinds of a config's block pattern (ordered)."""
    return tuple(dict.fromkeys(
        mixer_state_kind(cfg, m) for m in cfg.block_pattern))


def composite_granularity(kinds) -> str | None:
    """Weakest-member snapshot granularity over a model's state kinds."""
    gs = [get_spec(k).granularity for k in kinds]
    if not gs or any(g is None for g in gs):
        return None
    return "block" if all(g == "block" for g in gs) else "token"


# ---------------------------------------------------------------------------
# snapshot (de)serialization — on-disk persistence seam
# ---------------------------------------------------------------------------

def serialize_snapshot(snapshot, n_tokens: int) -> bytes:
    """Pickle-free encoding: npz of the snapshot's leaves + the position.

    The tree structure is NOT stored — the reader supplies it (the model
    that wrote a snapshot is the only model that can read it, which is
    also enforced by the params fingerprint in serve/prefix_cache.py)."""
    import numpy as np
    buf = io.BytesIO()
    leaves = jax.tree_util.tree_leaves(snapshot)
    np.savez(buf, n_tokens=np.int64(n_tokens),
             **{f"leaf{i}": np.asarray(x) for i, x in enumerate(leaves)})
    return buf.getvalue()


def deserialize_snapshot(data: bytes, treedef):
    """Inverse of serialize_snapshot; returns (snapshot, n_tokens)."""
    import numpy as np
    with np.load(io.BytesIO(data)) as z:
        n = int(z["n_tokens"])
        leaves = [jnp.asarray(z[f"leaf{i}"]) for i in range(len(z) - 1)]
    return jax.tree_util.tree_unflatten(treedef, leaves), n


# ---------------------------------------------------------------------------
# resumed-prefill bucketing
# ---------------------------------------------------------------------------

def bucket_chunks(pos0: int, end: int, block_size: int,
                  max_blocks: int | None = None) -> list[int]:
    """Split [pos0, end) into power-of-two multiples of block_size (largest
    first) plus one final sub-block tail; returns the absolute cut points
    (ascending, last == end).

    Every intermediate cut is block-aligned when pos0 is (the resume
    contract for block-granularity states), and the set of possible chunk
    lengths over ANY workload is {block_size * 2^i} plus the < block_size
    tails — so a jitted per-chunk-length prefill compiles O(log(max_len) +
    block_size) traces instead of one per distinct suffix length.

    ``max_blocks`` caps every chunk at that many blocks (rounded down to a
    power of two, min 1): the overlapped serve scheduler uses it to keep
    each chunk's device time under the per-tick prefill budget, so a long
    prompt becomes a run of equal budget-sized chunks instead of one
    monolithic power-of-two dispatch — same bounded trace set, preemptible
    between every cut."""
    if end <= pos0:
        return []
    cap = None
    if max_blocks is not None:
        cap = 1 << (max(1, max_blocks).bit_length() - 1)
    m, t = divmod(end - pos0, block_size)
    cuts, pos = [], pos0
    while m:
        p = 1 << (m.bit_length() - 1)
        if cap is not None:
            p = min(p, cap)
        pos += p * block_size
        cuts.append(pos)
        m -= p
    if t:
        cuts.append(end)
    return cuts


# ---------------------------------------------------------------------------
# partial prefill: a first-class, schedulable in-flight prefill
# ---------------------------------------------------------------------------

class PartialPrefill(NamedTuple):
    """The carry of a chunked prefill, paused between chunks.

    The overlapped serve scheduler spreads one prompt's prefill across
    many engine ticks; between chunks the in-flight work is exactly this
    value — and because every pause point is on the model's block grid,
    a paused prefill is itself snapshot-able (``partial_snapshot``) and
    therefore evictable: a half-prefilled slot can be shelved as a
    constant-size snapshot and re-materialized later, or handed to
    another request sharing the same prefix.

    state:    the model cache pytree covering the first n_tokens tokens.
    n_tokens: host int; block-aligned at every pause point (only the final
              chunk may land off-grid, and then the prefill is complete).
    logits:   (1, V) last-position logits of the latest chunk (None before
              the first chunk lands).
    """
    state: object
    n_tokens: int
    logits: object = None

    @property
    def started(self) -> bool:
        return self.logits is not None


# ---------------------------------------------------------------------------
# the model-level facade
# ---------------------------------------------------------------------------

class DecodeState:
    """Uniform decode-state surface for one (cfg, apply) pair.

    Everything the serve stack touches goes through here: the engine,
    `generate`, and the prefix cache are written against this class and
    never inspect cfg.family / cfg.attention / mixer kinds themselves.
    All tensor-returning methods are pure and jit-friendly (the engine
    jits thin wrappers around them).
    """

    def __init__(self, cfg, apply_fn, init_fn, init_slot_fn=None):
        self.cfg = cfg
        self.kinds = state_kinds(cfg)
        self._apply = apply_fn
        self._init = init_fn
        self._init_slot = init_slot_fn
        self._snap_treedef = None

    # -- capabilities ------------------------------------------------------

    @property
    def block_size(self) -> int:
        """Snapshot / resumed-prefill grid (multiples of lt_block_size)."""
        return self.cfg.lt_block_size

    @property
    def snapshot_granularity(self) -> str | None:
        return composite_granularity(self.kinds)

    @property
    def resumable(self) -> bool:
        return all(get_spec(k).resumable for k in self.kinds)

    # -- lifecycle ---------------------------------------------------------

    def init(self, params, batch: int, max_len: int):
        return self._init(params, batch, max_len)

    def init_slot(self, params, max_len: int):
        """Batch-1 cache with per-slot scalar positions (serving)."""
        if self._init_slot is not None:
            return self._init_slot(params, max_len)
        return self._init(params, 1, max_len)

    def prefill(self, params, tokens, state=None, *, max_len=None):
        """tokens (B, S) -> (last-position logits (B, V), state).

        Pass a pre-built `state` or `max_len`: KV-cache kinds size their
        buffers at init, and a cache sized to the prompt alone has no
        decode headroom — `dynamic_update_index_in_dim` would silently
        clamp the first decode write onto the last slot."""
        if state is None:
            if max_len is None:
                raise ValueError(
                    "prefill needs max_len (or a pre-built state): a cache "
                    "sized to the prompt length leaves no decode headroom")
            state = self.init(params, tokens.shape[0], max_len)
        logits, state, _ = self._apply(params, {"tokens": tokens},
                                       mode="prefill", cache=state)
        return logits[:, -1], state

    def resume(self, params, tokens, state, pos0):
        """Continue a prefill: `state` already covers the first pos0 tokens
        (block-aligned for block-granularity kinds); this chunk attends
        through it and positions run at the true absolute offsets."""
        positions = jnp.asarray(pos0, jnp.int32) + jnp.arange(tokens.shape[1])
        logits, state, _ = self._apply(params, {"tokens": tokens},
                                       mode="prefill", cache=state,
                                       positions=positions)
        return logits[:, -1], state

    def decode_step(self, params, tok, pos, state, fold_due=None):
        """tok (B, 1) at position `pos` -> (logits (B, V), state). `pos` is
        a scalar shared by the batch (a cache from `init`), or (B,), one
        per row: the engine's slot stack, whose slot axis is the batch
        axis (behind the layer axis of layer-stacked nodes) and whose
        nodes keep a position per slot. `fold_due`, one unbatched
        predicate (`fold_due` of the whole state), gates the block folds;
        None folds on each node's own positions."""
        pos = jnp.asarray(pos, jnp.int32)
        positions = pos[:, None] if pos.ndim else pos[None]
        logits, state, _ = self._apply(params, {"tokens": tok},
                                       mode="decode", cache=state,
                                       positions=positions,
                                       fold_due=fold_due)
        return logits[:, -1], state

    @staticmethod
    def fold_due(state):
        """Scalar bool: the next decode step folds a block somewhere in
        `state`, a cache pytree of any stacking (layers, slots); a
        constant False when no node kind folds."""
        due = jnp.asarray(False)
        for node in jax.tree_util.tree_leaves(state, is_leaf=is_state_node):
            fn = NODE_OPS[type(node)].fold_due
            if fn is not None:
                due = due | fn(node)
        return due

    # -- snapshots ---------------------------------------------------------

    def snapshot(self, state):
        if self.snapshot_granularity is None:
            raise ValueError(
                f"decode state of {self.cfg.name!r} (kinds: "
                f"{'/'.join(self.kinds)}) has no constant-size snapshot")
        return snapshot_state(state)

    def restore(self, fresh_state, snapshot, n_tokens):
        return restore_state(fresh_state, snapshot, n_tokens)

    def serialize(self, snapshot, n_tokens: int) -> bytes:
        return serialize_snapshot(snapshot, n_tokens)

    def deserialize(self, data: bytes):
        if self._snap_treedef is None:
            # structure probe: params are never read by cache init
            probe = self.snapshot(self.init_slot(None, self.block_size))
            self._snap_treedef = jax.tree_util.tree_structure(probe)
        return deserialize_snapshot(data, self._snap_treedef)

    # -- partial prefill (chunked/overlapped admission) --------------------

    def begin_partial(self, params, max_len: int) -> PartialPrefill:
        """A fresh, zero-token partial prefill (cold start)."""
        return PartialPrefill(self.init_slot(params, max_len), 0)

    def advance_partial(self, params, tokens, part: PartialPrefill
                        ) -> PartialPrefill:
        """Run one more chunk; tokens (1, S) continue at part.n_tokens.
        Serving hot paths use the engine's jitted resume instead — this is
        the protocol-level (unjitted) reference path."""
        logits, state = self.resume(params, tokens, part.state,
                                    part.n_tokens)
        return PartialPrefill(state, part.n_tokens + tokens.shape[1], logits)

    def partial_snapshot(self, part: PartialPrefill):
        """Constant-size snapshot of a paused prefill -> (snapshot, pos).
        Valid at block-grid pause points only (which is every pause point
        the scheduler produces)."""
        if part.n_tokens % self.block_size:
            raise ValueError(
                f"partial prefill paused off-grid ({part.n_tokens} tokens, "
                f"block {self.block_size}): not snapshotable")
        return self.snapshot(part.state), part.n_tokens

    def partial_restore(self, params, snapshot, n_tokens: int,
                        max_len: int) -> PartialPrefill:
        """Re-materialize a paused prefill from its snapshot. The restored
        carry has no logits yet (a pause point always has at least one
        chunk left to run, which re-establishes them)."""
        state = self.restore(self.init_slot(params, max_len), snapshot,
                             jnp.asarray(n_tokens, jnp.int32))
        return PartialPrefill(state, int(n_tokens))

    # -- slot stacking (continuous batching) -------------------------------

    @staticmethod
    def broadcast_slots(state, slots: int):
        return dec.broadcast_slot_caches(state, slots)

    @staticmethod
    def slot_scatter(slot_states, state, slot):
        return dec.slot_scatter(slot_states, state, slot)

    @staticmethod
    def slot_gather(slot_states, slot):
        return dec.slot_gather(slot_states, slot)
