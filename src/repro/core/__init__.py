"""PolySketchFormer core: sketches, polynomial attention, causal combine."""
from repro.core.sketches import (
    init_sketch, sketch_half, nonneg_features, sketch_param_count,
)
from repro.core.poly_attention import (
    qk_layernorm, poly_attention_full, softmax_attention_full,
)
from repro.core.linear_attention import (
    block_causal_linear_attention, noncausal_linear_attention,
)
from repro.core.decode import (
    PolysketchCache, KVCache, RecurrentCache, init_polysketch_cache,
    polysketch_decode_step, polysketch_fold, polysketch_prefill,
    init_kv_cache, kv_decode_step, kv_ring_decode_step, poly_kv_decode_step,
    broadcast_slot_caches, slot_scatter, slot_gather,
)
from repro.core.state import (
    DecodeState, StateSpec, register_state, get_spec, state_kinds,
    mixer_state_kind, composite_granularity, snapshot_state, restore_state,
    serialize_snapshot, deserialize_snapshot, bucket_chunks, is_state_node,
)

__all__ = [
    "init_sketch", "sketch_half", "nonneg_features", "sketch_param_count",
    "qk_layernorm", "poly_attention_full", "softmax_attention_full",
    "block_causal_linear_attention", "noncausal_linear_attention",
    "PolysketchCache", "KVCache", "RecurrentCache", "init_polysketch_cache",
    "polysketch_decode_step", "polysketch_fold", "polysketch_prefill",
    "init_kv_cache",
    "kv_decode_step", "kv_ring_decode_step", "poly_kv_decode_step",
    "broadcast_slot_caches", "slot_scatter", "slot_gather",
    "DecodeState", "StateSpec", "register_state", "get_spec", "state_kinds",
    "mixer_state_kind", "composite_granularity", "snapshot_state",
    "restore_state", "serialize_snapshot", "deserialize_snapshot",
    "bucket_chunks", "is_state_node",
]
