"""The decode tick's necessary work, from shapes: the least any program
must do to emit one token for each active slot.

Bytes: every weight read once per tick; per active slot and layer,
  polysketch  read the prefix state z (heads x r^2 x (hd+1)), read the
              filled part of the local buffers (keys, values, sketched
              keys), write one row of each, and 1/block of a fold (read
              the block's sketched keys and values, read and write z)
  softmax     read the cache up to the slot's position, write one row
Operations: work/model_flops.decode_flops per slot.

Bound = max(operations / peak FLOP/s, bytes / peak bandwidth); `bound_by`
says which. All tensors are counted at 4 bytes (the configuration's
float32).
"""
from __future__ import annotations

import os

import bench_harness

model_flops = bench_harness.load_module(
    os.path.join(os.path.dirname(os.path.abspath(__file__)),
                 "model_flops.py"))

F32 = 4


def param_count(c: dict) -> int:
    d, h, hd, ff = c["d_model"], c["n_heads"], c["head_dim"], c["d_ff"]
    kv = c.get("n_kv_heads", h)
    layer = 4 * d + d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    if c["attention"] == "polysketch":
        r = c["sketch_size"]

        def net(i):
            return (2 * i + i * 8 * r + 8 * r + 8 * r * r + r + 2 * r
                    + r * 8 * r + 8 * r + 8 * r * r + r)
        layer += 4 * hd + 2 * net(hd)
    return c["n_layers"] * layer + c["vocab_size"] * d + 2 * d


def slot_bytes(c: dict, pos: int) -> float:
    """State bytes one slot at position `pos` must move in one tick."""
    h, hd = c.get("n_kv_heads", c["n_heads"]), c["head_dim"]
    if c["attention"] == "polysketch":
        r, b = c["sketch_size"], c["lt_block_size"]
        z = r * r * (hd + 1)
        row = 2 * hd + r
        fill = pos % b
        fold = (b * (hd + r) + 2 * z) / b
        per_head = z + fill * row + row + fold
    elif c["attention"] == "softmax":
        per_head = 2 * hd * pos + 2 * hd
    else:
        raise ValueError(f"no byte count for {c['attention']!r}")
    return c["n_layers"] * h * per_head * F32


def necessary(c: dict, positions, peaks: dict) -> dict:
    flops = sum(model_flops.decode_flops(c, p) for p in positions)
    nbytes = param_count(c) * F32 + sum(slot_bytes(c, p) for p in positions)
    t_flops = flops / peaks["flops_bf16"]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return {"flops": flops, "bytes": nbytes,
            "bound_s": max(t_flops, t_bytes),
            "bound_by": "bytes" if t_bytes >= t_flops else "flops"}
