"""Model operations per token, from the configuration's shapes alone.

A multiply-add counts 2. Matmuls count 2 x their parameters per token;
attention counts what the published algorithm needs, whatever the program
does to compute it:

  softmax      q.k and the weighted sum of v over the causal context
  polysketch   the learned sketch nets on q and k (Appendix D), the
               r^2-feature readout of the prefix state and its update
               (each 2 r^2 (hd+1) per head), and the exact local block,
               counted at its causal average

Training counts 3x the forward (forward and backward); rematerialised
recompute does not count.
"""
from __future__ import annotations


def _sketch_net_flops(in_dim: int, r: int) -> int:
    # Dense(in->8r), Dense(8r->r), Dense(r->8r), Dense(8r->r)
    return 2 * (in_dim * 8 * r + 8 * r * r + r * 8 * r + 8 * r * r)


def matmul_flops_per_token(c: dict) -> int:
    d, h, hd, ff = c["d_model"], c["n_heads"], c["head_dim"], c["d_ff"]
    kv = c.get("n_kv_heads", h)
    per_layer = 2 * (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff)
    return c["n_layers"] * per_layer + 2 * d * c["vocab_size"]


def attention_flops(c: dict, local_ctx: float, full_ctx: float) -> float:
    """One token's attention over all layers: `full_ctx` keys for softmax,
    `local_ctx` keys of its own block for polysketch."""
    h, hd = c["n_heads"], c["head_dim"]
    if c["attention"] == "softmax":
        per_head = 2 * hd * full_ctx + 2 * hd * full_ctx
    elif c["attention"] == "polysketch":
        r = c["sketch_size"]
        sketch = 2 * 2 * _sketch_net_flops(hd, r)   # q and k, two nets each
        state = 2 * (2 * r * r * (hd + 1))          # readout and update
        local = 2 * hd * local_ctx + 2 * (hd + 1) * local_ctx
        per_head = sketch + state + local
    else:
        raise ValueError(f"no operation count for {c['attention']!r}")
    return c["n_layers"] * h * per_head


def forward_flops_per_token(c: dict, seq: int) -> float:
    """Mean forward FLOPs per token of one causal sequence of `seq`."""
    b = min(c.get("lt_block_size", seq), seq)
    return (matmul_flops_per_token(c)
            + attention_flops(c, (b + 1) / 2, (seq + 1) / 2))


def train_flops_per_token(c: dict, seq: int) -> float:
    return 3 * forward_flops_per_token(c, seq)


def decode_flops(c: dict, pos: int) -> float:
    """Forward FLOPs of the token at position `pos` (0-based) in decode;
    the polysketch fold is the state update, one block's worth every
    block, so it is counted per token as the update term."""
    b = c.get("lt_block_size", 1)
    return (matmul_flops_per_token(c)
            + attention_flops(c, pos % b + 1, pos + 1))
