"""Plain reference for the GPT-2-small family of the PolySketchFormer paper.

One pre-norm decoder block per layer, written from the paper (Kacham,
Mirrokni, Zhong, "PolySketchFormer", ICML 2024, Sections 2-3 and Appendix
D/H), in straightforward jax.numpy and float32, with no cache, kernel or
batching:

  h   = embed[tokens] * sqrt(d)
  per layer:
    x   = LN1(h);  q, k, v = x Wq, x Wk, x Wv   (RoPE on q, k)
    softmax:    y = softmax(q k^T / sqrt(hd) + causal) v
    polysketch: q, k = LN(q), LN(k) per head;  m = sketch(q / sqrt(hd))
                w_ij = (q_i . k_j / hd)^p      if i, j share a block
                     = (m(q_i) . m(k_j))^2     if j lies in an earlier block
                y_i  = sum_j<=i w_ij v_j / (1 + sum_j<=i w_ij)
    h   = h + y Wo;  h = h + (gelu(LN2(h) Wg) * (LN2(h) Wi)) Wo2
  logits = LN(h) embed^T

The causal block mixture is computed as one masked quadratic sum, so it
shares no algorithm with the program's block scan or decode fold. The
learned sketch of degree 4 (Appendix D) is m(x) = sqrt(r) tanh(f1(x) *
f2(x) / sqrt(r)), each f a LN-Dense-gelu-Dense-LN-Dense-gelu-Dense net.

Weights are made here, from a seed, in the parameter layout the program
names (embed/table, groups/block0/..., final_norm): `init_params` fills a
tree of shapes, and the forward reads leaves by those names. Nothing of the
program is imported.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

EPS = 1e-6


# ---------------------------------------------------------------------------
# weights from a seed
# ---------------------------------------------------------------------------

def _leaf_rule(path: tuple[str, ...], shape, stacked: bool):
    """(kind, std) for one leaf: kind is 'ones', 'normal' or 'table'."""
    name = path[-1]
    core = shape[1:] if stacked else shape
    if name == "table":
        return "normal", 0.02
    if len(core) == 1:
        # norm scales near 1 and biases near 0, but not exactly: a scale
        # or bias the program ignored would then show in the logits
        return ("ones", 0.05) if name.endswith("scale") else ("normal", 0.05)
    if name == "wo" and len(core) == 3:
        fan_in = core[0] * core[1]            # attention out (heads, hd, d)
    else:
        fan_in = core[0]
    return "normal", 1.0 / math.sqrt(fan_in)


def init_params(shapes, base):
    """Fill a tree of ShapeDtypeStructs from the PRNG key `base`. Jit this
    whole call: every leaf is drawn on the device, in its own dtype."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    out = []
    for i, (kp, s) in enumerate(flat):
        path = tuple(str(getattr(k, "key", k)) for k in kp)
        kind, std = _leaf_rule(path, s.shape, path[0] == "groups")
        x = jax.random.normal(jax.random.fold_in(base, i), s.shape,
                              jnp.float32) * std
        if kind == "ones":
            x = 1.0 + x
        out.append(x.astype(s.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# the forward pass
# ---------------------------------------------------------------------------

def _ln(x, scale, bias):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + EPS) * scale + bias


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def _rope(x, pos, theta):
    """x (H, S, hd): rotate the two halves of each head vector."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freq[None, :]
    c, s = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * c - b * s, b * c + a * s], axis=-1)


def _dot(mantissa):
    """einsum whose operands are first rounded to `mantissa` bits (None:
    left in float32); products and sums stay float32."""
    def dot(spec, a, b):
        if mantissa is not None:
            a, b = (jax.lax.reduce_precision(t, exponent_bits=8,
                                             mantissa_bits=mantissa)
                    for t in (a, b))
        return jnp.einsum(spec, a, b)
    return dot


def _sketch_net(dot, p, x):
    h = _ln(x, p["ln0_scale"], p["ln0_bias"])
    h = dot("...i,io->...o", _gelu(dot("...i,io->...o", h, p["w1"])
                                   + p["b1"]), p["w2"]) + p["b2"]
    h = _ln(h, p["ln1_scale"], p["ln1_bias"])
    return dot("...i,io->...o", _gelu(dot("...i,io->...o", h, p["w3"])
                                      + p["b3"]), p["w4"]) + p["b4"]


def _sketch(dot, p, x, r):
    z = _sketch_net(dot, p["proj1"], x) * _sketch_net(dot, p["proj2"], x)
    return math.sqrt(r) * jnp.tanh(z / math.sqrt(r))


def _attention(dot, cfg, mp, x, pos, rows):
    """x (S, d) -> (S, d); queries in blocks of `rows` to bound memory."""
    hd = cfg["head_dim"]
    q = dot("sd,dnh->nsh", x, mp["wq"])
    k = dot("sd,dnh->nsh", x, mp["wk"])
    v = dot("sd,dnh->nsh", x, mp["wv"])
    q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
    s = x.shape[0]
    kind = cfg["attention"]
    if kind == "polysketch":
        if not (cfg["poly_degree"] == 4 and cfg["learned_sketch"]
                and cfg["local_exact"]):
            raise NotImplementedError("reference covers the learned degree-4 "
                                      "sketch with exact local blocks")
        q = _ln(q, mp["pln_q_scale"], mp["pln_q_bias"])
        k = _ln(k, mp["pln_k_scale"], mp["pln_k_bias"])
        scale = 1.0 / hd
        r = cfg["sketch_size"]
        mq = _sketch(dot, mp["sketch"], q * math.sqrt(scale), r)
        mk = _sketch(dot, mp["sketch"], k * math.sqrt(scale), r)
        blk = cfg["lt_block_size"]
    elif kind != "softmax":
        raise NotImplementedError(f"reference has no {kind!r} attention")
    j = jnp.arange(s)

    @jax.checkpoint
    def one_block(i0):
        i = i0 + jnp.arange(rows)
        qi = jax.lax.dynamic_slice_in_dim(q, i0, rows, axis=1)
        causal = j[None, :] <= i[:, None]
        if kind == "softmax":
            a = dot("nih,njh->nij", qi, k) / math.sqrt(hd)
            a = jnp.where(causal, a, -jnp.inf)
            return dot("nij,njh->nih", jax.nn.softmax(a, axis=-1), v)
        mqi = jax.lax.dynamic_slice_in_dim(mq, i0, rows, axis=1)
        exact = (dot("nih,njh->nij", qi, k) * scale) ** 4
        sketched = dot("nir,njr->nij", mqi, mk) ** 2
        same = (j[None, :] // blk) == (i[:, None] // blk)
        w = jnp.where(causal, jnp.where(same, exact, sketched), 0.0)
        num = dot("nij,njh->nih", w, v)
        return num / (1.0 + w.sum(-1, keepdims=True))

    y = jax.lax.map(one_block, jnp.arange(0, s, rows))  # (S/rows,H,rows,hd)
    y = jnp.moveaxis(y, 0, 1).reshape(q.shape)
    return dot("nsh,nhd->sd", y, mp["wo"])


def _block(dot, cfg, bp, h, pos, rows):
    x = _ln(h, bp["norm1"]["scale"], bp["norm1"]["bias"])
    h = h + _attention(dot, cfg, bp["mixer"], x, pos, rows)
    x = _ln(h, bp["norm2"]["scale"], bp["norm2"]["bias"])
    f = bp["ffn"]
    g = _gelu(dot("sd,df->sf", x, f["wg"])) * dot("sd,df->sf", x, f["wi"])
    return h + dot("sf,fd->sd", g, f["wo"])


def forward(cfg: dict, params, tokens, *, rows: int = 512,
            mantissa: int | None = None):
    """Logits (S, V) of one sequence `tokens` (S,), in float32; with
    `mantissa`, every matmul's operands are rounded to that many mantissa
    bits first (the control's lower precision). S must be a multiple of
    `rows` (or below it). Pad at the end: the attention is causal, so
    padding changes no earlier position."""
    if cfg["norm"] != "layernorm" or not cfg["tie_embeddings"]:
        raise NotImplementedError("reference covers LayerNorm, tied "
                                  "embeddings")
    rows = min(rows, tokens.shape[0])
    dot = _dot(mantissa)
    table = params["embed"]["table"]
    h = table[tokens] * math.sqrt(cfg["d_model"])
    pos = jnp.arange(tokens.shape[0])

    @jax.checkpoint
    def layer(h, bp):
        return _block(dot, cfg, bp["block0"], h, pos, rows), None

    h, _ = jax.lax.scan(layer, h, params["groups"])
    h = _ln(h, params["final_norm"]["scale"], params["final_norm"]["bias"])
    return dot("sd,vd->sv", h, table)


def loss(cfg: dict, params, tokens, *, rows: int = 512,
         mantissa: int | None = None):
    """Mean next-token cross-entropy of one row `tokens` (S+1,), from
    `forward`'s logits."""
    logits = forward(cfg, params, tokens[:-1], rows=rows, mantissa=mantissa)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[1:, None], axis=-1).mean()
