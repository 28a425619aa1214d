"""Polynomial sketches (paper Algorithms 1 & 2, Theorems 1.1 / 2.2 / 2.4).

Implements the recursive Ahle-et-al-style polynomial sketch
``POLYSKETCHWITHNEGATIVITY`` and the paper's non-negative variant
``POLYSKETCHNONNEGATIVE`` (degree-p/2 sketch followed by self-tensoring),
plus the learnable-sketch variant (Appendix D) where every Gaussian
projection is replaced by a small dense network with a tanh squashing.

Conventions
-----------
- ``degree`` below always refers to the *attention* polynomial degree ``p``
  (an even integer, power of two for the recursion). The internal recursion
  runs at degree ``p/2`` per the paper's non-negativity construction.
- The degree-``p/2`` sketch output ``m = x^{(x)p/2} S in R^r`` is what we
  pass around; the r^2-dimensional feature map ``phi'(x) = self_kron(m)``
  is only materialized where needed (<phi'(q), phi'(k)> == <m_q, m_k>^2).
- All attention heads share one sketch per layer (paper Section 4).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.utils import self_kron


def _is_pow2(n: int) -> bool:
    return n >= 1 and (n & (n - 1)) == 0


# ---------------------------------------------------------------------------
# Random sketches (Algorithm 1)
# ---------------------------------------------------------------------------


def init_random_projection(key, in_dim: int, r: int):
    g = jax.random.normal(key, (in_dim, r), dtype=jnp.float32)
    return {"g": g}, {"g": (None, "sketch")}


def apply_random_projection(params, x):
    # Random sketches are *not* trained (paper's "random" variant); the
    # stop_gradient keeps them frozen even though they live in the param tree.
    g = jax.lax.stop_gradient(params["g"]).astype(x.dtype)
    return x @ g


# ---------------------------------------------------------------------------
# Learned sketches (Algorithm 2, Appendix D)
# ---------------------------------------------------------------------------
# f(x): LN -> Dense(8r) -> gelu -> Dense(r) -> LN -> Dense(8r) -> gelu
#       -> Dense(r).  ~8*m*r + 24*r^2 params, matching the paper.


def _dense_init(key, d_in, d_out):
    scale = 1.0 / math.sqrt(d_in)
    w = jax.random.uniform(key, (d_in, d_out), jnp.float32, -scale, scale)
    return w


def init_learned_projection(key, in_dim: int, r: int):
    ks = jax.random.split(key, 4)
    params = {
        "ln0_scale": jnp.ones((in_dim,), jnp.float32),
        "ln0_bias": jnp.zeros((in_dim,), jnp.float32),
        "w1": _dense_init(ks[0], in_dim, 8 * r),
        "b1": jnp.zeros((8 * r,), jnp.float32),
        "w2": _dense_init(ks[1], 8 * r, r),
        "b2": jnp.zeros((r,), jnp.float32),
        "ln1_scale": jnp.ones((r,), jnp.float32),
        "ln1_bias": jnp.zeros((r,), jnp.float32),
        "w3": _dense_init(ks[2], r, 8 * r),
        "b3": jnp.zeros((8 * r,), jnp.float32),
        "w4": _dense_init(ks[3], 8 * r, r),
        "b4": jnp.zeros((r,), jnp.float32),
    }
    axes = {
        "ln0_scale": (None,), "ln0_bias": (None,),
        "w1": (None, "sketch_hidden"), "b1": ("sketch_hidden",),
        "w2": ("sketch_hidden", None), "b2": (None,),
        "ln1_scale": (None,), "ln1_bias": (None,),
        "w3": (None, "sketch_hidden"), "b3": ("sketch_hidden",),
        "w4": ("sketch_hidden", None), "b4": (None,),
    }
    return params, axes


def _ln(x, scale, bias, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def apply_learned_projection(params, x):
    dt = x.dtype
    h = _ln(x, params["ln0_scale"].astype(dt), params["ln0_bias"].astype(dt))
    h = jax.nn.gelu(h @ params["w1"].astype(dt) + params["b1"].astype(dt))
    h = h @ params["w2"].astype(dt) + params["b2"].astype(dt)
    h = _ln(h, params["ln1_scale"].astype(dt), params["ln1_bias"].astype(dt))
    h = jax.nn.gelu(h @ params["w3"].astype(dt) + params["b3"].astype(dt))
    return h @ params["w4"].astype(dt) + params["b4"].astype(dt)


# ---------------------------------------------------------------------------
# Recursive sketch tree
# ---------------------------------------------------------------------------


def init_sketch(key, h: int, r: int, degree: int, learned: bool):
    """Parameters for POLYSKETCH{WITH,NON}NEGATIVE at attention degree p.

    The recursion is built for q = degree/2 (the paper's non-negative
    construction). Returns a (params, axes) pair.
    """
    assert degree % 2 == 0 and degree >= 2, degree
    q = degree // 2
    assert _is_pow2(q), f"degree/2 must be a power of two, got {q}"
    return _init_withneg(key, h, r, q, learned)


def _init_withneg(key, in_dim: int, r: int, q: int, learned: bool):
    if q == 1:
        return {}, {}
    kl, kr, k1, k2 = jax.random.split(key, 4)
    lp, la = _init_withneg(kl, in_dim, r, q // 2, learned)
    rp, ra = _init_withneg(kr, in_dim, r, q // 2, learned)
    proj_in = in_dim if q == 2 else r
    init_proj = init_learned_projection if learned else init_random_projection
    p1, a1 = init_proj(k1, proj_in, r)
    p2, a2 = init_proj(k2, proj_in, r)
    params = {"left": lp, "right": rp, "proj1": p1, "proj2": p2}
    axes = {"left": la, "right": ra, "proj1": a1, "proj2": a2}
    return params, axes


def _apply_withneg(params, x, q: int, learned: bool):
    """POLYSKETCH[WITH]NEGATIVITY / LEARNABLE variant: x -> x^{(x)q} S in R^r."""
    if q == 1:
        return x
    m1 = _apply_withneg(params["left"], x, q // 2, learned)
    m2 = _apply_withneg(params["right"], x, q // 2, learned)
    if learned:
        f1 = apply_learned_projection(params["proj1"], m1)
        f2 = apply_learned_projection(params["proj2"], m2)
        r = f1.shape[-1]
        z = math.sqrt(1.0 / r) * (f1 * f2)
        return math.sqrt(float(r)) * jnp.tanh(z)
    g1 = apply_random_projection(params["proj1"], m1)
    g2 = apply_random_projection(params["proj2"], m2)
    r = g1.shape[-1]
    return math.sqrt(1.0 / r) * (g1 * g2)


def sketch_half(params, x, degree: int, learned: bool):
    """Degree-p/2 sketch m(x) in R^r with <m(q),m(k)>^2 ~= <q,k>^p."""
    with jax.named_scope("sketch"):
        return _apply_withneg(params, x, degree // 2, learned)


def nonneg_features(params, x, degree: int, learned: bool):
    """phi'(x) in R^{r^2}: the paper's non-negative feature map."""
    return self_kron(sketch_half(params, x, degree, learned))


def sketch_param_count(h: int, r: int, degree: int, learned: bool) -> int:
    q = degree // 2
    # projections with input dim h live at the q==2 recursion leaves; all
    # other (inner) nodes project r -> r.
    n_leaf_nodes = q // 2
    n_inner_nodes = (q - 1) - n_leaf_nodes
    n_proj_h = 2 * n_leaf_nodes
    n_proj_r = 2 * n_inner_nodes
    if learned:
        per_h = 2 * h + 8 * h * r + 8 * r + 8 * r * r + r + 2 * r + r * 8 * r + 8 * r + 8 * r * r + r
        per_r = 2 * r + 8 * r * r + 8 * r + 8 * r * r + r + 2 * r + r * 8 * r + 8 * r + 8 * r * r + r
        return n_proj_h * per_h + n_proj_r * per_r
    return n_proj_h * h * r + n_proj_r * r * r
