"""jit'd public wrappers around the Pallas kernels.

Every op takes impl in {"pallas", "interpret", "xla"}:
  - "pallas":    compiled Pallas TPU kernel (real hardware target),
  - "interpret": Pallas interpret mode (the CPU-correctness path; runs only
                 when a caller asks for it, as the kernel tests do),
  - "xla":       pure-jnp implementation (paper-faithful reference path).
impl=None picks from the platform JAX compiles for: "pallas" on a TPU and
"xla" everywhere else.

Batching convention: leading dims (B, H, ...) are flattened to one `bh` axis
before the kernel and restored after. GQA is handled by repeating kv heads
to query heads (a deliberate simplicity/VMEM trade-off — keys are small).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.linear_attention import block_causal_linear_attention
from repro.core.poly_attention import poly_attention_full
from repro.kernels import ref as _ref
from repro.kernels.lt_mult import lt_mult_pallas
from repro.kernels.poly_flash import poly_flash_pallas
from repro.kernels.polysketch_causal import (join_state,
                                             polysketch_causal_pallas,
                                             split_state)
from repro.utils import pad_to_multiple

IMPLS = ("pallas", "interpret", "xla")


def resolve_impl(impl: str | None) -> str:
    """The kernel path for `impl`; None means the platform's own."""
    if impl is None:
        return "pallas" if jax.default_backend() == "tpu" else "xla"
    if impl not in IMPLS:
        raise ValueError(f"impl must be one of {IMPLS} or None, got {impl!r}")
    return impl


def _flatten_bh(*xs):
    lead = xs[0].shape[:-2]
    flat = [x.reshape(-1, *x.shape[-2:]) for x in xs]
    return lead, flat


def lt_mult(a, b, c, *, block_size: int = 256, impl: str | None = None):
    """lt(A B^T) C over the last two axes; leading dims are batch."""
    impl = resolve_impl(impl)
    if impl == "xla":
        return _lt_mult_blocked_xla(a, b, c, block_size=block_size)
    lead, (af, bf, cf) = _flatten_bh(a, b, c)
    out = lt_mult_pallas(af, bf, cf, block_size=block_size,
                         interpret=(impl == "interpret"))
    return out.reshape(*lead, *out.shape[-2:])


def _lt_mult_blocked_xla(a, b, c, *, block_size: int):
    """Paper-faithful S3.1 block algorithm in plain XLA ops."""
    n = a.shape[-2]
    blk = min(block_size, n)
    assert n % blk == 0
    t = n // blk
    f32 = jnp.float32
    ab = a.reshape(*a.shape[:-2], t, blk, a.shape[-1]).astype(f32)
    bb = b.reshape(*b.shape[:-2], t, blk, b.shape[-1]).astype(f32)
    cb = c.reshape(*c.shape[:-2], t, blk, c.shape[-1]).astype(f32)
    h = jnp.einsum("...tbm,...tbk->...tmk", bb, cb)
    z = jnp.cumsum(h, axis=-3) - h
    tri = jnp.tril(jnp.ones((blk, blk), f32))
    w = jnp.einsum("...tbm,...tcm->...tbc", ab, bb) * tri
    out = jnp.einsum("...tbc,...tck->...tbk", w, cb)
    out += jnp.einsum("...tbm,...tmk->...tbk", ab, z)
    return out.reshape(*c.shape).astype(c.dtype)


def polysketch_attention(qm, km, q, k, v, *, degree: int, scale: float,
                         local_exact: bool = True, block_size: int = 256,
                         impl: str | None = None, unroll: bool = False,
                         z0=None, return_state: bool = False):
    """Fused causal polysketch attention.

    qm, km: (B, Hq|Hkv, S, r) sketched (pre-scaled) q/k; q: (B, Hq, S, h);
    k, v: (B, Hkv, S, h). Returns (B, Hq, S, h).

    z0: optional (B, Hq|Hkv, r^2, h+1) initial prefix state (kv heads are
    repeated like km) — tokens attend through it as if the folded prefix
    preceded the sequence. With return_state, returns (out, z) where z
    (B, Hq, r^2, h+1) is the state after folding ALL tokens, including a
    final partial block (padded keys contribute exact zeros); callers that
    must keep a partial tail un-folded (decode buffers) split the tail off
    first — see core.decode.polysketch_prefill.
    """
    impl = resolve_impl(impl)
    hq, hkv = q.shape[-3], k.shape[-3]
    if hkv != hq:  # GQA: repeat kv to query heads
        g = hq // hkv
        km = jnp.repeat(km, g, axis=-3) if km.shape[-3] != hq else km
        k = jnp.repeat(k, g, axis=-3)
        v = jnp.repeat(v, g, axis=-3)
        if z0 is not None and z0.shape[-3] != hq:
            z0 = jnp.repeat(z0, g, axis=-3)
    n = q.shape[-2]
    blk = min(block_size, n)
    if impl == "xla":
        if n % blk:
            # zero-pad post-sketch: padded keys contribute zero weight
            qm, km, q, k, v = (pad_to_multiple(x, blk, axis=-2)[0]
                               for x in (qm, km, q, k, v))
        out = block_causal_linear_attention(
            qm, km, v, q, k, degree=degree, scale=scale,
            block_size=blk, local_exact=local_exact, unroll=unroll,
            z0=z0, return_state=return_state)
        if return_state:
            out, z = out
            return out[..., :n, :], z
        return out[..., :n, :]
    qm, _ = pad_to_multiple(qm, blk, axis=-2)
    km, _ = pad_to_multiple(km, blk, axis=-2)
    q, _ = pad_to_multiple(q, blk, axis=-2)
    k, _ = pad_to_multiple(k, blk, axis=-2)
    v, _ = pad_to_multiple(v, blk, axis=-2)
    lead, (qmf, kmf, qf, kf, vf) = _flatten_bh(qm, km, q, k, v)
    zv0 = zd0 = None
    if z0 is not None:
        zv0, zd0 = split_state(
            z0.astype(jnp.float32).reshape(-1, *z0.shape[-2:]))
    out = polysketch_causal_pallas(
        qmf, kmf, qf, kf, vf, zv0, zd0, degree=degree, scale=scale,
        local_exact=local_exact, block_size=blk,
        interpret=(impl == "interpret"), return_state=return_state)
    if return_state:
        out, zv, zd = out
        z = join_state(zv, zd).reshape(*lead, *zv.shape[-2:-1], -1)
        return out.reshape(*lead, *out.shape[-2:])[..., :n, :], z
    out = out.reshape(*lead, *out.shape[-2:])
    return out[..., :n, :]


def poly_attention(q, k, v, *, degree: int, scale: float | None = None,
                   causal: bool = True, block_q: int = 256,
                   block_kv: int = 256, impl: str | None = None):
    """Exact (quadratic) polynomial attention. q,k,v: (B, H, S, h)."""
    impl = resolve_impl(impl)
    if scale is None:
        scale = 1.0 / q.shape[-1]
    hq, hkv = q.shape[-3], k.shape[-3]
    if hkv != hq:
        g = hq // hkv
        k = jnp.repeat(k, g, axis=-3)
        v = jnp.repeat(v, g, axis=-3)
    if impl == "xla":
        return poly_attention_full(q, k, v, degree=degree, scale=scale,
                                   causal=causal)
    # zero-pad to whole blocks: a zero key gets weight <q, 0>^p = 0, and
    # padded query rows are sliced off
    n, t = q.shape[-2], k.shape[-2]
    q, _ = pad_to_multiple(q, min(block_q, n), axis=-2)
    k, _ = pad_to_multiple(k, min(block_kv, t), axis=-2)
    v, _ = pad_to_multiple(v, min(block_kv, t), axis=-2)
    lead, (qf, kf, vf) = _flatten_bh(q, k, v)
    out = poly_flash_pallas(qf, kf, vf, degree=degree, scale=scale,
                            causal=causal, block_q=block_q,
                            block_kv=block_kv,
                            interpret=(impl == "interpret"))
    return out.reshape(*lead, *out.shape[-2:])[..., :n, :]


REFS = {
    "lt_mult": _ref.lt_mult_ref,
    "polysketch_causal": _ref.polysketch_causal_ref,
    "poly_flash": _ref.poly_flash_ref,
}
