"""Fused causal PolySketch attention Pallas TPU kernel.

One kernel fuses the whole of paper Sections 3.1 + 3.2:
  - diagonal block: exact degree-p polynomial weights (or the (L R^T)^2
    sketched form when local_exact=False),
  - off-diagonal prefix: the r^2-dimensional non-negative feature map
    phi'(x) = m^{(x)2}, carried as the paper's prefix state
       Zv[i*r + j, d] = sum_s m_s[i] m_s[j] v_s[d]     (r^2, h) f32
       Zd[0, i*r + j] = sum_s m_s[i] m_s[j]            (1, r^2) f32
    which is exactly core.decode's z split into its value columns and its
    denominator column (laid out as a row).

The self-tensoring is built on the MXU: phi'(m) = (m @ E1) * (m @ E2), where
the constant 0/1 matrices E1[i, i*r + j] = 1 and E2[j, i*r + j] = 1 repeat
and tile the r sketch columns out to r^2 lanes. Each output element is one
product with 1.0, so at HIGHEST precision the features are exact, and no
vector reshape (which Mosaic cannot lower for a lane dim of r*h) is needed.
Every other operation is a plain 2-D matmul, an elementwise op or a
reduction.

The grid is (batch*kv_heads, n/b); TPU executes grid steps in order with the
last axis fastest, so the scratch state is reset at t == 0 and carried
across the sequence exactly like the paper's prefix sum Z_l. Inside a step
the b-token block is walked in row tiles of at most 256 tokens, so no
intermediate is larger than (256, max(b, r^2)) f32 — 1 MiB at b = r^2 =
1024 — and the causal diagonal block computes only its lower triangle of
tiles.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_ROW_TILE = 256
_VMEM_LIMIT = 48 * 2**20   # of the 128 MiB a v5e core has
_HI = jax.lax.Precision.HIGHEST


def _kernel(e1_ref, e2_ref, qm_ref, km_ref, q_ref, k_ref, v_ref, zv0_ref,
            zd0_ref, o_ref, zv_out_ref, zd_out_ref, zv_ref, zd_ref, *,
            degree: int, scale: float, local_exact: bool, tile: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        # seed the VMEM state from the caller's initial prefix state (zeros
        # for a cold run, a restored snapshot for a resumed prefill)
        zv_ref[...] = zv0_ref[0].astype(jnp.float32)
        zd_ref[...] = zd0_ref[0].astype(jnp.float32)

    f32 = jnp.float32
    blk = qm_ref.shape[1]
    e1 = e1_ref[...]
    e2 = e2_ref[...]

    def features(m):
        # phi'(m) = self_kron(m): (rows, r) -> (rows, r^2), exact
        return (jnp.dot(m, e1, precision=_HI, preferred_element_type=f32)
                * jnp.dot(m, e2, precision=_HI, preferred_element_type=f32))

    def contract_last(a, b):
        return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                                   preferred_element_type=f32)

    for s0 in range(0, blk, tile):
        # rows [s0, end) of the block attend to the block's keys [0, end)
        end = s0 + tile
        qm = qm_ref[0, s0:end].astype(f32)                    # (tile, r)
        v = v_ref[0, :end].astype(f32)                        # (end, h)

        # ---- diagonal block (exact local polynomial attention, S3.2) ----
        if local_exact:
            w = contract_last(q_ref[0, s0:end].astype(f32),
                              k_ref[0, :end].astype(f32)) * scale
            w = w ** degree
        else:
            w = contract_last(qm, km_ref[0, :end].astype(f32))
            w = w * w
        rows = jax.lax.broadcasted_iota(jnp.int32, w.shape, 0) + s0
        cols = jax.lax.broadcasted_iota(jnp.int32, w.shape, 1)
        w = jnp.where(rows >= cols, w, 0.0)
        num = jax.lax.dot(w, v, preferred_element_type=f32)   # (tile, h)
        den = jnp.sum(w, axis=-1, keepdims=True)              # (tile, 1)

        # ---- cross-block sketched prefix ----
        qf = features(qm)                                     # (tile, r^2)
        num += jax.lax.dot(qf, zv_ref[...], preferred_element_type=f32)
        den += jnp.sum(qf * zd_ref[...], axis=-1, keepdims=True)

        o_ref[0, s0:end] = (num / (1.0 + den)).astype(o_ref.dtype)

    # ---- state update: fold this block's keys into the prefix ----
    # (after every row tile has read the state of the earlier blocks)
    for s0 in range(0, blk, tile):
        kf = features(km_ref[0, s0:s0 + tile].astype(f32))   # (tile, r^2)
        zv_ref[...] += jax.lax.dot_general(
            kf, v_ref[0, s0:s0 + tile].astype(f32),
            (((0,), (0,)), ((), ())), preferred_element_type=f32)
        zd_ref[...] += jnp.sum(kf, axis=0, keepdims=True)

    # surface the carried state; the block index is constant in t, so the
    # write at the final grid step is what lands in HBM — the state after
    # folding every block, which a resumed call feeds back as zv0/zd0
    zv_out_ref[0] = zv_ref[...]
    zd_out_ref[0] = zd_ref[...]


def split_state(z):
    """(..., r^2, h+1) prefix state -> the kernel's (zv (..., r^2, h),
    zd (..., 1, r^2)): value columns, and the denominator column as a row."""
    return z[..., :-1], jnp.swapaxes(z[..., -1:], -1, -2)


def join_state(zv, zd):
    """Inverse of split_state."""
    return jnp.concatenate([zv, jnp.swapaxes(zd, -1, -2)], axis=-1)


@functools.partial(
    jax.jit,
    static_argnames=("degree", "scale", "local_exact", "block_size",
                     "interpret", "return_state"))
def polysketch_causal_pallas(qm, km, q, k, v, zv0=None, zd0=None, *,
                             degree: int, scale: float,
                             local_exact: bool = True, block_size: int = 256,
                             interpret: bool = False,
                             return_state: bool = False):
    """qm, km: (bh, n, r); q, k, v: (bh, n, h) -> (bh, n, h).

    n must be divisible by block_size (pad at the ops layer with zero keys —
    zero sketched/raw keys contribute zero attention weight).

    zv0 (bh, r^2, h) / zd0 (bh, 1, r^2): optional initial prefix state (see
    split_state) — a snapshot-resumed prefill attends through it exactly as
    if the folded tokens preceded the sequence. When return_state, also
    returns (zv, zd): the state after folding every block, ready to be fed
    back as (zv0, zd0).
    """
    bh, n, r = qm.shape
    h = v.shape[-1]
    rr = r * r
    blk = min(block_size, n)
    assert n % blk == 0, (n, blk)
    tile = math.gcd(blk, _ROW_TILE)
    if zv0 is None:
        zv0 = jnp.zeros((bh, rr, h), jnp.float32)
    if zd0 is None:
        zd0 = jnp.zeros((bh, 1, rr), jnp.float32)
    eye = jnp.eye(r, dtype=jnp.float32)
    e1 = jnp.repeat(eye, r, axis=1)          # e1[i, i*r + j] = 1
    e2 = jnp.tile(eye, (1, r))               # e2[j, i*r + j] = 1
    grid = (bh, n // blk)
    kernel = functools.partial(_kernel, degree=degree, scale=scale,
                               local_exact=local_exact, tile=tile)
    const_spec = pl.BlockSpec((r, rr), lambda i, t: (0, 0))
    seq_spec = lambda d: pl.BlockSpec((1, blk, d), lambda i, t: (i, t, 0))
    state_spec = lambda shp: pl.BlockSpec((1, *shp), lambda i, t: (i, 0, 0))
    out, zv, zd = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[const_spec, const_spec,
                  seq_spec(r), seq_spec(r), seq_spec(h), seq_spec(h),
                  seq_spec(h), state_spec((rr, h)), state_spec((1, rr))],
        out_specs=[seq_spec(h), state_spec((rr, h)), state_spec((1, rr))],
        out_shape=[
            jax.ShapeDtypeStruct((bh, n, h), v.dtype),
            jax.ShapeDtypeStruct((bh, rr, h), jnp.float32),
            jax.ShapeDtypeStruct((bh, 1, rr), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((rr, h), jnp.float32),
            pltpu.VMEM((1, rr), jnp.float32),
        ],
        # double-buffered blocks plus one row tile's intermediates come to
        # ~17 MiB at b = r^2 = 1024, past the 16 MiB default scoped limit
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=_VMEM_LIMIT),
        interpret=interpret,
    )(e1, e2, qm, km, q, k, v, zv0, zd0)
    return (out, zv, zd) if return_state else out
