"""Mamba2 SSD chunked scan — Pallas TPU kernel.

Grid ``(batch, head_block, chunk)`` with the chunk axis innermost; the
(P × N) recurrent state per head carries across chunks in VMEM scratch.
Per chunk: the intra-chunk term is a decay-gated (T × T) score matmul on
the MXU (scores are shared across heads in the block since Mamba2 uses one
B/C group), the inter-chunk term reads the carried state, and the state
folds the chunk in — identical math to ``repro.models.mamba2._ssd_chunked``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["mamba2_ssd_fwd"]


def _lower_ones(t: int) -> jax.Array:
    """(T, T) inclusive lower-triangular ones: ``_lower_ones(t) @ x`` is
    the prefix sum of ``x`` over rows.  Mosaic has no ``cumsum``
    lowering; the triangular matmul runs on the MXU instead."""
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return (row >= col).astype(jnp.float32)


def _dot(a, b, contract=((1,), (0,)), precision=None):
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


def _kernel(x_ref, dtc_ref, dtr_ref, a_ref, b_ref, c_ref, o_ref, h_scr):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    bm = b_ref[0].astype(jnp.float32)          # (T, N)
    cm = c_ref[0].astype(jnp.float32)          # (T, N)
    hb, t, p = x_ref.shape[1:]
    lower = _lower_ones(t)
    hi = jax.lax.Precision.HIGHEST

    # shared by every head in the block (one B/C group): C_t · B_u
    scores = _dot(cm, bm, ((1,), (1,)))                              # (T, T)
    keep = lower > 0                                                 # u ≤ t

    # heads are a static loop so every value is a 2-D tile: Mosaic cannot
    # lay out the (T, T, HB) pairwise-decay tensor
    for h in range(hb):
        x = x_ref[0, h].astype(jnp.float32)        # (T, P)
        a = a_ref[0, h].astype(jnp.float32)        # (1, 1)
        dt_col = dtc_ref[0, h].astype(jnp.float32)     # (T, 1)
        dt_row = dtr_ref[0, h, 0].astype(jnp.float32)  # (1, T)
        da = dt_col * a                                              # (T, 1) ≤ 0
        cum_col = _dot(lower, da, precision=hi)                      # (T, 1)
        cum_row = _dot(dt_row * a, lower, ((1,), (1,)), precision=hi)  # (1, T)
        # the chunk's total decay, replicated down a column (Mosaic cannot
        # broadcast a (1, 1) value over both tile axes)
        total_t = _dot(jnp.ones((t, t), jnp.float32), da, precision=hi)   # (T, 1)
        total_p = _dot(jnp.ones((p, t), jnp.float32), da, precision=hi)   # (P, 1)

        # intra-chunk: gated[t,u] = (C_t·B_u) exp(cum[t]-cum[u]) dt_u, u ≤ t
        decay = jnp.exp(jnp.minimum(cum_col - cum_row, 0.0))
        gated = jnp.where(keep, scores * decay, 0.0) * dt_row
        y = _dot(gated, x)

        # inter-chunk from carried state: y[t] += exp(cum[t]) C_t · h_prev
        h_prev = h_scr[h]                                            # (P, N)
        y = y + jnp.exp(cum_col) * _dot(cm, h_prev, ((1,), (1,)))

        # state: h = exp(total) h_prev + sum_u exp(total-cum[u]) dt_u x_u B_u^T
        to_end = jnp.exp(total_t - cum_col) * dt_col                 # (T, 1)
        h_scr[h] = jnp.exp(total_p) * h_prev + _dot(x * to_end, bm, ((0,), (0,)))
        o_ref[0, h] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "head_block", "interpret"))
def mamba2_ssd_fwd(
    x: jax.Array,     # (B, S, H, P)
    dt: jax.Array,    # (B, S, H)
    a: jax.Array,     # (H,) negative decay rates
    bmat: jax.Array,  # (B, S, N)
    cmat: jax.Array,  # (B, S, N)
    chunk: int = 64,
    head_block: int = 8,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    if s % chunk or h % head_block:
        raise ValueError(f"S={s} % chunk={chunk} or H={h} % hb={head_block}")
    nc = s // chunk
    nh = h // head_block

    # head-major layouts whose trailing block dims are either whole array
    # dims or 8-row multiples, as Mosaic requires: dt arrives both as
    # per-head columns (B, H, S, 1) and as per-chunk rows (B, H, NC, 1, T)
    xt = x.transpose(0, 2, 1, 3)                     # (B, H, S, P)
    dtt = dt.transpose(0, 2, 1)                      # (B, H, S)
    dt_col = dtt[..., None]
    dt_row = dtt.reshape(b, h, nc, 1, chunk)
    a4 = a.reshape(nh, head_block, 1, 1)

    out = pl.pallas_call(
        _kernel,
        grid=(b, nh, nc),
        in_specs=[
            pl.BlockSpec((1, head_block, chunk, p), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, head_block, chunk, 1), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, head_block, 1, 1, chunk),
                         lambda b_, h_, j: (b_, h_, j, 0, 0)),
            pl.BlockSpec((1, head_block, 1, 1), lambda b_, h_, j: (h_, 0, 0, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, j: (b_, j, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, j: (b_, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, head_block, chunk, p),
                               lambda b_, h_, j: (b_, h_, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), jnp.float32),
        scratch_shapes=[pltpu.VMEM((head_block, p, n), jnp.float32)],
        interpret=interpret,
    )(xt, dt_col, dt_row, a4, bmat, cmat)
    return out.transpose(0, 2, 1, 3)
