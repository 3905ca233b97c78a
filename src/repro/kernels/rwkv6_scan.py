"""RWKV6 WKV chunked scan — Pallas TPU kernel.

Grid ``(batch, head, chunk)`` with the chunk axis innermost: the (dk × dv)
recurrent state lives in VMEM scratch and carries across chunks (TPU grids
execute the trailing axis sequentially).  Per chunk the kernel computes the
intra-chunk pairwise-decay attention term on the VPU plus the inter-chunk
state read on the MXU, then folds the chunk into the state — the same math as
``repro.models.rwkv6._wkv_chunked``, validated against the step-by-step
oracle ``rwkv6_recurrent``.

TPU adaptation notes: the (T × T × dk) pairwise-decay tensor of the jnp
path is never materialized — the kernel loops over source positions
through f32 VMEM tiles of (T, dk), and chunk-local prefix sums are
triangular-ones matmuls (Mosaic has no ``cumsum``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rwkv6_wkv_fwd"]


# Largest sub-tile the f32 carry accumulation folds at once.  Chunk-local
# cumulative log-decays grow linearly with the tile length; at 64 positions
# of strong decay the exponents reach O(±200) and the f32 cancellation
# ``cum_tm1[t] - cum[u]`` costs ~1e-5 absolute in the exponent — enough to
# drift the carried state past the 2e-4 oracle tolerance.  Folding the
# state through ≤32-wide tiles keeps the same order of operations as the
# step-by-step reference within f32 rounding, independent of block size.
_STATE_TILE = 32


def _prefix_ones(t: int, strict: bool = False) -> jax.Array:
    """(T, T) lower-triangular ones: ``_prefix_ones(t) @ x`` is the
    inclusive prefix sum of ``x`` over rows (``strict``: exclusive).
    Mosaic has no ``cumsum`` lowering; a triangular matmul runs on the
    MXU instead, and at HIGHEST precision it keeps f32 accuracy."""
    row = jax.lax.broadcasted_iota(jnp.int32, (t, t), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (t, t), 1)
    return ((row > col) if strict else (row >= col)).astype(jnp.float32)


def _tri_sum(a: jax.Array, b: jax.Array) -> jax.Array:
    return jnp.dot(a, b, preferred_element_type=jnp.float32,
                   precision=jax.lax.Precision.HIGHEST)


def _fold_tile(r, k, v, lw, u, s):
    """One ≤32-wide tile: (y, s_new) for f32 (T, K) inputs, (1, K) bonus
    ``u`` and (K, K) state.

    The state-fold decay ``exp(sum_{j>u} lw_j)`` is computed from a direct
    suffix sum, not ``total - cum[u]`` — the latter cancels two large
    prefix sums and loses the low bits of exactly the exponents that matter
    (late positions, where the factor is near 1).
    """
    t = r.shape[0]
    lower = _prefix_ones(t)
    cum = _tri_sum(lower, lw)                          # inclusive prefix
    cum_tm1 = cum - lw                                 # exclusive prefix

    # intra-tile: y[t] = sum_{u<t} (r_t·exp(cum_tm1[t]-cum[u])·k_u) v_u
    #           + (r_t·diag(u)·k_t) v_t
    # pairwise log-domain form: exponents are ≤ 0 for every kept (t, u)
    # pair, so no overflow for arbitrarily strong decay.  The loop runs
    # over source positions u (static, ≤ 32) so every value stays a 2-D
    # (T, K) tile: Mosaic cannot lay out the (T, T, K) pairwise tensor.
    rows = jax.lax.broadcasted_iota(jnp.int32, (t, 1), 0)
    y = jnp.sum(r * u * k, axis=1, keepdims=True) * v
    for j in range(t - 1):
        decay = jnp.exp(jnp.minimum(cum_tm1 - cum[j:j + 1], 0.0))   # (T, K)
        a_col = jnp.sum(r * k[j:j + 1] * decay, axis=1, keepdims=True)
        y = y + jnp.where(rows > j, a_col, 0.0) * v[j:j + 1]

    # inter-tile: y[t] += (r_t * exp(cum_tm1[t])) @ S
    y = y + jnp.dot(r * jnp.exp(cum_tm1), s,
                    preferred_element_type=jnp.float32)

    # suffix[u] = sum_{j>u} lw[j]: strict upper-triangular sum, no
    # large-sum cancellation
    suffix = _tri_sum(_prefix_ones(t, strict=True).T, lw)
    # per-key total decay as a (K, 1) column, to scale the state's rows
    total = _tri_sum(lw.T, jnp.ones((t, 1), jnp.float32))
    kw = k * jnp.exp(suffix)                           # (T, K), exponents ≤ 0

    # state update: S = diag(exp(total)) S + sum_u (k_u exp(suffix[u])) v_u^T
    s_new = s * jnp.exp(total) + jnp.dot(
        kw.T, v, preferred_element_type=jnp.float32
    )
    return y, s_new


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_scr, *, ts: int):
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)      # (T, K)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)    # (T, K) log decay ≤ 0
    u = u_ref[0].astype(jnp.float32)         # (1, K)

    t = r.shape[0]
    s = s_scr[...]
    ys = []
    for i in range(0, t, ts):               # static unrolled sub-tile loop
        sl = slice(i, i + ts)
        y_i, s = _fold_tile(r[sl], k[sl], v[sl], lw[sl], u, s)
        ys.append(y_i)
    s_scr[...] = s

    o_ref[0, 0] = jnp.concatenate(ys, axis=0).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def rwkv6_wkv_fwd(
    r: jax.Array,      # (B, S, H, K)
    k: jax.Array,
    v: jax.Array,
    logw: jax.Array,   # (B, S, H, K), ≤ 0
    u: jax.Array,      # (H, K)
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    b, s, h, dk = r.shape
    if s % chunk:
        raise ValueError(f"seq {s} not divisible by chunk {chunk}")
    nc = s // chunk
    if chunk > _STATE_TILE and chunk % _STATE_TILE:
        # gcd would silently degenerate to tiny tiles (chunk=40 → ts=8,
        # chunk=33 → ts=1) and explode the unrolled fold loop
        raise ValueError(
            f"chunk {chunk} must be <= {_STATE_TILE} or a multiple of it"
        )
    ts = min(chunk, _STATE_TILE)

    def prep(x):
        return x.transpose(0, 2, 1, 3)     # (B, H, S, K)

    kernel = functools.partial(_kernel, ts=ts)
    out = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, j: (b_, h_, j, 0)),
            pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, j: (b_, h_, j, 0)),
            # (1, 1, K) blocks of the (H, 1, K) bonus: the trailing two
            # block dims equal the array's, as Mosaic requires
            pl.BlockSpec((1, 1, dk), lambda b_, h_, j: (h_, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, dk), lambda b_, h_, j: (b_, h_, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, dk), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, dk), jnp.float32)],
        interpret=interpret,
    )(prep(r), prep(k), prep(v), prep(logw), u.reshape(h, 1, dk))
    return out.transpose(0, 2, 1, 3)
