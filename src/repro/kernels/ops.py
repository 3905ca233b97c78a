"""jit'd public wrappers for the Pallas kernels (the ``ops.py`` layer).

On TPU these dispatch the compiled kernels; on the CPU backend (tests)
they run in interpret mode.  Any other backend is refused: these kernels
are written for the TPU, and a silent interpreter on an accelerator
would hide that the compiled path never ran.  Model code selects the
backend via ``cfg.attn_impl`` ("xla" | "pallas").
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_fwd
from .decode_attention import decode_attention_fwd
from .rwkv6_scan import rwkv6_wkv_fwd
from .mamba2_ssd import mamba2_ssd_fwd

__all__ = ["flash_attention", "decode_attention", "rwkv6_wkv", "mamba2_ssd", "default_interpret"]


def default_interpret() -> bool:
    """Compiled on TPU, interpreted on CPU; any other backend raises."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels run compiled on TPU or interpreted on CPU; the "
        f"default backend is {backend!r}")


def flash_attention(q, k, v, causal=True, window: Optional[int] = None,
                    block_q: int = 128, block_kv: int = 128):
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_kv=block_kv, interpret=default_interpret(),
    )


def decode_attention(q, k_cache, v_cache, positions, next_pos,
                     window: Optional[int] = None, block_kv: int = 128):
    return decode_attention_fwd(
        q, k_cache, v_cache, positions, next_pos,
        window=window, block_kv=block_kv, interpret=default_interpret(),
    )


def rwkv6_wkv(r, k, v, logw, u, chunk: int = 64):
    return rwkv6_wkv_fwd(r, k, v, logw, u, chunk=chunk, interpret=default_interpret())


def mamba2_ssd(x, dt, a, bmat, cmat, chunk: int = 64, head_block: int = 8):
    return mamba2_ssd_fwd(
        x, dt, a, bmat, cmat, chunk=chunk, head_block=head_block,
        interpret=default_interpret(),
    )
