"""jit'd public wrapper for flash attention with a custom VJP.

``flash_attention`` runs the Pallas TPU kernel and differentiates
through the hand-written backward kernels.  ``interpret=True`` runs the
same kernels in the Pallas interpreter (CPU tests); it is never chosen
implicitly.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import kernel as _k

__all__ = ["flash_attention"]


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, causal, window, softcap, scale, block_q, block_k,
           interpret):
    o, _ = _k.flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    return o


def _flash_fwd(q, k, v, causal, window, softcap, scale, block_q, block_k,
               interpret):
    o, lse = _k.flash_attention_fwd(
        q, k, v, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, window, softcap, scale, block_q, block_k, interpret,
               res, do):
    q, k, v, o, lse = res
    dq, dk, dv = _k.flash_attention_bwd(
        q, k, v, o, do, lse, causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=block_q, block_k=block_k, interpret=interpret)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                    causal: bool = True, window: Optional[int] = None,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Flash attention: q [B,H,S,D], k/v [B,Hkv,S,D] -> [B,H,S,D]."""
    return _flash(q, k, v, causal, window, softcap, scale,
                  block_q, block_k, interpret)
