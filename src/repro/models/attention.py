"""Attention implementations for train/prefill and distributed decode.

Three paths:

* ``blocked_attention`` — pure-jnp online-softmax over query chunks
  (flash-pattern memory: never materialises the full [S, S] score
  matrix).  The default train/prefill path; it lowers on any backend and
  GSPMD partitions it cleanly (batch -> data, heads -> model).
* ``repro.kernels.flash_attention`` — the Pallas TPU kernel; selected
  with ``impl='flash'`` on TPU runtimes.
* ``decode_attention`` — single-token decode against a *sequence-sharded*
  KV cache: each model-axis shard computes a partial softmax over its
  chunk of the cache; partials merge with the numerically-stable
  (m, l, o) combine — a textbook LPF superstep (one small all-reduce),
  executed via shard_map over the model axis.  Replicating a 32k cache
  over TP=16 would cost 17 GB/device; sharding costs 67 MB.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import compat

__all__ = ["blocked_attention", "decode_attention", "attention"]


def blocked_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                      causal: bool = True, window: Optional[int] = None,
                      softcap: Optional[float] = None,
                      scale: Optional[float] = None,
                      q_chunk: int = 512) -> jnp.ndarray:
    """q [B, S, H, D]; k/v [B, S, Hkv, D] -> [B, S, H, D].

    Scans over query chunks; scores per step are [B, H, qc, S] — O(S)
    memory in the sequence length, not O(S^2).  Each step is
    checkpointed, so the backward pass recomputes one chunk's scores at a
    time instead of keeping every chunk's (at S = 8192 and 16 heads,
    4.3 GB of f32 scores for one layer)."""
    B, S, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    qc = min(q_chunk, S)
    nq = S // qc if S % qc == 0 else -(-S // qc)
    pad = nq * qc - S
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))

    # GQA without materialising repeated K/V: fold head groups.  Operands
    # stay in the input dtype (bf16); only the score accumulator and the
    # softmax run in f32 — the flash-kernel precision contract, and the
    # difference between ~4 GB and ~40 GB of live attention intermediates
    # on the 8k-wide configs.
    q4 = q.reshape(B, nq, qc, Hkv, group, D)

    def step(carry, inp):
        i, qch = inp                                  # qch [B, qc, Hkv, g, D]
        s = jnp.einsum("bqhgd,bkhd->bhgqk", qch, k,
                       preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = i * qc + jnp.arange(qc)
        k_pos = jnp.arange(S)
        mask = jnp.ones((qc, S), bool)
        if causal:
            mask &= q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= (q_pos[:, None] - k_pos[None, :]) < window
        s = jnp.where(mask[None, None, None], s, -1e30)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.sum(p, axis=-1, keepdims=True)
        p = (p / jnp.maximum(l, 1e-30)).astype(v.dtype)
        o = jnp.einsum("bhgqk,bkhd->bqhgd", p, v,
                       preferred_element_type=jnp.float32)
        return carry, o.astype(v.dtype)

    _, outs = lax.scan(jax.checkpoint(step, prevent_cse=False), 0,
                       (jnp.arange(nq), jnp.moveaxis(q4, 1, 0)))
    Dv = v.shape[-1]
    out = jnp.moveaxis(outs, 0, 1).reshape(B, nq * qc, H, Dv)
    if pad:
        out = out[:, :S]
    return out.astype(q.dtype)


def _partial_softmax(q, k, v, scale, softcap, valid=None):
    """Partial attention stats over a cache chunk.
    q [B, H, D]; k/v [B, Sc, Hkv, D] -> (m, l, o) with o unnormalised.
    ``valid`` [Sc] bool masks cache slots not yet written."""
    B, H, D = q.shape
    Hkv = k.shape[2]
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, D).astype(jnp.float32) * scale
    s = jnp.einsum("bhgd,bkhd->bhgk", qg, k.astype(jnp.float32))
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    if valid is not None:
        s = jnp.where(valid[None, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1, keepdims=True)                  # [B,Hkv,g,1]
    p = jnp.exp(s - m)
    if valid is not None:
        p = jnp.where(valid[None, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    o = jnp.einsum("bhgk,bkhd->bhgd", p, v.astype(jnp.float32))
    return m, l, o


def merge_partials(m1, l1, o1, m2, l2, o2):
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(m1 - m)
    a2 = jnp.exp(m2 - m)
    return m, a1 * l1 + a2 * l2, a1 * o1 + a2 * o2


def decode_attention(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, k_new: jnp.ndarray,
                     v_new: jnp.ndarray, *, mesh,
                     seq_axes: Tuple[str, ...] = ("model",),
                     batch_axes: Tuple[str, ...] = ("data",),
                     softcap: Optional[float] = None,
                     window: Optional[int] = None,
                     scale: Optional[float] = None,
                     pos=None) -> jnp.ndarray:
    """One-token decode against a seq-sharded cache with distributed merge.

    q [B, H, D]; {k,v}_cache [B, S, Hkv, D] sharded (batch->batch_axes,
    S->seq_axes); {k,v}_new [B, 1, Hkv, D].  Returns [B, H, D].

    Sliding-window caches are assumed pre-rolled (the cache holds the
    last ``window`` positions), so all cache entries participate.
    """
    B, H, D = q.shape
    Hkv = k_cache.shape[2]
    scale_v = scale if scale is not None else 1.0 / math.sqrt(D)
    batch_axes = tuple(batch_axes) or None
    seq_axes = tuple(seq_axes)

    def body(qb, kc, vc, kn, vn):
        valid = None
        if pos is not None:
            # global slot index of my cache chunk across the seq shards
            Sc = kc.shape[1]
            shard = lax.axis_index(seq_axes if len(seq_axes) > 1
                                   else seq_axes[0])
            k_pos = shard * Sc + jnp.arange(Sc)
            valid = k_pos < pos
        m, l, o = _partial_softmax(qb, kc, vc, scale_v, softcap, valid)
        # merge across the sequence shards
        mg = lax.pmax(m, seq_axes)
        corr = jnp.exp(m - mg)
        l = lax.psum(l * corr, seq_axes)
        o = lax.psum(o * corr, seq_axes)
        # fold in the new token (replicated over seq shards)
        m2, l2, o2 = _partial_softmax(qb, kn, vn, scale_v, softcap)
        mf, lf, of = merge_partials(mg, l, o, m2, l2, o2)
        out = of / jnp.maximum(lf, 1e-30)
        return out.reshape(qb.shape[0], H, D).astype(qb.dtype)

    bspec = P(batch_axes)
    return compat.shard_map(
        body, mesh=mesh,
        in_specs=(P(batch_axes, None, None),
                  P(batch_axes, seq_axes, None, None),
                  P(batch_axes, seq_axes, None, None),
                  P(batch_axes, None, None, None),
                  P(batch_axes, None, None, None)),
        out_specs=P(batch_axes, None, None),
        check_vma=False,
    )(q, k_cache, v_cache, k_new, v_new)


def attention(q, k, v, *, impl: str = "blocked", causal=True, window=None,
              softcap=None, scale=None, q_chunk: int = 512):
    """Dispatch train/prefill attention by implementation name."""
    if impl == "flash":
        from repro.kernels.flash_attention.ops import flash_attention
        # kernel layout is [B, H, S, D]
        o = flash_attention(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                            jnp.swapaxes(v, 1, 2), causal=causal,
                            window=window, softcap=softcap, scale=scale)
        return jnp.swapaxes(o, 1, 2)
    if impl == "reference":
        from repro.kernels.flash_attention.ref import attention_ref
        o = attention_ref(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                          jnp.swapaxes(v, 1, 2), causal=causal,
                          window=window, softcap=softcap, scale=scale)
        return jnp.swapaxes(o, 1, 2)
    return blocked_attention(q, k, v, causal=causal, window=window,
                             softcap=softcap, scale=scale, q_chunk=q_chunk)
