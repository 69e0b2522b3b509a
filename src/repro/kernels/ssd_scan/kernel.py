"""Mamba-2 SSD (state-space duality) chunked scan — Pallas TPU kernel.

The SSD decomposition (Dao & Gu, arXiv:2405.21060) splits the sequence
into chunks of length L: within a chunk the recurrence is evaluated as a
dense (MXU-friendly) quadratic form; across chunks a [N, P] running state
is carried.  The chunk dimension is the grid's minor-most axis, so the
running state lives in VMEM scratch and flows sequentially — the same
accumulation idiom as the flash-attention kernels.

Per chunk (head h, all f32; per-position vectors are [L, 1] columns):
    dA   = dt * A_h                       [L]
    cum  = cumsum(dA) = tril(1) @ dA      [L]
    Yin  = ((C B^T) o exp(cum_i - cum_j) o (i>=j) o dt_j) x     (intra)
    Yout = (C o exp(cum)_i) state_prev                          (inter)
    state = exp(cum_L) state_prev + (B o (exp(cum_L - cum) dt))^T x
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, contract=((1,), (0,))):
    return jax.lax.dot_general(a, b, (contract, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _ssd_kernel(a_ref, x_ref, dt_ref, b_ref, c_ref, y_ref, state_ref,
                s_scr, *, L: int):
    h = pl.program_id(1)
    c_idx = pl.program_id(2)      # chunk (sequential)
    nc = pl.num_programs(2)

    @pl.when(c_idx == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # [L, P]
    dt = dt_ref[0, 0].astype(jnp.float32)        # [L, 1]
    bmat = b_ref[0, 0].astype(jnp.float32)       # [L, N]
    cmat = c_ref[0, 0].astype(jnp.float32)       # [L, N]
    dA = dt * a_ref[h]                           # [L, 1]

    # every per-position quantity stays a 2-D [L, 1] column; the
    # cumulative sum is a lower-triangular matmul, broadcast over lanes:
    # cum_b[i, j] = cum_i, so cum_b - cum_b.T is the segment sum i..j
    causal = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
              >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    cum_b = _dot(causal.astype(jnp.float32), jnp.broadcast_to(dA, (L, L)))
    cum = cum_b[:, :1]                           # [L, 1]
    total = jnp.sum(dA)                          # scalar cum_L
    seg = jnp.where(causal, cum_b - cum_b.T, -1e30)  # pre-exp clamp
    dt_row = jnp.broadcast_to(dt, (L, L)).T      # [i, j] = dt_j

    # intra-chunk quadratic form
    cb = _dot(cmat, bmat, ((1,), (1,)))                           # [L, L]
    y = _dot(cb * jnp.exp(seg) * dt_row, x)                       # [L, P]

    # inter-chunk contribution from the running state  [N, P]
    state = s_scr[...]
    y += _dot(cmat * jnp.exp(cum), state)

    # state update
    decay_end = jnp.exp(total - cum) * dt                         # [L, 1]
    s_new = jnp.exp(total) * state + _dot(bmat * decay_end, x,
                                          ((0,), (0,)))
    s_scr[...] = s_new
    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(c_idx == nc - 1)
    def _final():
        state_ref[0, 0] = s_new.astype(state_ref.dtype)


def ssd_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
             b: jnp.ndarray, c: jnp.ndarray, *, chunk: int = 128,
             interpret: bool = False):
    """Chunked SSD scan.

    x  [B, S, H, P]   inputs (already dt-free; dt applied inside)
    dt [B, S, H]      positive step sizes (softplus applied by caller)
    a  [H]            negative state decay scalars
    b  [B, S, G, N]   input projections  (G groups, H % G == 0)
    c  [B, S, G, N]   output projections
    Returns (y [B, S, H, P], final_state [B, H, N, P]).
    """
    B, S, H, P = x.shape
    G, N = b.shape[2], b.shape[3]
    L = min(chunk, S)
    nc = pl.cdiv(S, L)
    hg = H // G

    # layout: [B, H, S, *] so (batch, head) are grid-major
    xt = jnp.swapaxes(x, 1, 2)                        # [B, H, S, P]
    dtt = jnp.swapaxes(dt, 1, 2)[..., None]           # [B, H, S, 1]
    bt = jnp.swapaxes(b, 1, 2)                        # [B, G, S, N]
    ct = jnp.swapaxes(c, 1, 2)

    kernel = functools.partial(_ssd_kernel, L=L)
    y, state = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),    # a [H] scalars
            pl.BlockSpec((1, 1, L, P), lambda bb, h, cc: (bb, h, cc, 0)),
            pl.BlockSpec((1, 1, L, 1), lambda bb, h, cc: (bb, h, cc, 0)),
            pl.BlockSpec((1, 1, L, N),
                         lambda bb, h, cc, g=hg: (bb, h // g, cc, 0)),
            pl.BlockSpec((1, 1, L, N),
                         lambda bb, h, cc, g=hg: (bb, h // g, cc, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, L, P), lambda bb, h, cc: (bb, h, cc, 0)),
            pl.BlockSpec((1, 1, N, P), lambda bb, h, cc: (bb, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        interpret=interpret,
    )(a.astype(jnp.float32), xt, dtt, bt, ct)
    return jnp.swapaxes(y, 1, 2), state
