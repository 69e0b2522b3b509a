"""Process-local FFT Pallas kernel — the paper's FFT compute hot-spot.

Four-step (Bailey) FFT as matrix products, so the work lands on the MXU
and every operand is a lane-dense 2-D tile.  A row of length
``n = n1 * n2`` is viewed row-major as ``A[j1, j2] = x[n2 * j1 + j2]``;
then

    B = F1 @ A                 (n1-point DFTs down the columns)
    C = B * W,  W[k1, j2] = w_n^(j2 k1)          (twiddle)
    D = C @ F2                 (n2-point DFTs along the rows)
    X[k1 + n1 k2] = D[k1, k2]

so the naturally ordered result is ``D`` transposed, ``[n2, n1]``
row-major.  The DFT matrices ``F1`` [n1, n1], ``F2`` [n2, n2] and the
twiddle ``W`` [n1, n2] are built on the host in float64 and passed in as
inputs; they stay resident in VMEM across the grid.  Complex values
travel as separate re/im f32 planes (Mosaic has no complex dtype), and
every product runs at ``Precision.HIGHEST`` (f32 accuracy on the MXU).

The wrapper does the only reshapes, ``[batch, n] -> [batch, n1, n2]`` in
and ``[batch, n2, n1] -> [batch, n]`` out, both free row-major views in
HBM.  The batch dimension streams through the grid, ``rows_per_block``
rows per step.  For ``n = 2**15`` the split is ``n1 = 128, n2 = 256``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_HI = jax.lax.Precision.HIGHEST


def split(n: int) -> tuple[int, int]:
    """``(n1, n2)`` with ``n1 * n2 == n`` and ``n2 = 2**ceil(log2(n)/2)``."""
    assert n >= 1 and n & (n - 1) == 0, \
        f"four-step kernel needs power-of-two n, got {n}"
    n2 = 1 << (n.bit_length() // 2)
    return n // n2, n2


def twiddles(n: int, inverse: bool) -> tuple[np.ndarray, ...]:
    """``(F1r, F1i, Wr, Wi, F2r, F2i)`` as float32 planes; the inverse
    transform folds its ``1/n`` into ``F2``."""
    n1, n2 = split(n)
    sign = 1.0 if inverse else -1.0

    def expi(num, den):
        ang = sign * 2.0 * np.pi * (num % den) / den
        return np.cos(ang), np.sin(ang)

    k1 = np.arange(n1)[:, None]
    f1 = expi(k1 * np.arange(n1)[None, :], n1)
    w = expi(k1 * np.arange(n2)[None, :], n)
    j2 = np.arange(n2)[:, None]
    f2 = expi(j2 * np.arange(n2)[None, :], n2)
    scale = 1.0 / n if inverse else 1.0
    f2 = (f2[0] * scale, f2[1] * scale)
    return tuple(p.astype(np.float32) for p in (*f1, *w, *f2))


def _cmatmul(ar, ai, br, bi):
    """Complex product of re/im planes: four real MXU matmuls."""
    dot = functools.partial(jnp.dot, precision=_HI,
                            preferred_element_type=jnp.float32)
    return dot(ar, br) - dot(ai, bi), dot(ar, bi) + dot(ai, br)


def _fft_kernel(f1r_ref, f1i_ref, wr_ref, wi_ref, f2r_ref, f2i_ref,
                xr_ref, xi_ref, or_ref, oi_ref, *, rows: int):
    def row(r, carry):
        br, bi = _cmatmul(f1r_ref[...], f1i_ref[...], xr_ref[r], xi_ref[r])
        wr, wi = wr_ref[...], wi_ref[...]
        cr = br * wr - bi * wi
        ci = br * wi + bi * wr
        dr, di = _cmatmul(cr, ci, f2r_ref[...], f2i_ref[...])
        or_ref[r] = dr.T
        oi_ref[r] = di.T
        return carry

    jax.lax.fori_loop(0, rows, row, 0)


def fft_planes(re: jnp.ndarray, im: jnp.ndarray, *, inverse: bool = False,
               rows_per_block: int = 4, interpret: bool = False):
    """Batched FFT on separate f32 planes: re/im [batch, n] -> (re, im)."""
    batch, n = re.shape
    n1, n2 = split(n)
    rb = min(rows_per_block, batch)
    tw = [jnp.asarray(t) for t in twiddles(n, inverse)]
    const = lambda shape: pl.BlockSpec(shape, lambda i: (0, 0))
    rows_in = pl.BlockSpec((rb, n1, n2), lambda i: (i, 0, 0))
    rows_out = pl.BlockSpec((rb, n2, n1), lambda i: (i, 0, 0))
    out = jax.ShapeDtypeStruct((batch, n2, n1), jnp.float32)
    yr, yi = pl.pallas_call(
        functools.partial(_fft_kernel, rows=rb),
        grid=(pl.cdiv(batch, rb),),
        in_specs=[const((n1, n1)), const((n1, n1)),
                  const((n1, n2)), const((n1, n2)),
                  const((n2, n2)), const((n2, n2)),
                  rows_in, rows_in],
        out_specs=[rows_out, rows_out],
        out_shape=[out, out],
        interpret=interpret,
    )(*tw, re.reshape(batch, n1, n2), im.reshape(batch, n1, n2))
    return yr.reshape(batch, n), yi.reshape(batch, n)
