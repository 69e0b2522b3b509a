"""Plain reference for a 1-D complex forward DFT, independent of the
program under test.

It uses no FFT library and nothing from ``repro``: the transform is a
mixed-radix Cooley-Tukey recursion whose every stage is a dense DFT
matrix product, and whose twiddle factors are built on the host in
float64 from exact integer phases.  A complex array is held as a real
array ``[2, ...]`` (real part, imaginary part), and a complex matrix
product as one real product that contracts over both the component and
the index, so the precision is explicit: ``dtype=float32`` is the
reference (matmuls at ``HIGHEST``); ``dtype=bfloat16`` is the control,
the same computation one precision step below the complex64 that the
configuration states.

With ``n = a * b``, input index ``j = b*j1 + j2`` and output index
``k = k1 + a*k2``:

    X[k1 + a k2] = sum_j2 w_b^(j2 k2) * w_n^(j2 k1) * sum_j1 x[b j1 + j2] w_a^(j1 k1)

so one stage is a length-``a`` DFT down the columns of ``x.reshape(a, b)``,
a twiddle, and a length-``b`` transform along each row (recursively).
:func:`rows` yields the output block by block of rows ``k1`` so that the
whole of it never has to sit on the device beside the program's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: largest DFT done as one dense matrix product
LEAF = 1024


def _turns(num: np.ndarray, m: int) -> np.ndarray:
    """exp(-2 pi i num / m) in float64; the integer phase is reduced
    first, so large products lose no bits."""
    return np.exp(-2j * np.pi * (np.mod(num, m) / m))


def _parts(z: np.ndarray) -> np.ndarray:
    return np.stack([z.real, z.imag])


@functools.lru_cache(maxsize=None)
def _dft(m: int) -> np.ndarray:
    """[d, c, k, j]: the complex DFT matrix as a real map of components
    c -> d."""
    k = np.arange(m, dtype=np.int64)
    w = _turns(np.outer(k, k), m)
    return np.stack([np.stack([w.real, -w.imag]),
                     np.stack([w.imag, w.real])])


def twiddle_tables(a: int, b: int, k1: np.ndarray) -> tuple:
    """w_(a*b)^(k1 * j2) for the given k1 and j2 < b, as the product of
    two small float64-exact tables (j2 = j2h * bl + j2l):
    ``hi [2, len(k1), bh]`` and ``lo [2, len(k1), bl]``."""
    m = a * b
    bl = min(b, LEAF)
    k1 = np.asarray(k1, np.int64)[:, None]
    hi = _turns(k1 * (np.arange(b // bl, dtype=np.int64) * bl)[None, :], m)
    lo = _turns(k1 * np.arange(bl, dtype=np.int64)[None, :], m)
    return _parts(hi), _parts(lo)


def _cmul(z, t):
    return jnp.stack([z[0] * t[0] - z[1] * t[1], z[0] * t[1] + z[1] * t[0]])


def _twiddle(z, hi, lo):
    """z [2, ..., r, b] times hi[:, r, bh] (x) lo[:, r, bl]."""
    t = _cmul(hi[:, :, :, None], lo[:, :, None, :])
    return _cmul(z, t.reshape(*t.shape[:2], -1))


def _precision(dtype):
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32
            else jax.lax.Precision.DEFAULT)


def _fft_last(z, dtype):
    """DFT along the last axis of z [2, ..., m]."""
    m = z.shape[-1]
    if m <= LEAF:
        return jnp.einsum("c...j,dckj->d...k", z, jnp.asarray(_dft(m), dtype),
                          precision=_precision(dtype))
    a, b = LEAF, m // LEAF
    z = z.reshape(*z.shape[:-1], a, b)
    z = jnp.einsum("c...jb,dckj->d...kb", z, jnp.asarray(_dft(a), dtype),
                   precision=_precision(dtype))
    hi, lo = twiddle_tables(a, b, np.arange(a))
    z = _twiddle(z, jnp.asarray(hi, dtype), jnp.asarray(lo, dtype))
    z = _fft_last(z, dtype)                              # [2, ..., a, b]
    return jnp.swapaxes(z, -1, -2).reshape(*z.shape[:-2], m)


@functools.partial(jax.jit, static_argnames=("a", "dtype"))
def _columns(x, a, dtype):
    """Stage one: length-a DFT down the columns of x.reshape(a, b)."""
    z = jnp.stack([jnp.real(x), jnp.imag(x)]).astype(dtype).reshape(2, a, -1)
    return jnp.einsum("cjb,dckj->dkb", z, jnp.asarray(_dft(a), dtype),
                      precision=_precision(dtype))


@functools.partial(jax.jit, static_argnames=("size",))
def _row_block(z, lo_k, hi, lo, size):
    """Twiddle and transform ``size`` rows of stage one's output from row
    ``lo_k``: X[k1 + a*k2] for those k1 and all k2, complex64."""
    blk = jax.lax.dynamic_slice_in_dim(z, lo_k, size, axis=1)
    y = _fft_last(_twiddle(blk, hi, lo), z.dtype)
    return jax.lax.complex(y[0].astype(jnp.float32), y[1].astype(jnp.float32))


def rows(x: jax.Array, dtype=jnp.float32, block: int = 64):
    """Yield ``(k1, X[k1 + a*k2])`` for consecutive blocks of rows k1.

    ``x`` is a complex64 vector of power-of-two length on one device.
    Output element ``X[k]`` with ``k = k1 + a*k2`` is row ``k1``, column
    ``k2`` of what is yielded."""
    n = int(x.shape[0])
    if n <= LEAF:
        z = jnp.stack([jnp.real(x), jnp.imag(x)]).astype(dtype)
        y = _fft_last(z, dtype).astype(jnp.float32)
        yield np.arange(n), jax.lax.complex(y[0], y[1])[:, None]
        return
    a, b = LEAF, n // LEAF
    z = _columns(x, a, dtype)
    block = min(block, a)
    for k0 in range(0, a, block):
        ks = np.arange(k0, k0 + block)
        hi, lo = twiddle_tables(a, b, ks)
        yield ks, _row_block(z, k0, jnp.asarray(hi, dtype),
                             jnp.asarray(lo, dtype), block)


def fft(x: jax.Array, dtype=jnp.float32, block: int = 64) -> jax.Array:
    """The whole transform at once (small sizes)."""
    parts = [blk for _, blk in rows(x, dtype, block)]
    return jnp.concatenate(parts, axis=0).T.reshape(-1)
