"""Complex-array wrapper for the local FFT kernel."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import kernel as _k

__all__ = ["fft", "ifft"]


def _run(x: jnp.ndarray, inverse: bool, interpret: bool):
    x = jnp.asarray(x)
    if not jnp.issubdtype(x.dtype, jnp.complexfloating):
        x = x.astype(jnp.complex64)
    shape = x.shape
    n = shape[-1]
    xr = jnp.real(x).astype(jnp.float32).reshape(-1, n)
    xi = jnp.imag(x).astype(jnp.float32).reshape(-1, n)
    yr, yi = _k.fft_planes(xr, xi, inverse=inverse, interpret=interpret)
    return jax.lax.complex(yr, yi).reshape(shape).astype(
        jnp.complex64 if x.dtype != jnp.complex128 else x.dtype)


def fft(x: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """FFT along the last axis (power-of-two length)."""
    return _run(x, False, interpret)


def ifft(x: jnp.ndarray, *, interpret: bool = False) -> jnp.ndarray:
    """Inverse FFT along the last axis (power-of-two length)."""
    return _run(x, True, interpret)
