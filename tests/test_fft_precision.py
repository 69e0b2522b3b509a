"""Twiddle-precision regression for the BSP FFT.

The time-shifted twiddle ``w_n^{s k2}`` must be computed in the real
dtype matching the input's precision: a float32 phase wraps ``s * k2``
products up to ~p * n, which at n >= 2**16 costs ~1e-3 relative error —
three orders of magnitude above complex128's capability.  (Standalone
from ``test_immortal_algorithms.py`` so it runs without hypothesis.)
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.algorithms import bsp_fft
from repro.core import compat

pytestmark = pytest.mark.slow


def test_fft_complex128_twiddle_precision(mesh8):
    """n = 2**16 complex128 FFT must reach float64-grade accuracy; the
    float32-phase bug sat at ~1e-3 relative error on this input."""
    n = 1 << 16
    with compat.enable_x64():
        rng = np.random.default_rng(0)
        x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)
             ).astype(np.complex128)
        y = np.asarray(bsp_fft(mesh8, jnp.asarray(x)))
        ref = np.fft.fft(x)
        rel = np.abs(y - ref).max() / np.abs(ref).max()
        assert rel < 1e-10, rel


def test_fft_complex64_still_accurate(mesh8):
    """The dtype-dependent phase must not disturb the complex64 path."""
    n = 1 << 12
    rng = np.random.default_rng(1)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)
         ).astype(np.complex64)
    y = np.asarray(bsp_fft(mesh8, jnp.asarray(x)))
    ref = np.fft.fft(x)
    assert np.abs(y - ref).max() / np.abs(ref).max() < 2e-4
