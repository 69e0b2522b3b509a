"""``fft_h_bytes`` vs the *measured* ledger — the immortal cost claim.

The BSP FFT's documented cost is (n/p)(p-1)/p * itemsize bytes per
superstep (one redistribution unordered, plus an equal reorder pass when
ordered), with itemsize the complex element width: 8 for complex64, 16
for complex128.  Until now only the precision path was regression-tested
(``test_fft_precision.py``); here the predictor is checked against the
h-relation the executed supersteps actually ledgered, for both dtypes
and both output orders — through the recorded-program path the FFT now
runs on.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.algorithms import bsp_fft
from repro.core import compat
from repro.algorithms.fft import fft_h_bytes

pytestmark = pytest.mark.slow


def _run(mesh8, n, dtype, ordered):
    rng = np.random.default_rng(42)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)).astype(dtype)
    y, ledger = bsp_fft(mesh8, jnp.asarray(x), ordered=ordered,
                        return_ledger=True)
    ref = np.fft.fft(x)
    rel = np.abs(np.asarray(y) - ref).max() / np.abs(ref).max()
    return ledger, rel


@pytest.mark.parametrize("ordered", [True, False])
def test_fft_ledger_matches_h_bytes_complex64(mesh8, ordered):
    n, p = 1024, 8
    ledger, rel = _run(mesh8, n, np.complex64, ordered)
    assert rel < 2e-4
    assert ledger.supersteps == (2 if ordered else 1)
    want = fft_h_bytes(n, p, ordered=ordered, itemsize=8)
    assert ledger.h_bytes == want
    # each superstep is the canonical total exchange: a single fused
    # collective whose wire bytes equal its h-relation
    for r in ledger.records:
        assert r.method == "fused" and r.rounds == 1
        assert r.wire_bytes == r.h_bytes


@pytest.mark.parametrize("ordered", [True, False])
def test_fft_ledger_matches_h_bytes_complex128(mesh8, ordered):
    n, p = 1024, 8
    with compat.enable_x64():
        ledger, rel = _run(mesh8, n, np.complex128, ordered)
    assert rel < 1e-10
    assert ledger.supersteps == (2 if ordered else 1)
    want = fft_h_bytes(n, p, ordered=ordered, itemsize=16)
    assert ledger.h_bytes == want
    assert want == 2 * fft_h_bytes(n, p, ordered=ordered, itemsize=8)
    for r in ledger.records:
        assert r.method == "fused" and r.rounds == 1
