"""The BSP FFT driver's cyclic layout: ``cyclic_layout(x, p)``.

The layout is a fixed permutation, ``out[s*(n/p) + l] = x[l*p + s]``,
built from strided slices: the same values as ``x[s::p]`` concatenated
in ``s`` order, without the gather that a step-indexed ``x[s::p]``
lowers to.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.algorithms import bsp_fft
from repro.algorithms.fft import _strided_layout, cyclic_layout
from repro.core import compat

N = 1 << 10


def _input(dtype, seed=0, n=N):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal(n) + 1j * rng.standard_normal(n)
            ).astype(dtype)


@pytest.mark.parametrize("kind", ["jnp", "numpy"])
@pytest.mark.parametrize("dtype", [np.complex64, np.complex128])
@pytest.mark.parametrize("p", [1, 2, 4, 8])
def test_layout_is_the_cyclic_permutation(p, dtype, kind):
    with compat.enable_x64():
        xh = _input(dtype)
        x = jnp.asarray(xh) if kind == "jnp" else xh
        got = np.asarray(cyclic_layout(x, p))
    assert got.dtype == dtype
    np.testing.assert_array_equal(got, xh.reshape(N // p, p).T.reshape(-1))


@pytest.mark.parametrize("kind", ["jnp", "numpy"])
def test_layout_at_one_process_is_the_input(kind):
    xh = _input(np.complex64)
    x = jnp.asarray(xh) if kind == "jnp" else xh
    assert cyclic_layout(x, 1) is x


@pytest.mark.parametrize("p", [2, 4, 8])
def test_layout_lowers_to_strided_slices(p):
    x = jax.ShapeDtypeStruct((N,), jnp.complex64)
    text = _strided_layout.lower(x, p).as_text()
    assert "stablehlo.gather" not in text
    strided = re.findall(rf"stablehlo\.slice %\w+ \[(\d+):{N}:{p}\]", text)
    assert sorted(int(s) for s in strided) == list(range(p))


def test_bsp_fft_under_enclosing_jit(mesh8):
    n = 1 << 12
    x = jnp.asarray(_input(np.complex64, seed=3, n=n))
    y = np.asarray(jax.jit(lambda v: bsp_fft(mesh8, v))(x))
    ref = np.asarray(jnp.fft.fft(x))
    rel = np.linalg.norm(y - ref) / np.linalg.norm(ref)
    assert rel < 1e-4, rel
