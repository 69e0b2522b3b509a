"""The BSP FFT driver's cyclic layout: ``cyclic_layout(x, p)``, and
how ``bsp_fft`` places it on its processes.

The layout is a fixed permutation, ``out[s*(n/p) + l] = x[l*p + s]``,
built from strided slices: the same values as ``x[s::p]`` concatenated
in ``s`` order, without the gather that a step-indexed ``x[s::p]``
lowers to.  ``bsp_fft`` hands process ``s`` the slice ``x[s::p]`` alone,
as its shard of a ``P(axes)``-sharded vector.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.algorithms import bsp_fft
from repro.algorithms import fft as fft_mod
from repro.algorithms.fft import _strided_layout, cyclic_layout
from repro.core import LPF_SYNC_DEFAULT, compat, exec_
from repro.core import context as context_mod

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


#: (mesh shape, mesh axes, the axes bsp_fft runs over)
MESHES = [((2,), ("x",), ("x",)), ((4,), ("x",), ("x",)),
          ((8,), ("x",), ("x",)),
          ((2, 2, 2), ("pod", "data", "model"), ("pod", "data", "model")),
          ((2, 2, 2), ("pod", "data", "model"), ("data", "model"))]

#: where the input lives: on the first device, committed to another
#: device, in host memory, or already spread over the processes
INPUTS = ["first_device", "other_device", "numpy", "sharded"]


@pytest.fixture()
def handed(monkeypatch):
    """Every argument bsp_fft hands exec_, with its keyword arguments."""
    seen = []

    def spy(mesh, spmd, args, **kw):
        seen.append((args, kw))
        return exec_(mesh, spmd, args, **kw)

    monkeypatch.setattr(fft_mod, "exec_", spy)
    return seen


def _placed(kind, xh, mesh, axes):
    if kind == "first_device":
        return jnp.asarray(xh)
    if kind == "other_device":
        return jax.device_put(xh, jax.devices()[-1])
    if kind == "numpy":
        return xh
    return jax.device_put(xh, NamedSharding(mesh, P(axes)))


@pytest.mark.parametrize("kind", INPUTS)
@pytest.mark.parametrize("shape,names,axes", MESHES,
                         ids=["p2", "p4", "p8", "p8_3axes", "p4_2of3axes"])
def test_each_process_receives_only_its_cyclic_slice(handed, shape, names,
                                                     axes, kind):
    mesh = compat.make_mesh(shape, names, devices=jax.devices()[:8])
    p = int(np.prod([mesh.shape[a] for a in axes]))
    n, npp = 1 << 10, (1 << 10) // p
    xh = _input(np.complex64, seed=p, n=n)
    y = bsp_fft(mesh, _placed(kind, xh, mesh, axes), axes=axes)

    (arg, kw), = handed
    assert kw["in_specs"] == P(axes)
    if kind in ("first_device", "other_device"):
        # laid out on the input's device, each slice sent to its own
        # processes: the array exec_ receives is already placed
        assert arg.sharding == NamedSharding(mesh, P(axes))
        assert len(arg.addressable_shards) == mesh.size
        for shard in arg.addressable_shards:
            s = shard.index[0].start // npp
            np.testing.assert_array_equal(np.asarray(shard.data), xh[s::p])
    else:
        np.testing.assert_array_equal(np.asarray(arg).reshape(p, npp),
                                      xh.reshape(npp, p).T)

    # the kept executable's parameter is one n/p slice per device
    spmd = fft_mod._fft_exec_spmd(n, p, True, False, LPF_SYNC_DEFAULT,
                                  False)
    compiled = [v[0] for k, v in context_mod._EXEC_CACHE.items()
                if k[0] is spmd][-1]
    (sharding,), _ = compiled.input_shardings
    assert not sharding.is_fully_replicated
    assert sharding.shard_shape((n,)) == (npp,)
    entry = compiled.as_text().split("ENTRY", 1)[1]
    assert re.search(rf"= c64\[{npp}\]\S* parameter\(0\)", entry)

    # bit for bit what the replicated placement gives: every process
    # receives the whole layout and keeps its own block
    def replicated(ctx, s, pp, xt):
        return spmd(ctx, s, pp, xt.reshape(pp, npp)[s])

    ref = exec_(mesh, replicated, jnp.asarray(cyclic_layout(xh, p)),
                axes=axes, out_specs=P(axes))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(ref))
    np.testing.assert_allclose(np.asarray(y), np.fft.fft(xh), rtol=0,
                               atol=1e-3 * np.abs(np.fft.fft(xh)).max())
