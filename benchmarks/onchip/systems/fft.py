"""The immortal FFT's cells: ``repro.algorithms.bsp_fft(mesh, x)``.

Set-up makes one complex64 input of length ``2^log2n`` on the first
chip from the seed, in one jitted call.  The timed call is the user's
eager call, ``bsp_fft(mesh, x)`` with ordered output, over a mesh of the
mix's ``devices`` chips.  One output of the window, drawn from the seed
(reservoir sampling), is kept and compared after the window with the
plain reference (``references/fft.py``, float32 at ``HIGHEST``) on the
first chip: the relative L2 error and the largest error relative to the
largest output.  The control puts the same reference, computed in
bfloat16, in the program's place.
"""

from __future__ import annotations

import functools
import math
import random

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import cells
from seeding import prng_key

R = cells.load_module(cells.HERE / "references" / "fft.py")


@functools.partial(jax.jit, static_argnames=("n",))
def _make_input(key, n: int):
    re, im = jax.random.normal(key, (2, n), jnp.float32)
    return jax.lax.complex(re, im)


@functools.partial(jax.jit, static_argnames=("a", "size"))
def _gaps(y, ref, k0, a: int, size: int):
    """Squared error, squared reference, largest error and largest
    reference magnitude of output columns k0 .. k0+size of y.reshape(b, a)
    against the reference rows ``ref [size, b]``."""
    got = jax.lax.dynamic_slice_in_dim(y.reshape(-1, a), k0, size, axis=1).T
    d = jnp.abs(got - ref)
    r = jnp.abs(ref)
    return jnp.stack([jnp.sum(d * d), jnp.sum(r * r), jnp.max(d), jnp.max(r)])


class System:
    def __init__(self, config: dict, mix: dict, devices, seed: int,
                 rehearse: bool = False):
        from repro.algorithms import bsp_fft
        from repro.core import compat

        sizes = {**config, **config["rehearse"]} if rehearse else config
        self.n = 1 << int(sizes["log2n"])
        self.p = int(mix["devices"])
        self.devices = list(devices[:self.p])
        self.limits = config["limits"]
        self._bsp_fft = bsp_fft
        self.mesh = compat.make_mesh((self.p,), ("x",), devices=self.devices)
        self.x = _make_input(prng_key(seed, self.devices[0]), self.n)
        self.x.block_until_ready()
        self._rng = random.Random(seed)
        self._seen = 0
        self.sample = None

    # -- the window ------------------------------------------------------
    def call(self, i: int):
        return self._bsp_fft(self.mesh, self.x)

    def control(self, i: int):
        return R.fft(self.x, jnp.bfloat16)

    @staticmethod
    def wait(out) -> None:
        out.block_until_ready()

    def keep(self, i: int, out) -> None:
        self._seen += 1
        if self._rng.random() * self._seen < 1.0:
            self.sample = out

    def info(self) -> dict:
        return {"n": self.n, "p": self.p}

    # -- after the window ------------------------------------------------
    def release(self) -> None:
        """Only the input and the kept output stay on the chips."""
        self.mesh = None

    def check(self) -> list:
        y = jax.device_put(self.sample, SingleDeviceSharding(self.devices[0]))
        self.sample = None
        a = min(self.n, R.LEAF)
        sums = jnp.zeros(4)
        for ks, ref in R.rows(self.x, jnp.float32):
            part = _gaps(y, ref, int(ks[0]), a, len(ks))
            sums = jnp.stack([sums[0] + part[0], sums[1] + part[1],
                              jnp.maximum(sums[2], part[2]),
                              jnp.maximum(sums[3], part[3])])
        err2, ref2, err_max, ref_max = (float(v) for v in sums)
        return [("fft_rel_l2", math.sqrt(err2 / ref2),
                 self.limits["fft_rel_l2"]),
                ("fft_rel_max", err_max / ref_max,
                 self.limits["fft_rel_max"])]
