"""Public wrapper for the SSD scan: the Pallas kernel forward
(``interpret=True`` runs it in the Pallas interpreter, for CPU tests)
and a reference VJP (the recurrence differentiates cleanly through the
oracle while the kernel serves the forward hot path)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import kernel as _k
from . import ref as _ref

__all__ = ["ssd"]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _ssd(x, dt, a, b, c, chunk, interpret):
    y, _ = _k.ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=interpret)
    return y


def _ssd_fwd(x, dt, a, b, c, chunk, interpret):
    y, _ = _k.ssd_scan(x, dt, a, b, c, chunk=chunk, interpret=interpret)
    return y, (x, dt, a, b, c)


def _ssd_bwd(chunk, interpret, res, dy):
    x, dt, a, b, c = res
    _, vjp = jax.vjp(lambda *ops: _ref.ssd_ref(*ops)[0], x, dt, a, b, c)
    return vjp(dy)


_ssd.defvjp(_ssd_fwd, _ssd_bwd)


def ssd(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray, b: jnp.ndarray,
        c: jnp.ndarray, *, chunk: int = 128,
        interpret: bool = False) -> jnp.ndarray:
    """SSD scan output y [B, S, H, P] (see kernel.ssd_scan)."""
    return _ssd(x, dt, a, b, c, chunk, interpret)
