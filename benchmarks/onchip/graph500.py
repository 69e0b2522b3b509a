"""Graph500's Kronecker (R-MAT) edge generator, run on the device.

The quadrant rule is that of the program's ``rmat_graph`` (one uniform
per edge and bit level, quadrant chosen by the cumulative (A, B, C, D)
thresholds), and so is what is kept: the first ``edge_factor * 2^scale``
distinct directed non-loop pairs in draw order.  Added, as Graph500
does: a seeded random permutation of the vertex labels.  The draws come
from ``jax.random`` (threefry): one key draws the edges, another the
permutation, so that one set of edges can be given many labellings.
The graph is made in one jitted call on the device that holds the keys.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

#: first guess of draws per kept edge; raised and drawn again if short
DRAW_FACTOR = 1.25


@functools.partial(jax.jit, static_argnames=("scale", "m", "draws", "cdf"))
def _generate(k_draw, k_perm, scale: int, m: int, draws: int, cdf: tuple):
    u = jax.random.uniform(k_draw, (scale, draws), jnp.float32)
    src_bit = u >= cdf[1]
    dst_bit = (u >= cdf[2]) | ((u >= cdf[0]) & ~src_bit)
    weight = (1 << jnp.arange(scale - 1, -1, -1, dtype=jnp.int32))[:, None]
    src = jnp.sum(jnp.where(src_bit, weight, 0), axis=0, dtype=jnp.int32)
    dst = jnp.sum(jnp.where(dst_bit, weight, 0), axis=0, dtype=jnp.int32)
    idx = jnp.arange(draws, dtype=jnp.int32)
    # group equal pairs; the stable sort keeps each group's first draw first
    s, d, i = jax.lax.sort((src, dst, idx), num_keys=2, is_stable=True)
    new = jnp.concatenate([jnp.ones(1, bool),
                           (s[1:] != s[:-1]) | (d[1:] != d[:-1])])
    first = new & (s != d)
    distinct = jnp.sum(first, dtype=jnp.int32)
    keep = jnp.sort(jnp.where(first, i, draws))[:m]
    keep = jnp.minimum(keep, draws - 1)       # only read when distinct < m
    perm = jax.random.permutation(k_perm, 1 << scale).astype(jnp.int32)
    es, ed = jax.lax.sort((perm[src[keep]], perm[dst[keep]]), num_keys=2)
    return es, ed, distinct


def kronecker_edges(draw_key, perm_key, scale: int, edge_factor: int,
                    a: float, b: float, c: float) -> np.ndarray:
    """Edge list ``[edge_factor * 2^scale, 2]`` (src, dst), int64, sorted,
    distinct, without self-loops: the edges drawn with ``draw_key``, the
    vertex labels permuted with ``perm_key``."""
    m = edge_factor << scale
    cdf = tuple(float(v) for v in np.cumsum([a, b, c]))
    factor = DRAW_FACTOR
    while True:
        draws = 1024 * math.ceil(m * factor / 1024)
        es, ed, distinct = _generate(draw_key, perm_key, scale, m, draws,
                                     cdf)
        if int(distinct) >= m:
            return np.stack([np.asarray(es, np.int64),
                             np.asarray(ed, np.int64)], axis=1)
        factor *= 1.25
