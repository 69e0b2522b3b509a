"""Plain reference for PageRank with dangling mass, independent of the
program under test.

Power iteration on the host in float64:

    r' = alpha * (A r + (sum of r over dangling vertices) / n) + (1 - alpha) / n

with ``A[d, s] = 1 / outdeg(s)`` for each (distinct) edge s -> d, stopped
when the l1 change falls under ``tol``.  ``dtype=bfloat16`` is the
control: the same iteration with the weights and the rank vector held in
bfloat16, one precision step below the program's float32.  Rounding
keeps its l1 change from ever reaching a small ``tol``, so it stops
where the change stops falling (or at ``max_iter``).
"""

from __future__ import annotations

import ml_dtypes
import numpy as np

BFLOAT16 = ml_dtypes.bfloat16


def _round(v: np.ndarray, dtype) -> np.ndarray:
    return v if dtype == np.float64 else v.astype(dtype).astype(np.float64)


def solve(edges: np.ndarray, n: int, *, alpha: float = 0.85,
          tol: float = 1e-10, max_iter: int = 500,
          dtype=np.float64) -> tuple:
    """Return ``(ranks [n] float64, iterations)``."""
    src, dst = edges[:, 0], edges[:, 1]
    outdeg = np.bincount(src, minlength=n)
    w = _round(1.0 / outdeg[src], dtype)
    dangling = outdeg == 0
    r = _round(np.full(n, 1.0 / n), dtype)
    last = np.inf
    for it in range(1, max_iter + 1):
        spmv = np.bincount(dst, weights=w * r[src], minlength=n)
        r_new = _round(alpha * (spmv + r[dangling].sum() / n)
                       + (1.0 - alpha) / n, dtype)
        change = np.abs(r_new - r).sum()
        r = r_new
        if change < tol or (dtype != np.float64 and change >= last):
            break
        last = change
    return r, it

