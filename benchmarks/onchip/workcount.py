"""Work that each problem needs, fixed by the problem and not by how the
program computes it, and the least time a chip can take for it."""

from __future__ import annotations

import math

#: bytes of one complex64 element
C64 = 8


def fft_flops(n: int) -> float:
    """5 n log2 n: the standard count for a complex FFT of length n."""
    return 5.0 * n * math.log2(n)


def fft_bytes(n: int) -> int:
    """One read and one write of the complex64 vector."""
    return 2 * C64 * n


def fft_exchange_bytes(n: int, p: int) -> int:
    """Bytes each of p chips must send in an ordered distributed FFT:
    two exchanges of the (p - 1) / p of its n / p elements that live
    elsewhere."""
    return 2 * C64 * (n // p) * (p - 1) // p


def pagerank_bytes(iters: int, edges: int, n: int) -> int:
    """Per iteration, 16 B per stored edge (row index, column index,
    weight, gathered rank) and 12 B per vertex (read r, write r, the
    dangling flag)."""
    return iters * (16 * edges + 12 * n)


def least_seconds(flops: float, nbytes: float, chips: int,
                  peak: dict) -> float:
    """The roofline: the larger of compute time and memory time at the
    published peaks, with the work spread over ``chips``."""
    return max(flops / (chips * peak["flops"]),
               nbytes / (chips * peak["hbm_Bps"]))
