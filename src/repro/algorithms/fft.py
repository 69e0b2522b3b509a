"""The immortal BSP FFT (Inda & Bisseling, paper ref [10]) on LPF.

Radix-p decomposition with a *single* data redistribution, valid whenever
``n >= p**2`` (the paper's ``sqrt(n) > p`` condition).  Writing the input
index ``j = l*p + s`` (cyclic over processes) and the output index
``k = k2 + (n/p)*k1``:

    y[k2 + (n/p) k1] = sum_s  w_p^{s k1} * ( w_n^{s k2} * X_s[k2] )

where ``X_s = FFT_{n/p}(x_s)`` is a process-local FFT of the cyclic slice.
The algorithm is therefore:

  (0) local ``n/p``-point FFT of the cyclic-distributed input,
  (1) local twiddle by ``w_n^{s k2}`` (the *time-shifted* scaling the
      paper laments vendor libraries do not expose),
  (2) ONE total exchange — blocks of ``n/p**2`` — so each process owns a
      contiguous ``k2`` range for all ``s``;   cost  (n/p)g + l,
  (3) local ``p``-point DFTs across the gathered ``s`` dimension,
      evaluated as a dense [p, p] twiddle matmul (MXU-friendly on TPU),
  (4) *optional* second exchange to produce naturally-ordered output
      (``ordered=True``); the immortal algorithm's native output order is
      "k1-major blocked by k2" — exactly the unordered/decimated output
      the paper benchmarks.

BSP cost:  2 (n/p) log(n/p + p) flops  +  (n/p)(p-1)/p * itemsize * g
           + l   (unordered; ordered doubles the comm term), where
           itemsize is 8 bytes for complex64 and 16 for complex128 —
           matching ``fft_h_bytes``'s default of 8.

The process-local FFT runs through ``repro.kernels.fft_stage`` (Pallas,
TPU-tiled) when ``use_kernel=True``, else ``jnp.fft.fft``.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (LPFContext, LPF_SYNC_DEFAULT, SyncAttributes, exec_,
                        hook, span)
from jax.sharding import PartitionSpec as P

__all__ = ["bsp_fft_spmd", "bsp_fft", "fft_flops", "fft_h_bytes"]


def fft_flops(n: int) -> float:
    """Standard 5 n log2 n flop count for a complex FFT."""
    return 5.0 * n * math.log2(max(n, 2))


def fft_h_bytes(n: int, p: int, ordered: bool = True,
                itemsize: int = 8) -> int:
    """Predicted h-relation (bytes) of the BSP FFT — the immortal cost.

    ``itemsize`` is the *complex* element width: 8 for complex64 (the
    default, matching the benchmarks) and 16 for complex128."""
    if p == 1:
        return 0
    one = (n // p) * (p - 1) // p * itemsize
    return (2 * one) if ordered else one


def _local_fft(x: jnp.ndarray, use_kernel: bool) -> jnp.ndarray:
    if use_kernel:
        from repro.kernels.fft_stage import ops as fft_ops
        return fft_ops.fft(x)
    return jnp.fft.fft(x)


@functools.partial(jax.jit, static_argnums=1)
def _strided_layout(x: jnp.ndarray, p: int) -> jnp.ndarray:
    n = x.shape[0]
    return jnp.concatenate([jax.lax.slice(x, (s,), (n,), (p,))
                            for s in range(p)])


def cyclic_layout(x, p: int):
    """The cyclic layout of ``x`` over ``p`` processes, pid-major:
    ``out[s*(n/p) + l] = x[l*p + s]``.  At ``p == 1`` it is ``x`` itself,
    with no device op."""
    return x if p == 1 else _strided_layout(x, p)


def bsp_fft_spmd(ctx: LPFContext, x_local: jnp.ndarray, n: int, *,
                 ordered: bool = True, use_kernel: bool = False,
                 attrs: SyncAttributes = LPF_SYNC_DEFAULT,
                 inverse: bool = False) -> jnp.ndarray:
    """Run the immortal FFT inside an SPMD region.

    ``x_local``: this process's *cyclic* slice (x[s], x[s+p], ...) of
    length n/p, complex64/128.  Returns the local output slice: the
    contiguous block ``y[s*(n/p) : (s+1)*(n/p)]`` when ``ordered`` else
    the algorithm's native unordered block.
    """
    p, s = ctx.p, ctx.pid
    npp = n // p
    if n % (p * p) != 0 and p > 1:
        raise ValueError(f"BSP FFT requires p^2 | n (got n={n}, p={p})")
    if x_local.shape[0] != npp:
        raise ValueError(f"local slice must be n/p={npp}, got {x_local.shape}")
    ctype = x_local.dtype
    sign = 1.0 if inverse else -1.0

    # (0) local FFT of the cyclic slice (conj-trick for the inverse)
    if inverse:
        X = jnp.conj(_local_fft(jnp.conj(x_local), use_kernel))
    else:
        X = _local_fft(x_local, use_kernel)

    if p == 1:
        return X / n if inverse else X

    # (1) time-shifted twiddle  w_n^{+- s k2}, built in the real dtype
    # matching the input's precision (float64 for complex128 inputs —
    # a float32 phase costs ~1e-4 relative error at n >= 2**16)
    real_dt = jnp.finfo(ctype).dtype
    k2 = jnp.arange(npp, dtype=real_dt)
    phase = (s.astype(real_dt) * k2 / n) * real_dt.type(sign * 2.0 * np.pi)
    Z = X * jax.lax.complex(jnp.cos(phase), jnp.sin(phase)).astype(ctype)

    # (2)-(4) run recorded: the twiddle matmul is a genuine compute
    # dependency between redistribute and reorder, so the pair can never
    # batch — but the flush is dataflow-precise: reading Zk executes
    # exactly the redistribute's cone, so when this FFT runs inside an
    # enclosing recorded program (a batched spectral pipeline), the
    # caller's independent supersteps stay recorded, and the DAG
    # schedule search may hoist them — non-adjacent or not — into
    # overlap groups with this FFT's exchanges (two recorded FFTs
    # schedule as [A.redist||B.redist][A.reorder||B.reorder]; see
    # benchmarks/schedule_search.py).
    with ctx.program("bsp_fft"):
        # (2) the single redistribution: block d of my k2-range to process d
        w = npp // p  # n / p^2 elements per (src, dst) pair
        ctx.resize_memory_register(ctx.registry.n_active + 2)
        ctx.resize_message_queue(p * p)
        src = ctx.register_global("fft.src", Z)
        dst = ctx.register_global("fft.buf", jnp.zeros(p * w, ctype))
        ctx.put_msgs([(s_, d, src, d * w, dst, s_ * w, w)
                      for s_ in range(p) for d in range(p)])
        ctx.sync(attrs, label="fft.redistribute")
        Zk = ctx.tensor(dst).reshape(p, w)      # [s, k2_local]
        ctx.deregister(src)

        # (3) p-point DFTs across s as a dense twiddle matmul (MXU-friendly;
        # full f32 precision — the TPU's default matmul rounds to bf16)
        k1 = np.arange(p)
        Wp = np.exp(sign * 2j * np.pi * np.outer(k1, k1) / p).astype(ctype)
        Y = jnp.einsum("ts,sk->tk", jnp.asarray(Wp), Zk,
                       precision=jax.lax.Precision.HIGHEST)  # [k1, k2_local]

        if not ordered:
            ctx.deregister(dst)
            out = Y.reshape(-1)
            return out / n if inverse else out

        # (4) ordering pass: row k1 belongs to process k1 (block distribution)
        ctx.resize_memory_register(ctx.registry.n_active + 2)
        ctx.resize_message_queue(p * p)
        osrc = ctx.register_global("fft.osrc", Y.reshape(-1))
        odst = ctx.register_global("fft.odst", jnp.zeros(npp, ctype))
        # my row k1=d (length w) goes to process d at offset (my pid)*w
        ctx.put_msgs([(s_, d, osrc, d * w, odst, s_ * w, w)
                      for s_ in range(p) for d in range(p)])
        ctx.sync(attrs, label="fft.reorder")
        yl = ctx.tensor(odst)
        ctx.deregister(dst)
        ctx.deregister(osrc)
        ctx.deregister(odst)
    return yl / n if inverse else yl


@functools.lru_cache(maxsize=256)
def _fft_exec_spmd(n: int, p: int, ordered: bool, use_kernel: bool,
                   attrs: SyncAttributes, inverse: bool):
    """``bsp_fft``'s SPMD function, one per set of static parameters:
    ``exec_`` keys its compiled program on the function."""

    def spmd(ctx, s, pp, xt):
        xl = xt.reshape(pp, n // pp)[s]
        return bsp_fft_spmd(ctx, xl, n, ordered=ordered,
                            use_kernel=use_kernel, attrs=attrs,
                            inverse=inverse)

    return spmd


def bsp_fft(mesh: jax.sharding.Mesh, x: jnp.ndarray, *,
            axes: Optional[tuple] = None, ordered: bool = True,
            use_kernel: bool = False, inverse: bool = False,
            attrs: SyncAttributes = LPF_SYNC_DEFAULT,
            return_ledger: bool = False):
    """Whole-array driver: ``lpf_exec`` the immortal FFT over ``mesh``.

    ``x`` is the full (host) vector; it is scattered cyclically, the SPMD
    FFT runs, and the naturally-ordered result is gathered back.
    """
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    p = int(np.prod([mesh.shape[a] for a in axes]))
    n = int(x.shape[0])
    # cyclic layout, pid-major, from strided slices (lax.slice with a
    # stride): a step-indexed x[s::p] lowers to a gather, and an
    # [n/p, p] view pads its p-wide minor dimension to a full TPU lane
    # tile, a 32 GiB buffer for complex64 at n = 2^28
    with span("lpf.fft.layout"):
        xc = cyclic_layout(x, p)
    spmd = _fft_exec_spmd(n, p, ordered, use_kernel, attrs, inverse)
    out = exec_(mesh, spmd, jnp.asarray(xc), axes=axes,
                out_specs=P(axes), return_ledger=return_ledger)
    if return_ledger:
        out, ledger = out
    y = out.reshape(-1)
    if not ordered:
        # undo the unordered layout on host for verification: process s
        # holds [k1, k2local] with k2local in block s
        y = y.reshape(p, p, n // (p * p))          # [s, k1, k2l]
        y = jnp.transpose(y, (1, 0, 2)).reshape(-1)  # k1-major, k2 = s*w + k2l
    return (y, ledger) if return_ledger else y
