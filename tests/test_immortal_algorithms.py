"""Immortal algorithms: BSP FFT and LPF PageRank vs oracles."""

import jax.numpy as jnp
import numpy as np
import pytest
hypothesis = pytest.importorskip(
    "hypothesis", reason="property tests need the [test] extra")
from hypothesis import given, settings, strategies as st

from repro.algorithms import (banded_graph, bsp_fft, dataflow_pagerank,
                              fft_h_bytes, lpf_pagerank, partition_graph,
                              reference_pagerank, rmat_graph)

pytestmark = pytest.mark.slow


@pytest.mark.parametrize("n", [64, 512, 4096])
@pytest.mark.parametrize("ordered", [True, False])
def test_fft_matches_numpy(mesh8, rng, n, ordered):
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)
         ).astype(np.complex64)
    y = bsp_fft(mesh8, jnp.asarray(x), ordered=ordered)
    ref = np.fft.fft(x)
    assert np.abs(np.asarray(y) - ref).max() / np.abs(ref).max() < 2e-4


def test_fft_inverse_roundtrip(mesh8, rng):
    n = 1024
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)
         ).astype(np.complex64)
    y = bsp_fft(mesh8, jnp.asarray(x))
    xi = bsp_fft(mesh8, y, inverse=True)
    assert np.abs(np.asarray(xi) - x).max() < 2e-3


def test_fft_ledger_matches_immortal_cost(mesh8, rng):
    n = 2048
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)
         ).astype(np.complex64)
    _, ledger = bsp_fft(mesh8, jnp.asarray(x), return_ledger=True)
    assert ledger.h_bytes == fft_h_bytes(n, 8, ordered=True)
    assert ledger.supersteps == 2          # one redistribution + ordering


@settings(max_examples=6, deadline=None)
@given(st.integers(6, 12))
def test_fft_property_sizes(mesh8, logn):
    n = 1 << logn
    rng = np.random.default_rng(logn)
    x = (rng.standard_normal(n) + 1j * rng.standard_normal(n)
         ).astype(np.complex64)
    y = bsp_fft(mesh8, jnp.asarray(x))
    ref = np.fft.fft(x)
    assert np.abs(np.asarray(y) - ref).max() / np.abs(ref).max() < 2e-4


def test_pagerank_banded(mesh8):
    edges = banded_graph(64, 3)
    g = partition_graph(edges, 64, 8)
    r, iters, res = lpf_pagerank(mesh8, g, tol=1e-7)
    ref, _ = reference_pagerank(edges, 64)
    assert np.abs(np.asarray(r) - ref).max() < 1e-5
    assert abs(np.asarray(r).sum() - 1.0) < 1e-4


def test_pagerank_rmat_with_dangling(mesh8):
    edges = rmat_graph(128, 400, seed=3)
    g = partition_graph(edges, 128, 8)
    r, iters, res = lpf_pagerank(mesh8, g, tol=1e-7, max_iter=300)
    ref, _ = reference_pagerank(edges, 128, tol=1e-12)
    assert np.abs(np.asarray(r) - ref).max() / ref.max() < 1e-3
    assert iters < 300                     # converged, not capped


def test_pagerank_h_bytes_static(mesh8):
    edges = rmat_graph(128, 400, seed=3)
    g = partition_graph(edges, 128, 8)
    # halo plan is static: h-relation independent of values
    assert g.h_bytes() > 0
    assert g.halo_max >= max(c for (_, _, _, _, c) in g.msgs)


def test_dataflow_baseline_unnormalised(rng):
    """The 'pure Spark' baseline reproduces SparkPageRank semantics:
    ranks sum to ~n only when there are no dangling nodes."""
    edges = banded_graph(32, 2)
    r = dataflow_pagerank(edges, 32, iters=20)
    assert abs(r.sum() - 32.0) < 1e-2


def test_partition_roundtrip_spmv(mesh8, rng):
    """One LPF halo exchange + local SpMV equals the dense A @ r."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro import core as lpf
    from repro.algorithms.pagerank import _halo_exchange

    n, p = 64, 8
    edges = rmat_graph(n, 200, seed=5)
    g = partition_graph(edges, n, p)
    r0 = rng.random(n).astype(np.float32)

    A = np.zeros((n, n), np.float32)
    outdeg = np.bincount(edges[:, 0], minlength=n)
    for s, d in edges:
        A[d, s] = 1.0 / outdeg[s]
    want = A @ r0

    args = {
        "row_ids": jnp.asarray(g.row_ids), "col_ext": jnp.asarray(g.col_ext),
        "vals": jnp.asarray(g.vals), "pack_idx": jnp.asarray(g.pack_idx),
        "r": jnp.asarray(r0.reshape(p, -1)),
    }

    def spmd(ctx, s, pp, a):
        rl = a["r"].reshape(a["r"].shape[1:])
        halo = _halo_exchange(ctx, g, rl, lpf.LPF_SYNC_DEFAULT,
                              a["pack_idx"].reshape(-1))
        x_ext = jnp.concatenate([rl, halo])
        contrib = a["vals"].reshape(-1) * x_ext[a["col_ext"].reshape(-1)]
        return jax.ops.segment_sum(contrib, a["row_ids"].reshape(-1),
                                   num_segments=g.rows + 1)[:g.rows]

    out = lpf.exec_(mesh8, spmd, args,
                    in_specs={k: P("x") for k in args},
                    out_specs=P("x"))
    np.testing.assert_allclose(np.asarray(out).reshape(-1), want,
                               rtol=1e-5, atol=1e-6)


def _rmat_loop(n, m, seed, a=0.57, b=0.19, c=0.19):
    """The per-edge loop generator ``rmat_graph`` vectorizes: the first
    ``m`` distinct non-loop edges of the same seeded draw stream."""
    rng = np.random.default_rng(seed)
    scale = int(np.log2(n))
    probs = np.array([a, b, c, 1.0 - a - b - c])
    weights = 1 << np.arange(scale - 1, -1, -1, dtype=np.int64)
    edges = set()
    while len(edges) < m:
        quad = rng.choice(4, size=(max(4 * m, 1024), scale), p=probs)
        src = (quad >= 2).astype(np.int64) @ weights
        dst = (quad % 2).astype(np.int64) @ weights
        for s, d in zip(src, dst):
            if s != d:
                edges.add((int(s), int(d)))
                if len(edges) >= m:
                    break
    return np.array(sorted(edges), dtype=np.int64)


@pytest.mark.parametrize("n,m,seed", [(64, 200, 5), (64, 1000, 2),
                                      (128, 400, 3), (1024, 6144, 1)])
def test_rmat_matches_loop_reference(n, m, seed):
    np.testing.assert_array_equal(rmat_graph(n, m, seed=seed),
                                  _rmat_loop(n, m, seed))


def _dense_pagerank(edges, n, alpha=0.85, tol=1e-10, max_iter=500):
    A = np.zeros((n, n), np.float64)
    outdeg = np.bincount(edges[:, 0], minlength=n)
    for s, d in edges:
        A[d, s] = 1.0 / outdeg[s]
    dangling = (outdeg == 0).astype(np.float64)
    r = np.full(n, 1.0 / n)
    for it in range(max_iter):
        r_new = alpha * (A @ r + np.dot(dangling, r) / n) + (1 - alpha) / n
        if np.abs(r_new - r).sum() < tol:
            return r_new, it + 1
        r = r_new
    return r, max_iter


@pytest.mark.parametrize("edges,n", [(banded_graph(64, 3), 64),
                                     (rmat_graph(128, 400, seed=3), 128),
                                     (rmat_graph(512, 2048, seed=7), 512)])
def test_reference_pagerank_matches_dense(edges, n):
    """The sparse oracle equals the dense A @ r iteration (same math,
    another summation order: float64 round-off only)."""
    r, iters = reference_pagerank(edges, n)
    r_dense, iters_dense = _dense_pagerank(edges, n)
    assert iters == iters_dense
    np.testing.assert_allclose(r, r_dense, rtol=1e-12, atol=0)
