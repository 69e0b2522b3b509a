"""PageRank on a GraphBLAS-lite SpMV over LPF (paper §4.3).

The accelerated implementation translates the canonical linear-algebra
formulation (Langville & Meyer, paper ref [11]) onto LPF supersteps:

    r' = alpha * (A r  +  1/n * sum_{dangling j} r_j)  +  (1 - alpha)/n

Each iteration is:
  superstep 1 — halo exchange: owners *put* packed rank entries to the
                processes whose rows reference them (the static plan from
                the sparsity structure — an irregular h-relation, LPF's
                natural habitat);
  local       — SpMV via segment-sum + dangling correction;
  superstep 2 — a tiny allreduce of [dangling mass, next dangling mass,
                l1 residual] fused into one 3-word vector.

Unlike the paper's "pure Spark" baseline (also reimplemented here as
:func:`dataflow_pagerank`, which all-gathers the full rank vector every
iteration and ignores dangling mass and convergence), the LPF version
handles dangling nodes and checks an l1 tolerance — the same asymmetry
the paper deliberately keeps (§4.3, "can only skew the comparison in
favour of Spark").
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import bsp
from repro.core import (LPFContext, LPF_SYNC_DEFAULT, SyncAttributes, exec_,
                        span)
from .graphs import GraphPlan, PartitionedGraph

__all__ = ["lpf_pagerank", "pagerank_spmd", "dataflow_pagerank",
           "reference_pagerank"]


def _halo_exchange(ctx: LPFContext, g: Union[PartitionedGraph, GraphPlan],
                   r_local: jnp.ndarray,
                   attrs: SyncAttributes, pack_idx: jnp.ndarray
                   ) -> jnp.ndarray:
    """One halo superstep: returns the [halo_max] remote ranks."""
    pack = r_local[pack_idx]  # static-shape gather of entries to send
    ctx.resize_memory_register(ctx.registry.n_active + 2)
    ctx.resize_message_queue(max(1, len(g.msgs)))
    s_pack = ctx.register_global("pr.pack", pack)
    s_halo = ctx.register_global("pr.halo", jnp.zeros(g.halo_max, r_local.dtype))
    ctx.put_msgs([(o, d, s_pack, po, s_halo, ho, c)
                  for (o, d, po, ho, c) in g.msgs if c > 0])
    ctx.sync(attrs, label="pr.halo")
    halo = ctx.tensor(s_halo)
    ctx.deregister(s_pack)
    ctx.deregister(s_halo)
    return halo


def pagerank_spmd(ctx: LPFContext, g: Union[PartitionedGraph, GraphPlan],
                  shard: dict, *,
                  alpha: float = 0.85, tol: float = 1e-7,
                  max_iter: int = 200,
                  attrs: SyncAttributes = LPF_SYNC_DEFAULT
                  ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Run PageRank inside an SPMD region.

    ``g``: the partitioned graph, or its :attr:`~PartitionedGraph.plan`.
    ``shard``: this process's rows of the stacked arrays (squeezed):
    row_ids/col_ext/vals [nnz_max], pack_idx [send_max], dangling [rows].
    Returns (r_local [rows], iterations, l1 residual).
    """
    rows, n = g.rows, g.n
    row_ids = shard["row_ids"]
    col_ext = shard["col_ext"]
    vals = shard["vals"]
    pack_idx = shard["pack_idx"]
    dangling = shard["dangling"]
    axes = ctx.axes

    r0 = jnp.full(rows, 1.0 / n, jnp.float32)

    def reduce3(ctx2, v3):
        return bsp.allreduce(ctx2, v3, attrs=attrs, label="pr.reduce")

    def one_iter(ctx2: LPFContext, r: jnp.ndarray, dmass: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
        # the whole iteration records as one program (``compile_loop``
        # opens the trace): the halo read is a *dataflow-precise* flush
        # (it executes exactly the halo superstep's cone, not whatever
        # else the trace holds), so the halo + score-update pattern
        # keeps independent supersteps — the nested stats-allreduce
        # pair — recorded across the SpMV compute barrier, where the
        # DAG schedule search may reorder or overlap them, and replays
        # per-iteration traces from the program cache
        # (reordered-but-equivalent recordings of later iterations
        # canonicalize to the same cache entry)
        halo = _halo_exchange(ctx2, g, r, attrs, pack_idx)
        x_ext = jnp.concatenate([r, halo])
        contrib = vals * x_ext[col_ext]
        spmv = jax.ops.segment_sum(contrib, row_ids,
                                   num_segments=rows + 1,
                                   indices_are_sorted=False)[:rows]
        r_new = alpha * (spmv + dmass / n) + (1.0 - alpha) / n
        # fused 3-word allreduce: next dangling mass, residual, (spare)
        stats = jnp.stack([jnp.sum(r_new * dangling),
                           jnp.sum(jnp.abs(r_new - r)),
                           jnp.zeros((), jnp.float32)])
        tot = reduce3(ctx2, stats)
        return r_new, tot[0], tot[1]

    # initial dangling mass of the uniform vector
    stats0 = bsp.allreduce(
        ctx, jnp.stack([jnp.sum(r0 * dangling),
                        jnp.zeros((), jnp.float32),
                        jnp.zeros((), jnp.float32)]),
        attrs=attrs, label="pr.init")
    d0 = stats0[0]

    def cond(carry):
        _, _, it, res = carry
        return (it < max_iter) & (res > tol)

    def body(ctx2, carry):
        r, dmass, it, _ = carry
        r_new, dnew, res = one_iter(ctx2, r, dmass)
        return (r_new, dnew, it + 1, res)

    # the whole iterated program lowers as ONE XLA While computation
    # (body traced once, per-iteration superstep costs ledgered once)
    # instead of a Python-dispatched hook per iteration
    r, dmass, iters, res = ctx.compile_loop(
        body, (r0, d0, jnp.zeros((), jnp.int32),
               jnp.full((), jnp.inf, jnp.float32)),
        cond=cond, label="pr.iter")
    return r, iters, res


@functools.lru_cache(maxsize=256)
def _pagerank_exec_spmd(plan: GraphPlan, alpha: float, tol: float,
                        max_iter: int, attrs: SyncAttributes):
    """``lpf_pagerank``'s SPMD function, one per graph plan and solver
    parameters: ``exec_`` keys its compiled program on the function.  It
    holds the plan, not the graph, whose host arrays stay uncached."""

    def spmd(ctx, s, p, a):
        shard = {k: v.reshape(v.shape[1:]) for k, v in a.items()}
        return pagerank_spmd(ctx, plan, shard, alpha=alpha, tol=tol,
                             max_iter=max_iter, attrs=attrs)

    return spmd


def lpf_pagerank(mesh: jax.sharding.Mesh, g: PartitionedGraph, *,
                 axes: Optional[tuple] = None, alpha: float = 0.85,
                 tol: float = 1e-7, max_iter: int = 200,
                 attrs: SyncAttributes = LPF_SYNC_DEFAULT):
    """Whole-graph driver: distribute shards, run, gather [n] ranks."""
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    with span("lpf.pagerank.upload"):
        args = {
            "row_ids": jnp.asarray(g.row_ids),
            "col_ext": jnp.asarray(g.col_ext),
            "vals": jnp.asarray(g.vals), "pack_idx": jnp.asarray(g.pack_idx),
            "dangling": jnp.asarray(g.dangling),
        }
    in_specs = {k: P(axes) for k in args}
    spmd = _pagerank_exec_spmd(g.plan, alpha, tol, max_iter, attrs)
    r, iters, res = exec_(mesh, spmd, args, axes=axes,
                          in_specs=in_specs,
                          out_specs=(P(axes), P(), P()))
    return r.reshape(-1), int(iters), float(res)


# --------------------------------------------------------------------------
# baselines
# --------------------------------------------------------------------------

def dataflow_pagerank(edges: np.ndarray, n: int, iters: int,
                      alpha: float = 0.85) -> np.ndarray:
    """The paper's "pure Spark" analogue: contributions shuffled globally
    every iteration (here: a full gather + segment-sum in jit), *without*
    dangling handling or convergence checks — faithful to
    examples/SparkPageRank.scala which computes
    ``rank = 0.15 + 0.85 * sum(contribs)``."""
    src = jnp.asarray(edges[:, 0])
    dst = jnp.asarray(edges[:, 1])
    outdeg = jnp.asarray(np.maximum(
        np.bincount(edges[:, 0], minlength=n), 1).astype(np.float32))

    @jax.jit
    def step(r):
        contrib = r[src] / outdeg[src]
        s = jax.ops.segment_sum(contrib, dst, num_segments=n)
        return (1.0 - alpha) + alpha * s

    r = jnp.ones(n, jnp.float32)
    for _ in range(iters):
        r = step(r)
    return np.asarray(r)


def reference_pagerank(edges: np.ndarray, n: int, alpha: float = 0.85,
                       tol: float = 1e-10, max_iter: int = 500
                       ) -> Tuple[np.ndarray, int]:
    """Sparse float64 numpy oracle with dangling handling (test
    reference): ``A[d, s] = 1/outdeg(s)`` once per distinct edge."""
    outdeg = np.bincount(edges[:, 0], minlength=n)
    keys = np.unique(edges[:, 0].astype(np.int64) * n + edges[:, 1])
    src, dst = keys // n, keys % n
    w = 1.0 / outdeg[src]
    dangling = outdeg == 0
    r = np.full(n, 1.0 / n)
    for it in range(max_iter):
        r_new = alpha * (np.bincount(dst, weights=w * r[src], minlength=n)
                         + r[dangling].sum() / n) + (1 - alpha) / n
        if np.abs(r_new - r).sum() < tol:
            return r_new, it + 1
        r = r_new
    return r, max_iter
