"""Graph substrate for the PageRank immortal algorithm.

Deterministic R-MAT generator (the paper uses SuiteSparse/WebGraph
matrices; offline we synthesise power-law webgraphs), a block row
partitioner producing uniform SPMD-ready CSR shards, and the *static halo
plan*: for every (owner, requester) process pair, which rank entries must
travel each iteration.  The plan is exactly an LPF h-relation — the
communication pattern of sparse matrix-vector multiplication is known
from the sparsity structure, so every PageRank iteration is one
`lpf_put`-superstep plus one small allreduce.
"""

from __future__ import annotations

import dataclasses
from typing import List, Tuple

import numpy as np

__all__ = ["rmat_graph", "banded_graph", "GraphPlan", "PartitionedGraph",
           "partition_graph"]


def rmat_graph(n: int, m: int, seed: int = 0,
               a: float = 0.57, b: float = 0.19, c: float = 0.19
               ) -> np.ndarray:
    """Directed R-MAT edge list [m, 2] (src, dst), deduplicated, no self
    loops, sorted.  ``n`` must be a power of two.

    The edges are the first ``m`` distinct non-loop (src, dst) pairs of
    the seeded draw stream: one uniform per (edge, bit level), the
    quadrant chosen by the cumulative ``(a, b, c, d)`` thresholds.
    Drawing and deduplication are vectorized, so Graph500-scale graphs
    (2^20+ vertices, edge factor 16) build in seconds."""
    assert n & (n - 1) == 0, "rmat needs power-of-two n"
    rng = np.random.default_rng(seed)
    scale = int(np.log2(n))
    cdf = np.cumsum([a, b, c, 1.0 - a - b - c])
    cdf /= cdf[-1]
    rows = int(min(max(4 * m, 1024), 1 << 21))
    chunks = []                          # non-loop keys, in draw order
    drawn = 0
    while True:
        while drawn < m:
            u = np.ascontiguousarray(rng.random((rows, scale)).T)
            # quadrant 0..3 = (src bit, dst bit), most significant first
            src_bit = u >= cdf[1]
            dst_bit = (u >= cdf[2]) | ((u >= cdf[0]) & ~src_bit)
            src = np.zeros(rows, np.int64)
            dst = np.zeros(rows, np.int64)
            for j in range(scale):
                src <<= 1
                src |= src_bit[j]
                dst <<= 1
                dst |= dst_bit[j]
            keys = (src * n + dst)[src != dst]
            chunks.append(keys)
            drawn += keys.size
        keys = np.concatenate(chunks)
        _, first = np.unique(keys, return_index=True)
        if first.size >= m:
            break
        drawn = first.size               # draw the shortfall again
    keys = np.sort(keys[np.sort(first)[:m]])
    return np.stack([keys // n, keys % n], axis=1)


def banded_graph(n: int, band: int = 4) -> np.ndarray:
    """Deterministic banded digraph (cage-matrix-like): vertex v links to
    v+1 .. v+band (mod n)."""
    src = np.repeat(np.arange(n), band)
    off = np.tile(np.arange(1, band + 1), n)
    dst = (src + off) % n
    return np.stack([src, dst], axis=1)


@dataclasses.dataclass(frozen=True)
class GraphPlan:
    """What the PageRank trace reads from a :class:`PartitionedGraph`
    besides its array shapes: sizes and the halo message table."""

    n: int
    p: int
    rows: int
    halo_max: int
    msgs: Tuple[Tuple[int, int, int, int, int], ...]


@dataclasses.dataclass
class PartitionedGraph:
    """Block-row partitioned column-stochastic link matrix + halo plan.

    Traced (per-process, stacked on axis 0) arrays — distribute with
    ``in_specs=P(axes)``:
      ``row_ids``  [p, nnz_max]   local row of each stored nonzero
      ``col_ext``  [p, nnz_max]   column index into [local r | halo]
      ``vals``     [p, nnz_max]   1/outdeg(src)   (0 padding)
      ``pack_idx`` [p, send_max]  local r indices to pack for neighbours
      ``dangling`` [p, rows]      1.0 where the local vertex is dangling

    Static (host) plan:
      ``msgs``     [(owner, requester, pack_off, halo_off, count)]
      ``halo_max`` / ``send_max`` reserved capacities (lpf_resize_*)
    """

    n: int
    p: int
    rows: int
    nnz_max: int
    send_max: int
    halo_max: int
    row_ids: np.ndarray
    col_ext: np.ndarray
    vals: np.ndarray
    pack_idx: np.ndarray
    dangling: np.ndarray
    msgs: List[Tuple[int, int, int, int, int]]

    @property
    def plan(self) -> GraphPlan:
        return GraphPlan(self.n, self.p, self.rows, self.halo_max,
                         tuple(self.msgs))

    def h_bytes(self, itemsize: int = 4) -> int:
        """The per-iteration halo h-relation (bytes) — the immortal cost."""
        sent = np.zeros(self.p, np.int64)
        recv = np.zeros(self.p, np.int64)
        for o, d, _, _, c in self.msgs:
            if o != d:
                sent[o] += c * itemsize
                recv[d] += c * itemsize
        return int(max(sent.max(initial=0), recv.max(initial=0)))


def partition_graph(edges: np.ndarray, n: int, p: int) -> PartitionedGraph:
    """Build the SPMD shards + halo plan for ``r' = A r`` with
    ``A[dst, src] = 1/outdeg(src)``."""
    if n % p:
        raise ValueError(f"n={n} must be divisible by p={p}")
    rows = n // p
    src, dst = edges[:, 0], edges[:, 1]
    outdeg = np.bincount(src, minlength=n).astype(np.float64)
    dangling_v = (outdeg == 0).astype(np.float32)

    owner = dst // rows            # nonzero [dst, src] lives on dst's owner
    col_owner = src // rows

    per_pid_nnz = np.bincount(owner, minlength=p)
    nnz_max = int(per_pid_nnz.max(initial=1))

    # per-pid halos: unique remote sources, grouped by owning process
    halos: List[np.ndarray] = []
    halo_groups: List[List[np.ndarray]] = []
    for d in range(p):
        mask = owner == d
        remote = np.unique(src[mask & (col_owner != d)])
        groups = [remote[(remote // rows) == o] for o in range(p)]
        halos.append(np.concatenate(groups) if groups else remote)
        halo_groups.append(groups)
    halo_max = max(1, max(h.size for h in halos))

    # owner-side pack buffers: concatenation over requesters of the
    # local indices each requester needs
    pack_lists: List[List[np.ndarray]] = [[] for _ in range(p)]
    for d in range(p):
        for o in range(p):
            g = halo_groups[d][o]
            if g.size:
                pack_lists[o].append((d, g - o * rows))
    msgs: List[Tuple[int, int, int, int, int]] = []
    pack_idx = np.zeros((p, 1), np.int32)
    send_max = 1
    packs: List[np.ndarray] = []
    for o in range(p):
        cat = []
        off = 0
        for d, loc in pack_lists[o]:
            halo_off = 0
            for oo in range(o):
                halo_off += halo_groups[d][oo].size
            msgs.append((o, d, off, halo_off, int(loc.size)))
            cat.append(loc)
            off += loc.size
        packs.append(np.concatenate(cat).astype(np.int32) if cat
                     else np.zeros(0, np.int32))
        send_max = max(send_max, off)
    pack_idx = np.zeros((p, send_max), np.int32)
    for o in range(p):
        pack_idx[o, :packs[o].size] = packs[o]

    # CSR-ish shards with extended column indices
    row_ids = np.full((p, nnz_max), rows, np.int32)  # pad -> dump bucket
    col_ext = np.zeros((p, nnz_max), np.int32)
    vals = np.zeros((p, nnz_max), np.float32)
    for d in range(p):
        mask = owner == d
        s_d, t_d = src[mask], dst[mask]
        # map source -> extended index; halos[d] is sorted (np.unique
        # output grouped by owner, and owner = v // rows is monotone)
        ext = np.where(col_owner[mask] == d, s_d - d * rows,
                       rows + np.searchsorted(halos[d], s_d))
        k = s_d.size
        row_ids[d, :k] = (t_d - d * rows).astype(np.int32)
        col_ext[d, :k] = ext.astype(np.int32)
        vals[d, :k] = (1.0 / outdeg[s_d]).astype(np.float32)

    dang = dangling_v.reshape(p, rows)
    return PartitionedGraph(
        n=n, p=p, rows=rows, nnz_max=nnz_max, send_max=send_max,
        halo_max=halo_max, row_ids=row_ids, col_ext=col_ext, vals=vals,
        pack_idx=pack_idx, dangling=dang, msgs=msgs)
