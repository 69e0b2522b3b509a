"""PageRank's cells: ``repro.algorithms.lpf_pagerank(mesh, g, ...)``.

Set-up makes the Graph500 Kronecker graph of the configuration on the
first chip (``graph500.py``): its edges drawn from the configuration's
``graph_seed``, its vertex labels permuted from the run's seed, so every
seed gives the solver the same graph under other labels.  (Float32
rounding, which the labels' order moves, decides whether a solve takes
18 or 19 iterations.)  It brings the edge list to the host
and partitions it with the program's ``partition_graph`` over the
mix's ``devices`` chips.  The timed call is one whole solve from that
host-resident partitioned graph, as a user makes it.  Every solve of the
window is kept, and after the window each one's ranks are compared with
the plain float64 reference (``references/pagerank.py``, converged to
1e-10): the largest error relative to the largest rank.  The control
puts the same reference, computed in bfloat16, in the program's place.
"""

from __future__ import annotations

import numpy as np

import cells
from seeding import prng_key

REF = cells.load_module(cells.HERE / "references" / "pagerank.py")
G500 = cells.load_module(cells.HERE / "graph500.py")

#: the reference's own convergence: far below the program's tolerance
REFERENCE_TOL = 1e-10


class System:
    def __init__(self, config: dict, mix: dict, devices, seed: int,
                 rehearse: bool = False):
        from repro.algorithms import lpf_pagerank, partition_graph
        from repro.core import compat

        sizes = {**config, **config["rehearse"]} if rehearse else config
        self.n = 1 << int(sizes["scale"])
        self.p = int(mix["devices"])
        self.devices = list(devices[:self.p])
        self.alpha = float(config["alpha"])
        self.tol = float(config["tol"])
        self.max_iter = int(config["max_iter"])
        self.limits = config["limits"]
        self.edges = G500.kronecker_edges(
            prng_key(int(config["graph_seed"]), self.devices[0]),
            prng_key(seed, self.devices[0]), int(sizes["scale"]),
            int(config["edge_factor"]), *config["initiator"])
        self.graph = partition_graph(self.edges, self.n, self.p)
        self.mesh = compat.make_mesh((self.p,), ("x",), devices=self.devices)
        self._solve = lpf_pagerank
        self.kept = []

    # -- the window ------------------------------------------------------
    def call(self, i: int):
        r, iters, _ = self._solve(self.mesh, self.graph, alpha=self.alpha,
                                  tol=self.tol, max_iter=self.max_iter)
        return r, iters

    def control(self, i: int):
        r, iters = REF.solve(self.edges, self.n, alpha=self.alpha,
                             tol=self.tol, max_iter=self.max_iter,
                             dtype=REF.BFLOAT16)
        return r, iters

    @staticmethod
    def wait(out) -> None:
        r = out[0]
        if hasattr(r, "block_until_ready"):
            r.block_until_ready()

    def keep(self, i: int, out) -> None:
        self.kept.append(out)

    def info(self) -> dict:
        return {"n": self.n, "p": self.p, "edges": int(self.edges.shape[0]),
                "iters": [int(it) for _, it in self.kept]}

    # -- after the window ------------------------------------------------
    def release(self) -> None:
        self.graph = self.mesh = None

    def check(self) -> list:
        ref, _ = REF.solve(self.edges, self.n, alpha=self.alpha,
                           tol=REFERENCE_TOL, max_iter=10 * self.max_iter)
        top = ref.max()
        worst = max(float(np.abs(np.asarray(r, np.float64) - ref).max() / top)
                    for r, _ in self.kept)
        return [("pagerank_rel_max", worst, self.limits["pagerank_rel_max"])]
