"""The solves' share of the chips' memory roofline, in percent: the
bytes each iteration needs (16 B per stored edge, 12 B per vertex) times
the iterations of every solve, at the published HBM bandwidth, over the
traced window."""

import workcount


def read(run):
    if run.trace is None or run.peak is None:
        return None
    info = run.info
    need = sum(workcount.pagerank_bytes(it, info["edges"], info["n"])
               for it in info["iters"])
    least = workcount.least_seconds(0.0, need, info["p"], run.peak)
    return 100.0 * least / run.window_s
