"""Milliseconds per iteration: the whole window, host path included,
over the iterations of all the solves completed in it, each solve to the
configuration's l1 tolerance.  Not the time per solve: float32 rounding
decides whether a seed's solve takes 18 or 19 iterations."""


def read(run):
    iters = sum(run.info["iters"])
    return 1e3 * run.window_s / iters if iters else None
