"""Milliseconds per transform: the whole window over the transforms
completed in it."""


def read(run):
    return 1e3 * run.window_s / len(run.calls)
