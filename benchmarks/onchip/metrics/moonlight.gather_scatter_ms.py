"""Device time per training step of the gather and scatter operations
(devtrace's ``gather_scatter`` class: the expert layers' dispatch and
combine, the embedding lookup and their backward passes), in ms."""


def read(run):
    if run.trace is None:
        return None
    return 1e3 * run.trace.class_s.get("gather_scatter", 0.0) / len(run.calls)
