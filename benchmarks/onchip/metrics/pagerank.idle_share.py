"""Share of the traced window in which no operation ran on the chips,
in percent, averaged over the chips."""


def read(run):
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
