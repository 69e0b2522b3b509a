"""95th percentile (nearest rank) of the per-transform latency, call to
result ready, over every transform of the window."""

import math


def read(run):
    lat = sorted(done - called for called, _, done in run.calls)
    return 1e3 * lat[math.ceil(0.95 * len(lat)) - 1]
