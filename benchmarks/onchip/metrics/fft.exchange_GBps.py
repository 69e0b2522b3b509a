"""Bytes each chip must send per transform (two exchanges of the part of
its n/p elements that belong elsewhere) over the device time of the
collective operations per transform per chip, in GB/s.  Nothing to read
on one chip."""

import workcount


def read(run):
    if run.trace is None or run.info["p"] < 2:
        return None
    coll_s = run.trace.class_s.get("collective", 0.0)
    if coll_s <= 0.0:
        return None
    sent = workcount.fft_exchange_bytes(run.info["n"], run.info["p"])
    return sent * len(run.calls) / coll_s / 1e9
