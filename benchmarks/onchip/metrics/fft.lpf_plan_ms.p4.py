"""Per transform, the host time LPF spends planning and lowering its
supersteps (the program's ``lpf.sync`` and ``lpf.flush`` spans, inside
exec_'s trace), in ms."""

import lpfspans


def read(run):
    return lpfspans.ms_per_call(run, lpfspans.PLAN)
