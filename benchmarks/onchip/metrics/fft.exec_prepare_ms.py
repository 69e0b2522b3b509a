"""Per transform, the host time exec_ spends preparing the program it
runs, from the program's own spans: tracing (LPF's planning included),
lowering, and the executable's fetch from the persistent compilation
cache, in ms."""

import lpfspans


def read(run):
    return lpfspans.ms_per_call(run, lpfspans.PREPARE)
