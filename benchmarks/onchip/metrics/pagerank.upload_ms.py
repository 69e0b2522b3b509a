"""Per solve, the host time of lpf_pagerank's upload of the partitioned
graph (the program's ``lpf.pagerank.upload`` span), in ms."""

import lpfspans


def read(run):
    return lpfspans.ms_per_call(run, ("lpf.pagerank.upload",))
