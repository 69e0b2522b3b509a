"""Per transform, the host time of bsp_fft's cyclic layout of its input
(the program's ``lpf.fft.layout`` span), in ms."""

import lpfspans


def read(run):
    return lpfspans.ms_per_call(run, ("lpf.fft.layout",))
