"""The whole transform's share of the chips' roofline, in percent: the
least time the chips could take for one transform (5 n log2 n flops or
one read and one write of the vector, at the published peaks) over the
traced window's time per transform."""

import workcount


def read(run):
    if run.trace is None or run.peak is None:
        return None
    n, p = run.info["n"], run.info["p"]
    least = workcount.least_seconds(workcount.fft_flops(n),
                                    workcount.fft_bytes(n), p, run.peak)
    return 100.0 * least * len(run.calls) / run.window_s
