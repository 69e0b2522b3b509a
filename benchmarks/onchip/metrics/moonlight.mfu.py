"""Useful work per training step (``modelcount.train_step_flops``: 6 x
the matmul parameters a token passes through here x tokens, plus 3 x
the causal attention forward) over the traced window's time per step, as
a share of the chip's published peak, in percent."""

import modelcount


def read(run):
    if run.trace is None or run.peak is None:
        return None
    flops = modelcount.train_step_flops(run.config, int(run.mix["seq_len"]),
                                        int(run.mix["batch"]))
    per_step = run.window_s / len(run.calls)
    return 100.0 * flops / (per_step * run.peak["flops"])
