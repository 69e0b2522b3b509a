"""Mean host time of the call into bsp_fft, until it returns and before
the wait for its result: the benchmark's ``bench.call`` span."""


def read(run):
    return 1e3 * sum(ret - called for called, ret, _ in run.calls) / len(
        run.calls)
