"""Of the window's staged ``exec_`` calls, the share that ran a kept
executable instead of tracing anew: 100 × ``lpf.exec.hit`` /
(``lpf.exec.hit`` + ``lpf.exec.trace``), in %.  None where the window
crossed neither span."""


def read(run):
    hits, _ = run.event_total("lpf.exec.hit")
    traces, _ = run.event_total("lpf.exec.trace")
    if not hits + traces:
        return None
    return 100.0 * hits / (hits + traces)
