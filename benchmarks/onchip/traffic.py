"""The one traffic generator: a closed loop over the system's timed call.

A mix file (``mixes/<name>.json``) gives ``devices``, the chips in the
mesh the calls run over; the system module builds its mesh from it.

One call is in flight at a time: each is waited on before the next is
made.  The loop runs until ``seconds`` have passed since the first call.
Each call is recorded as ``(called, returned, done)`` on the host's
clock: ``returned`` when the program handed back control, ``done`` when
its result was ready.
"""

from __future__ import annotations

import time

import jax


def closed_loop(call, wait, keep, seconds: float) -> list:
    """Drive ``call(i)`` for ``seconds``; ``wait(out)`` blocks until
    ``out`` is ready and ``keep(i, out)`` may retain it for the check.
    Returns the call records in call order."""
    records = []
    start = time.perf_counter()
    i = 0
    while time.perf_counter() - start < seconds:
        t_call = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.call"):
            out = call(i)
        t_ret = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.wait"):
            wait(out)
        records.append((t_call, t_ret, time.perf_counter()))
        keep(i, out)
        del out
        i += 1
    return records
