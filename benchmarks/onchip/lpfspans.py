"""The program's own spans, as a run's window recorded them.

The program writes each span it crosses as one ``jax.monitoring``
duration event under the span's name (``repro.core.spans``), and
``run.py``'s listener keeps those of the window in ``run.events``.  A
program without the spans leaves nothing to read.
"""

#: exec_'s stages before the call: tracing (LPF's planning included),
#: lowering, and the executable's fetch from the persistent compilation
#: cache or its compile
PREPARE = ("lpf.exec.trace", "lpf.exec.lower", "lpf.exec.compile")
#: LPF's planning of supersteps: each sync, and each flush of a recorded
#: trace; both nest inside lpf.exec.trace
PLAN = ("lpf.sync", "lpf.flush")


def ms_per_call(run, names):
    """Milliseconds the spans ``names`` took in the window per call, or
    None where the window crossed none of them."""
    found = [run.event_total(name) for name in names]
    if not any(count for count, _ in found):
        return None
    return 1e3 * sum(total for _, total in found) / len(run.calls)
