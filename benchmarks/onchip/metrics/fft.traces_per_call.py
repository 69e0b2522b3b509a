"""JAX jaxpr traces in the window (its own
``/jax/core/compile/jaxpr_trace_duration`` events) per transform."""

TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


def read(run):
    count, _ = run.event_total(TRACE_EVENT)
    return count / len(run.calls)
