"""Per solve, the host time JAX spends making the program it runs:
tracing, lowering to MLIR, and the backend step, which fetches the
executable from the persistent compilation cache (or compiles it), from
JAX's own events in the window, in ms."""

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


def read(run):
    total = sum(run.event_total(name)[1] for name in EVENTS)
    return 1e3 * total / len(run.calls)
