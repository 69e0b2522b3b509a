"""Spans: named host-time intervals at LPF's layer boundaries.

``with span("lpf.sync"): ...`` writes the interval twice, under the same
name:

* into the profiler's trace, as a ``jax.profiler.TraceAnnotation``, on
  the clock of the device timelines (only while a profile is taken);
* as one ``jax.monitoring`` duration event in seconds, with ``parent``
  the name of the enclosing span on this thread (``""`` at the top), for
  any listener registered with
  ``jax.monitoring.register_event_duration_secs_listener``.

The number of a span's events is its counter: one per crossing of the
boundary.  Spans are always on; a span costs a few microseconds.  The
event is recorded when the body raises too.

The program's spans:

* ``lpf.exec.trace``, ``lpf.exec.lower``, ``lpf.exec.compile`` and
  ``lpf.exec.run``: ``exec_``'s four stages, tracing the SPMD function
  (with all LPF work done while tracing), lowering it, fetching the
  executable from the persistent compilation cache or compiling it, and
  placing the arguments and dispatching (with ``jit=False``, the whole
  eager call);
* ``lpf.exec.hit``: ``exec_`` found the executable of an equal call
  kept, and runs it with no trace, lower or compile;
* ``lpf.sync``: planning and lowering one superstep, or deferring it
  into a recording;
* ``lpf.flush``: a recorded trace's program fetch or build,
  certification, compilation and lowering;
* ``lpf.fft.layout``: ``bsp_fft``'s cyclic layout of its input;
* ``lpf.pagerank.upload``: ``lpf_pagerank``'s upload of the partitioned
  graph.
"""

from __future__ import annotations

import threading
import time

import jax

__all__ = ["span"]

#: ``names``: the spans open on this thread, innermost last
_open = threading.local()


class span:
    """Context manager: time the body as the span ``name``."""

    __slots__ = ("name", "_parent", "_annotation", "_t0")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(self.name)
        self._annotation.__enter__()
        stack = getattr(_open, "names", None)
        if stack is None:
            stack = _open.names = []
        self._parent = stack[-1] if stack else ""
        stack.append(self.name)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        _open.names.pop()
        jax.monitoring.record_event_duration_secs(self.name, seconds,
                                                  parent=self._parent)
