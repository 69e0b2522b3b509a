"""Reduce a profiler trace to the numbers the benchmark reports.

A trace, as read here, is a set of device timelines (one per chip: the
operations that ran on it, each with start and duration) and the host's
events (the benchmark's own ``bench.*`` spans among them), all in
nanoseconds on one clock.  From it:

* busy time: the union of the operation intervals on each chip inside
  the window, and the idle share ``1 - busy / window``;
* device time by operation and by class (fft, matmul, gather/scatter,
  collective, copy, other), counting only ops that hold no others;
* idle gaps, each labelled by what the host was doing over it: the
  ``bench.*`` span that covers the gap's midpoint and, inside it, the
  innermost host event on the same thread.

The window is from the start of the first ``bench.*`` span to the end of
the last one.  All per-chip quantities are averaged over the chips.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import json
import re

BENCH_PREFIX = "bench."

_OPCODE = re.compile(r" = .*? ([a-z][a-z0-9-]*)\(")
_TARGET = re.compile(r'custom_call_target="([^"]+)"')
_KIND = re.compile(r"kind=(k[A-Za-z]+)")

_COLLECTIVE = re.compile(r"^(all-to-all|all-gather|all-reduce|reduce-scatter|"
                         r"collective-permute|send|recv)")
_COPY = {"copy", "copy-start", "copy-done", "transpose", "concatenate",
         "slice", "dynamic-slice", "dynamic-update-slice", "pad", "bitcast",
         "broadcast", "reshape"}
#: custom calls that only split or join complex numbers
_COPY_TARGETS = {"X64SplitLow", "X64SplitHigh", "X64Combine"}


def _parse(text: str) -> tuple:
    """(short name, opcode, detail) of a TPU op event's HLO text, e.g.
    ``%fusion.14 = f32[...] fusion(...), kind=kCustom, ...`` gives
    ``("fusion.14", "fusion", "kCustom")``.  Plain names pass through."""
    short = text.split(" = ")[0].lstrip("%")
    m = _OPCODE.search(text)
    opcode = m.group(1) if m else short.split(".")[0]
    detail = _TARGET.search(text) or _KIND.search(text)
    return short, opcode, detail.group(1) if detail else ""


def op_name(text: str) -> str:
    """Short op name for reports, with a custom call's target or a
    fusion's kind: ``fusion.14[kCustom]``."""
    short, _, detail = _parse(text)
    return f"{short}[{detail}]" if detail else short


def op_class(text: str) -> str:
    """fft, matmul, gather_scatter, collective, copy or other.

    XLA's TPU backend computes a large complex FFT as convolutions
    (``matmul``), and emits gathers and scatters as ``kCustom``
    fusions."""
    short, opcode, detail = _parse(text)
    if opcode == "fft":
        return "fft"
    if _COLLECTIVE.match(opcode):
        return "collective"
    if (opcode in ("convolution", "dot") or "convolution" in short
            or short.startswith("dot")):
        return "matmul"
    if opcode in ("gather", "scatter") or detail == "kCustom":
        return "gather_scatter"
    if opcode in _COPY or detail in _COPY_TARGETS:
        return "copy"
    return "other"


@dataclasses.dataclass
class Trace:
    #: chip name -> [(start_ns, dur_ns, op name)]
    device: dict
    #: [(start_ns, dur_ns, name, thread)]
    host: list

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls({k: [tuple(e) for e in v] for k, v in d["device"].items()},
                   [tuple(e) for e in d["host"]])


def load_xplane(path: str) -> Trace:
    """Read a ``.xplane.pb``: TPU chips from their ``XLA Ops`` lines; on
    a host without chips (a rehearsal) the XLA CPU client's threads
    stand in for one device."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device, host, cpu_ops = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device[plane.name] = [(e.start_ns, e.duration_ns, e.name)
                                          for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.start_ns, e.duration_ns, e.name, line.name)
                       for e in line.events]
                if "XLA" in line.name and "Cpu" in line.name:
                    cpu_ops += [e[:3] for e in evs
                                if not e[2].startswith(("end:", "Threadpool"))]
                else:
                    host += evs
    if not device and cpu_ops:
        device["cpu"] = sorted(cpu_ops)
    return Trace(device, host)


def _leaves(events):
    """Events sorted by start, and whether each is a leaf: an op that
    holds others (a ``while`` around its body's ops) counts in busy time
    but not in the time by operation, where its body's ops count."""
    events = sorted(events)
    leaf = [True] * len(events)
    for k, (s, d, _) in enumerate(events[:-1]):
        if events[k + 1][0] < s + d:
            leaf[k] = False
    return events, leaf


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


@dataclasses.dataclass
class Summary:
    window_s: float
    chips: int
    busy_s: float                       # mean over chips
    op_s: dict                          # op name -> seconds, mean over chips
    class_s: dict                       # class -> seconds, mean over chips
    gap_s: dict                         # host label -> idle seconds, mean

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gap_s.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


class _HostIndex:
    """Finds what the host was doing at an instant."""

    def __init__(self, host):
        self.bench = sorted((s, s + d, n, t) for s, d, n, t in host
                            if n.startswith(BENCH_PREFIX))
        threads = {t for *_, t in self.bench}
        self.inner = sorted((s, s + d, n) for s, d, n, t in host
                            if t in threads and not n.startswith(BENCH_PREFIX))
        self.starts = [e[0] for e in self.inner]

    def window(self):
        if not self.bench:
            return None
        return self.bench[0][0], max(e[1] for e in self.bench)

    def label(self, t: float) -> str:
        span = [b for b in self.bench if b[0] <= t < b[1]]
        if not span:
            return "outside"
        name = min(span, key=lambda b: b[1] - b[0])[2]
        # innermost host event covering t: scan back from the last start
        k = bisect.bisect_right(self.starts, t)
        best = None
        for s, e, n in reversed(self.inner[max(0, k - 4096):k]):
            if s <= t < e and (best is None or e - s < best[1] - best[0]):
                best = (s, e, n)
        return f"{name}/{best[2]}" if best else name


def reduce(trace: Trace, window=None) -> Summary:
    """Summarise ``trace`` over ``window`` (start_ns, end_ns); by default
    the window of the ``bench.*`` spans."""
    hosts = _HostIndex(trace.host)
    if window is None:
        window = hosts.window()
    if window is None:
        evs = [e for v in trace.device.values() for e in v]
        window = (min(s for s, _, _ in evs), max(s + d for s, d, _ in evs))
    w0, w1 = window
    chips = max(1, len(trace.device))
    busy = 0.0
    op_ns = collections.Counter()
    gap_ns = collections.Counter()
    for events in trace.device.values():
        clipped = []
        for (s, d, name), leaf in zip(*_leaves(events)):
            a, b = max(s, w0), min(s + d, w1)
            if b > a:
                clipped.append((a, b))
                if leaf:
                    op_ns[name] += b - a
        merged = _merge(clipped)
        busy += sum(b - a for a, b in merged)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gap_ns[hosts.label((a + b) / 2)] += b - a
    class_ns = collections.Counter()
    name_ns = collections.Counter()
    for name, ns in op_ns.items():
        class_ns[op_class(name)] += ns
        name_ns[op_name(name)] += ns
    per = 1e9 * chips
    return Summary(
        window_s=(w1 - w0) / 1e9, chips=chips, busy_s=busy / per,
        op_s={k: v / per for k, v in name_ns.items()},
        class_s={k: v / per for k, v in class_ns.items()},
        gap_s={k: v / per for k, v in gap_ns.items()})


def load(path: str) -> Trace:
    with open(path) as f:
        return Trace.from_json(json.load(f))
