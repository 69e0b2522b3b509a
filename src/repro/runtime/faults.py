"""Deterministic fault injection and the chaos soak harness.

The LPF paper's error contract promises that *mitigable* errors are
side-effect-free (the caller may resize and retry) and that anything
else is classified before communication is issued.  This module makes
that contract testable: a :class:`FaultPlan` is a deterministic,
seedable schedule of infrastructure failures fired at the execution
stack's defined seams (see :mod:`repro.core.faultpoints`):

========================  ==================================================
seam                      injected failure
========================  ==================================================
``persist_save``          ``OSError`` out of ``PersistentStore.save``
                          (full disk / read-only cache dir)
``persist_load``          ``OSError``, truncated, or bit-flipped read out
                          of ``PersistentStore._read``
``straggler``             wall-clock delay before a schedule issues
``capacity``              mitigable ``LPFCapacityError`` at staging time
``serve_admit``           :class:`InjectedFault` during request admission
                          (``LPFServer.submit``)
``serve_decode``          :class:`InjectedFault` before a decode batch
                          issues (``LPFServer.step``)
========================  ==================================================

No seam fires unless a plan is **armed** (:func:`arm` / :func:`inject`
/ the ``LPF_FAULT_PLAN`` env var), and an unarmed seam is a single
``is None`` check — the zero-fault path is byte-identical with the
machinery in the tree.

Plan grammar (``FaultPlan.parse`` / ``.spec()`` round-trip)::

    LPF_FAULT_PLAN="capacity@0;persist_load@1:bitflip;straggler@2=0.05"

    event   := seam "@" at ["x" repeat] [":" mode] ["=" arg]
    at      := 0-based invocation index of the seam at which to fire
    repeat  := consecutive firings from `at` (default 1, -1 = forever)
    mode    := persist_load only: oserror | truncate | bitflip
    arg     := straggler only: delay seconds (default 0.02)

The chaos soak harness (``python -m repro.runtime.faults --chaos
--seeds N``) replays warm-start, bucketed-sync, decode, and serve
workloads under seeded random plans and asserts the core invariant:
every run either completes with numerics and ledger **identical** to
the fault-free run, or raises a **classified**
:class:`repro.core.LPFError` before any communication is issued —
never an unclassified exception, never an unverified execution.
``--smoke`` runs one fixed plan per seam (the CI tripwire that keeps
the seams from rotting).

The ``serve`` workload's invariant is per *request*, not per run
(:func:`_serve_compare`): under any fault-plus-overload plan every
request either completes with tokens bit-identical to its unloaded
solo decode, or terminates refused with a classified
:class:`~repro.runtime.server.ServeRejected` — and the server object
itself must survive the whole arrival sequence (an exception escaping
the serve loop fails the run even if it is an LPFError).
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import errno
import os
import random
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

# NOTE: module level stays stdlib-only — arming a plan (e.g. from
# LPFContext reading LPF_FAULT_PLAN) must not drag in jax; the chaos
# harness imports the heavy stack lazily inside its functions.
from ..core.faultpoints import SEAMS, InjectedFault, _install
from ..core import faultpoints as _faultpoints

__all__ = ["FaultEvent", "FaultPlan", "FaultInjector", "InjectedFault",
           "SEAMS", "arm", "disarm", "active", "inject",
           "ensure_env_plan", "SMOKE_PLANS", "chaos_main"]

#: default injected straggler delay (seconds) when an event has no arg
DEFAULT_DELAY = 0.02

_MODES = {
    "persist_save": ("",),
    "persist_load": ("oserror", "truncate", "bitflip"),
    "straggler": ("",),
    "capacity": ("",),
    "serve_admit": ("",),
    "serve_decode": ("",),
}

_EVENT_RE = re.compile(
    r"^(?P<seam>[a-z_]+)@(?P<at>\d+)"
    r"(?:x(?P<repeat>-?\d+))?"
    r"(?::(?P<mode>[a-z_]+))?"
    r"(?:=(?P<arg>[0-9.eE+\-]+))?$")


@dataclasses.dataclass(frozen=True)
class FaultEvent:
    """One scheduled failure: fire at the ``at``-th invocation of
    ``seam`` (0-based), for ``repeat`` consecutive invocations
    (-1 = every invocation from ``at`` on)."""

    seam: str
    at: int
    mode: str = ""
    arg: float = 0.0
    repeat: int = 1

    def __post_init__(self):
        if self.seam not in SEAMS:
            raise ValueError(f"unknown seam {self.seam!r}; one of {SEAMS}")
        if self.mode and self.mode not in _MODES[self.seam]:
            raise ValueError(
                f"seam {self.seam!r} has no mode {self.mode!r}")
        if self.at < 0:
            raise ValueError("event index must be >= 0")
        if self.repeat == 0:
            raise ValueError("repeat must be nonzero (-1 = forever)")

    def due(self, idx: int) -> bool:
        if idx < self.at:
            return False
        return self.repeat < 0 or idx < self.at + self.repeat

    def spec(self) -> str:
        s = f"{self.seam}@{self.at}"
        if self.repeat != 1:
            s += f"x{self.repeat}"
        if self.mode:
            s += f":{self.mode}"
        if self.arg:
            s += f"={self.arg:g}"
        return s


@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A deterministic schedule of :class:`FaultEvent`; the unit the
    chaos harness seeds, replays, and prints on failure."""

    events: Tuple[FaultEvent, ...]
    seed: Optional[int] = None

    def spec(self) -> str:
        """The parseable textual form (``LPF_FAULT_PLAN`` syntax)."""
        return ";".join(e.spec() for e in self.events)

    def seams(self) -> Tuple[str, ...]:
        return tuple(sorted({e.seam for e in self.events}))

    @classmethod
    def parse(cls, spec: str) -> "FaultPlan":
        events = []
        for part in spec.split(";"):
            part = part.strip()
            if not part:
                continue
            m = _EVENT_RE.match(part)
            if m is None:
                raise ValueError(f"malformed fault event {part!r} "
                                 f"(grammar: seam@at[xN][:mode][=arg])")
            events.append(FaultEvent(
                seam=m.group("seam"), at=int(m.group("at")),
                mode=m.group("mode") or "",
                arg=float(m.group("arg") or 0.0),
                repeat=int(m.group("repeat") or 1)))
        return cls(events=tuple(events))

    @classmethod
    def random(cls, seed: int, seams: Sequence[str] = SEAMS,
               max_events: int = 3) -> "FaultPlan":
        """A seed-deterministic plan over ``seams`` (stdlib ``random``
        so the draw never skews across numpy versions)."""
        rng = random.Random(seed)
        events = []
        for _ in range(rng.randint(1, max_events)):
            seam = rng.choice(list(seams))
            mode = rng.choice(_MODES[seam]) if seam == "persist_load" \
                else ""
            # mostly one-shot faults; occasionally a *persistent* one
            # (every invocation fails) to drive the degradation ladder
            # to its terminal rung (memory-only mode / classified error)
            repeat = -1 if rng.random() < 0.2 else 1
            arg = round(rng.uniform(0.001, DEFAULT_DELAY), 4) \
                if seam == "straggler" else 0.0
            events.append(FaultEvent(seam=seam, at=rng.randint(0, 2),
                                     mode=mode, arg=arg, repeat=repeat))
        return cls(events=tuple(events), seed=seed)


class FaultInjector:
    """Counts seam invocations and fires the armed plan's due events.

    ``fired`` records every injected failure as ``(seam, invocation
    index, mode)`` so tests can assert a plan actually exercised its
    target (a plan that never fires proves nothing)."""

    def __init__(self, plan: FaultPlan):
        self.plan = plan
        self.counts: Dict[str, int] = collections.Counter()
        self.fired: List[Tuple[str, int, str]] = []

    def _next(self, seam: str) -> Optional[FaultEvent]:
        idx = self.counts[seam]
        self.counts[seam] = idx + 1
        for e in self.plan.events:
            if e.seam == seam and e.due(idx):
                self.fired.append((seam, idx, e.mode or "default"))
                return e
        return None

    # -- seam entry points (see repro.core.faultpoints) -----------------
    def fire(self, seam: str, **info) -> None:
        e = self._next(seam)
        if e is None:
            return
        if seam == "persist_save":
            raise OSError(errno.ENOSPC, "injected fault: disk full")
        if seam == "capacity":
            from ..core.errors import LPFCapacityError
            staged = int(info.get("staged", 0))
            new = int(info.get("new", 1))
            cap = int(info.get("capacity", 0))
            raise LPFCapacityError(
                f"injected fault: message queue capacity exhausted "
                f"({staged} staged + {new} new > effective capacity)",
                required=staged + new, capacity=cap, kind="queue")
        if seam == "serve_admit":
            raise InjectedFault(
                f"injected fault: admission infrastructure failure "
                f"(rid={info.get('rid')})")
        if seam == "serve_decode":
            raise InjectedFault(
                f"injected fault: decode launch failure "
                f"(bucket={info.get('bucket')}, "
                f"fallback={bool(info.get('fallback'))})")
        raise AssertionError(f"seam {seam!r} has no fire() action")

    def corrupt(self, seam: str, blob: bytes) -> bytes:
        e = self._next(seam)
        if e is None:
            return blob
        mode = e.mode or "oserror"
        if mode == "oserror":
            raise OSError(errno.EIO, "injected fault: read failure")
        if mode == "truncate":
            return blob[:len(blob) // 2]
        # bitflip: corrupt one payload byte; the checksum must catch it
        pos = len(blob) // 2
        flipped = bytes([blob[pos] ^ 0x40])
        return blob[:pos] + flipped + blob[pos + 1:]

    def delay(self, seam: str, **info) -> float:
        e = self._next(seam)
        if e is None:
            return 0.0
        return e.arg if e.arg > 0 else DEFAULT_DELAY


# ==========================================================================
# arming
# ==========================================================================

def arm(plan: FaultPlan) -> FaultInjector:
    """Arm ``plan`` process-wide (replacing any armed injector) and
    return its injector."""
    inj = FaultInjector(plan)
    _install(inj)
    return inj


def disarm() -> None:
    _install(None)


def active() -> Optional[FaultInjector]:
    """The armed injector, or ``None`` on the zero-fault path."""
    return _faultpoints._INJECTOR


@contextlib.contextmanager
def inject(plan: FaultPlan):
    """``with inject(plan) as inj: ...`` — arm for the block, restore
    the previously armed injector (usually none) on exit."""
    inj = FaultInjector(plan)
    prev = _install(inj)
    try:
        yield inj
    finally:
        _install(prev)


def ensure_env_plan() -> Optional[FaultInjector]:
    """Arm the ``LPF_FAULT_PLAN`` env plan if one is set and nothing is
    armed yet (idempotent: a root :class:`LPFContext` calls this on
    construction)."""
    spec = os.environ.get("LPF_FAULT_PLAN")
    if not spec or _faultpoints.armed():
        return active()
    return arm(FaultPlan.parse(spec))


# ==========================================================================
# chaos workloads
# ==========================================================================
#
# Each workload is a deterministic function returning a comparable
# result (numerics + ledger / predicted costs); the harness runs it
# fault-free once (the baseline), then under each seeded plan, and
# asserts identical-result-or-classified-error.  Workloads declare
# which seams they can reach so random plans are drawn to actually
# fire (a persist fault cannot fire in a workload with no store).

def _np():
    import numpy as np
    return np


def _wl_warm_start() -> dict:
    """Record every canned trace into a persistent cache, then
    warm-start a fresh cache from the same directory — the PR-8
    cross-process claim, here as a fault target for the persist-I/O
    seams.  Pure Python (no devices): disk faults must be absorbed by
    the degradation ladder, so this workload ALWAYS completes and must
    always match the baseline."""
    import tempfile
    from ..analysis.traces import CANNED_TRACES
    from ..core import CPU_HOST, PlanCache, ProgramCache, probe
    machine = probe({"x": 8}, CPU_HOST)
    out = {}
    with tempfile.TemporaryDirectory() as tmp:
        for phase in ("record", "warm"):
            pc = ProgramCache(persist_dir=tmp)
            plan_cache = PlanCache()
            for name, builder in sorted(CANNED_TRACES.items()):
                p, _slots, steps, scratch = builder()
                prog, key = pc.get_or_build_keyed(
                    steps, p, machine, plan_cache=plan_cache,
                    scratch=scratch)
                cert = pc.certify(key, steps, prog, scratch=scratch)
                if not cert.ok:   # pragma: no cover - verifier backstop
                    raise AssertionError(f"uncertified schedule: {name}")
                out[(phase, name)] = tuple(st.plan.cost
                                           for st in prog.steps)
    return {"costs": out}


def _run_mesh_trace(steps, slots, *, use_with_capacity: bool = True):
    """Issue a canned trace through the real ``ctx.program`` path on
    the host mesh; returns values + the ledger records."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from ..core import LPFContext, PlanCache, ProgramCache, compat

    n = jax.device_count()
    mesh = compat.make_mesh((n,), ("x",))
    pc, pgc = PlanCache(), ProgramCache()
    box = {}

    def wrapped(_):
        ctx = LPFContext(("x",), plan_cache=pc, program_cache=pgc)
        ctx.resize_memory_register(len(slots) + 1)
        smap = {}
        for s in slots:
            init = (jnp.arange(s.size, dtype=jnp.int32) * 7
                    + s.sid * 1000 + ctx.pid.astype(jnp.int32) * 37)
            smap[s.sid] = ctx.register_global(s.name, init)

        def region(c):
            with c.program("chaos"):
                for st in steps:
                    c.put_msgs([(m.src, m.dst, smap[m.src_slot.sid],
                                 m.src_off, smap[m.dst_slot.sid],
                                 m.dst_off, m.size) for m in st.msgs])
                    c.sync(st.attrs, label=st.label)
            return tuple(c.value(smap[s.sid]) for s in slots)

        ctx.resize_message_queue(max(len(st.msgs) for st in steps))
        if use_with_capacity:
            outs = ctx.with_capacity(region)
        else:
            outs = region(ctx)
        box["ledger"] = list(ctx.ledger.records)
        return outs

    fn = jax.jit(compat.shard_map(
        wrapped, mesh=mesh, in_specs=(P(),),
        out_specs=tuple(P("x") for _ in slots), check_vma=False))
    outs = fn(jnp.zeros(1))
    values = {s.sid: np.asarray(v).reshape(n, s.size)
              for s, v in zip(slots, outs)}
    return {"values": values, "ledger": box["ledger"]}


def _wl_bucketed_sync() -> dict:
    """The DDP bucketed gradient sync shape on the host mesh: capacity
    exercises resize-and-retry, the straggler seam only costs wall
    clock."""
    import jax
    from ..analysis.traces import canned_bucketed_trace
    p, slots, steps, _scratch = canned_bucketed_trace(
        p=jax.device_count(), n_buckets=3, w=8)
    return _run_mesh_trace(steps, slots)


def _wl_decode() -> dict:
    """A decode-step-shaped loop: ``compile_loop`` rolls an iterated
    one-superstep ring shift (the serve path's per-token program) into
    one XLA scan; faults land on the body's single recorded program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P
    from ..core import LPFContext, PlanCache, ProgramCache, compat

    n = jax.device_count()
    mesh = compat.make_mesh((n,), ("x",))
    pc, pgc = PlanCache(), ProgramCache()
    box = {}

    def wrapped(_):
        ctx = LPFContext(("x",), plan_cache=pc, program_cache=pgc)

        def body(c2, carry):
            c2.resize_memory_register(2)
            c2.resize_message_queue(c2.p)
            a = c2.register_global("tok", carry)
            b = c2.register_global("nxt", jnp.zeros_like(carry))
            c2.put(a, b, to=lambda s_: (s_ + 1) % c2.p, size=4)
            c2.sync(label="decode.shift")
            out = c2.value(b) + 1.0
            c2.deregister(a)
            c2.deregister(b)
            return out

        x0 = jnp.arange(4.0) + ctx.pid
        final = ctx.compile_loop(body, x0, n_iters=4, label="decode")
        box["ledger"] = list(ctx.ledger.records)
        return final

    fn = jax.jit(compat.shard_map(wrapped, mesh=mesh, in_specs=(P(),),
                                  out_specs=P("x"), check_vma=False))
    out = _np().asarray(fn(jnp.zeros(1))).reshape(n, 4)
    return {"values": {0: out}, "ledger": box["ledger"]}


def _wl_serve() -> dict:
    """The hardened serve loop under fault-plus-overload: a burst
    arrival pattern into a small bounded queue (driving the ladder
    through shrink, shed, and backpressure) while the ``serve_admit``
    and ``serve_decode`` seams (plus the program layer's ``straggler``)
    fire.  The result carries every request's terminal
    state AND the per-request solo-decode reference streams; the
    invariant is per request (:func:`_serve_compare`), because under
    faults a *different* admission mix is legitimate — what is never
    legitimate is a completed request whose tokens differ from its
    unloaded solo decode, an unclassified refusal, a missed deadline
    for an admitted request, or a dead server."""
    from ..runtime.server import (LPFServer, ProgramDecodeEngine,
                                  synthetic_requests)
    eng = ProgramDecodeEngine(buckets=((2, 8), (4, 8)))
    reqs = synthetic_requests(
        24, seed=7, buckets=eng.buckets(),
        token_cost_s=eng.token_seconds((4, 8)), deadline_scale=60.0)
    # the unloaded baseline: every request decoded solo, fault-free as
    # far as the serve seams go (they fire only inside LPFServer).
    # Both serve buckets share cache_len, so streams are bucket-
    # independent and one solo decode per request suffices.
    ref = {}
    for r in reqs:
        t = eng.round_tokens((2, 8), r.n_tokens)
        ref[r.rid] = eng.decode((2, 8), [r], t)[r.rid][:r.n_tokens]
    served: Dict[int, tuple] = {}
    try:
        srv = LPFServer(eng, max_queue=6)
        # bursts of 4 submissions per decode step: the queue saturates,
        # the ladder climbs, and admission keeps being exercised
        for i in range(0, len(reqs), 4):
            for r in reqs[i:i + 4]:
                srv.submit(r)
            srv.step()
        srv.drain()
    except BaseException as e:   # noqa: BLE001 - the invariant under test
        return {"server_died": f"{type(e).__name__}: {e}", "ref": ref,
                "served": served, "health": {}}
    for rid, out in srv.take_outcomes().items():
        if out.status == "completed":
            ok_deadline = out.completion_v <= out.predicted_v + 1e-12
            served[rid] = ("completed", out.tokens, ok_deadline)
        else:
            served[rid] = (out.status, out.reason, out.classified)
    return {"server_died": None, "ref": ref, "served": served,
            "health": srv.health()}


def _serve_compare(res: dict, baseline: dict) -> Tuple[bool, str]:
    """The serve chaos invariant, request by request (see
    :func:`_wl_serve`).  ``res`` may legitimately admit a different
    mix than ``baseline``; only ``baseline['ref']`` (the unloaded
    solo-decode streams) anchors the numeric comparison."""
    if res["server_died"]:
        return False, f"server died: {res['server_died']}"
    h = res["health"]
    if h.get("deadline_misses", 0) != 0:
        return False, f"{h['deadline_misses']} admitted request(s) " \
                      f"missed their model-clock deadline"
    ref = baseline["ref"]
    if set(res["served"]) != set(ref):
        return False, "request(s) vanished without a terminal outcome"
    for rid, term in sorted(res["served"].items()):
        if term[0] == "completed":
            _, tokens, ok_deadline = term
            if not ok_deadline:
                return False, f"rid {rid}: completed past its " \
                              f"admission-predicted bound"
            if tuple(tokens) != tuple(ref[rid]):
                return False, f"rid {rid}: tokens differ from the " \
                              f"unloaded solo decode"
        else:
            status, reason, classified = term
            if not classified:
                return False, f"rid {rid}: {status} ({reason}) " \
                              f"without a classified LPFError"
    return True, ""


#: workload name -> (fn, seams random plans may draw from)
WORKLOADS = {
    "warm_start": (_wl_warm_start, ("persist_save", "persist_load")),
    "bucketed_sync": (_wl_bucketed_sync, ("straggler", "capacity")),
    "decode": (_wl_decode, ("straggler", "capacity")),
    "serve": (_wl_serve, ("serve_admit", "serve_decode", "straggler")),
}

#: workloads whose pass criterion is not whole-result equality; the
#: comparator returns ``(ok, why_not)`` against the fault-free baseline
_COMPARATORS = {
    "serve": _serve_compare,
}

#: the CI smoke matrix: one fixed plan per seam (and per persist_load
#: corruption mode), each pinned to a workload that can reach it
SMOKE_PLANS = (
    ("warm_start", "persist_save@0"),
    ("warm_start", "persist_save@0x-1"),
    ("warm_start", "persist_load@0:oserror"),
    ("warm_start", "persist_load@0:truncate"),
    ("warm_start", "persist_load@0:bitflip"),
    ("bucketed_sync", "straggler@0=0.005"),
    ("bucketed_sync", "capacity@0"),
    ("decode", "capacity@0"),
    ("serve", "serve_admit@0"),
    ("serve", "serve_admit@0x-1"),
    ("serve", "serve_decode@0"),
    # both the fused attempt and the per-token retry fail: the whole
    # ladder runs and every affected request must end classified
    ("serve", "serve_decode@0x-1"),
)


def _results_equal(a: dict, b: dict) -> bool:
    np = _np()
    if a.keys() != b.keys():
        return False
    for k in a:
        if k == "values":
            if a[k].keys() != b[k].keys():
                return False
            for sid in a[k]:
                if not np.array_equal(a[k][sid], b[k][sid]):
                    return False
        elif a[k] != b[k]:
            return False
    return True


def _run_one(workload: str, plan: Optional[FaultPlan],
             baselines: dict) -> Tuple[str, str]:
    """Run ``workload`` under ``plan`` (or fault-free) and classify the
    outcome against the chaos invariant.  Returns ``(verdict, detail)``
    where verdict is ``identical`` / ``classified`` (both pass) or
    ``MISMATCH`` / ``UNCLASSIFIED`` (both fail)."""
    from ..core.errors import LPFError
    fn, _seams = WORKLOADS[workload]
    if workload not in baselines:
        disarm()
        baselines[workload] = fn()
    fired: List[Tuple[str, int, str]] = []
    try:
        if plan is None:
            res = fn()
        else:
            with inject(plan) as inj:
                res = fn()
                fired = list(inj.fired)
    except LPFError as e:
        # classified before any communication was issued for the
        # failing operation — the contract's acceptable outcome
        return "classified", f"{type(e).__name__}: {e}"
    except Exception as e:   # noqa: BLE001 - the invariant under test
        return "UNCLASSIFIED", f"{type(e).__name__}: {e}"
    compare = _COMPARATORS.get(workload)
    if compare is not None:
        ok, why = compare(res, baselines[workload])
        if not ok:
            return "MISMATCH", why
    elif not _results_equal(res, baselines[workload]):
        return "MISMATCH", "result differs from fault-free baseline"
    return "identical", f"{len(fired)} fault(s) fired"


# ==========================================================================
# CLI
# ==========================================================================

def chaos_main(argv: Optional[Sequence[str]] = None) -> int:
    # the mesh workloads want p=8 host devices, like the test suite;
    # must be decided before jax first imports
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")
    ap = argparse.ArgumentParser(
        prog="python -m repro.runtime.faults",
        description="Deterministic fault injection: chaos soak harness "
                    "and fixed-plan smoke runs.")
    ap.add_argument("--chaos", action="store_true",
                    help="seeded random-plan soak across the workloads")
    ap.add_argument("--smoke", action="store_true",
                    help="one fixed plan per seam (CI tripwire)")
    ap.add_argument("--seeds", type=int, default=100,
                    help="number of seeded plans for --chaos")
    ap.add_argument("--seed0", type=int, default=0,
                    help="first seed (shard long soaks across jobs)")
    ap.add_argument("--plan", type=str, default=None,
                    help="run one explicit plan spec (needs --workload)")
    ap.add_argument("--workload", type=str, default=None,
                    help="workload for --plan")
    ap.add_argument("--workloads", type=str,
                    default=",".join(WORKLOADS),
                    help="comma list to rotate --chaos seeds over")
    args = ap.parse_args(argv)

    baselines: dict = {}
    failures: List[str] = []
    tally = collections.Counter()

    def run(workload: str, plan: Optional[FaultPlan], tag: str) -> None:
        verdict, detail = _run_one(workload, plan, baselines)
        tally[verdict] += 1
        spec = plan.spec() if plan is not None else "<none>"
        line = f"[{tag}] {workload:<14} plan={spec:<40} {verdict}: {detail}"
        print(line)
        if verdict in ("MISMATCH", "UNCLASSIFIED"):
            failures.append(line)

    if args.plan is not None:
        if args.workload not in WORKLOADS:
            ap.error(f"--plan needs --workload (one of {list(WORKLOADS)})")
        run(args.workload, FaultPlan.parse(args.plan), "plan")
    elif args.smoke:
        for workload, spec in SMOKE_PLANS:
            run(workload, FaultPlan.parse(spec), "smoke")
    elif args.chaos:
        names = [w.strip() for w in args.workloads.split(",") if w.strip()]
        for w in names:
            if w not in WORKLOADS:
                ap.error(f"unknown workload {w!r}")
        for i in range(args.seeds):
            seed = args.seed0 + i
            workload = names[seed % len(names)]
            plan = FaultPlan.random(seed, seams=WORKLOADS[workload][1])
            run(workload, plan, f"seed {seed}")
    else:
        ap.error("pick a mode: --chaos, --smoke, or --plan SPEC")

    print(f"\nchaos summary: {dict(tally)}")
    if failures:
        print(f"\n{len(failures)} INVARIANT VIOLATION(S):",
              file=sys.stderr)
        for line in failures:
            print("  " + line, file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(chaos_main())
