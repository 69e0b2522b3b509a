"""LPF contexts — ``lpf_exec``, ``lpf_hook``, ``lpf_rehook`` and the
twelve-primitive surface.

A *context* is a set of mesh axes inside an SPMD (``shard_map``) region.
``exec_`` launches an SPMD function on a mesh (the paper's process
spawning); ``hook`` runs an SPMD function *inside an existing traced
parallel program* — the interoperability mechanism that let the paper call
LPF algorithms from Spark lets us call them from any jit-compiled JAX
program, including a training step.  ``rehook`` re-scopes to a pristine
context, optionally over a sub-set of the axes (the paper's
library-encapsulation mechanism).

The context is imperative at trace time (mirroring the C API): ``put`` /
``get`` stage messages, ``sync`` compiles and executes the superstep, slot
values are read back with ``value`` / ``tensor``.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import os
import threading
import time
from typing import (Any, Callable, Dict, Hashable, List, Optional, Sequence,
                    Tuple, Union)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from . import compat
from . import faultpoints as _fp
from .attrs import LPF_SYNC_DEFAULT, SyncAttributes
from .cost import CostLedger, SuperstepCost
from .errors import LPFAnalysisError, LPFCapacityError, LPFFatalError
from .machine import LPFMachine, HardwareModel, TPU_V5E, probe as _probe
from .memslot import Slot, SlotRegistry
from .program import (ProgramCache, ProgramStep, dependency_cone,
                      global_program_cache)
from .spans import span
from .sync import (Msg, PlanCache, execute_plan, execute_schedule,
                   global_plan_cache)

__all__ = ["LPFContext", "exec_", "hook", "rehook", "LPF_ROOT_AXES"]

PidFn = Union[int, Sequence[int], Callable[[int], int]]
LPF_ROOT_AXES: Tuple[str, ...] = ()


def _per_pid(value: PidFn, p: int, name: str) -> List[int]:
    if callable(value):
        return [int(value(s)) for s in range(p)]
    if isinstance(value, (int, np.integer)):
        return [int(value)] * p
    out = [int(v) for v in value]
    if len(out) != p:
        raise LPFFatalError(f"{name} table must have length p={p}")
    return out


class _CacheStatsView(dict):
    """``ctx.cache_stats``: a dict of the memo layers' counter objects
    (``plan``/``program``) with a ``reset()`` that zeroes them in place —
    benchmarks and the replay tests measure hit/miss deltas without a
    process restart (the cache *contents* stay warm)."""

    def reset(self) -> None:
        for stats in self.values():
            stats.reset()


class LPFContext:
    """The LPF state of one SPMD region (paper: ``lpf_t``)."""

    def __init__(self, axes: Sequence[str] = LPF_ROOT_AXES, *,
                 hardware: HardwareModel = TPU_V5E,
                 plan_cache: Optional[PlanCache] = None,
                 program_cache: Optional[ProgramCache] = None,
                 persist_dir: Optional[str] = None,
                 sanitize: Optional[bool] = None,
                 _parent: Optional["LPFContext"] = None):
        self.axes: Tuple[str, ...] = tuple(axes)
        if self.axes:
            self.p: int = int(lax.psum(1, self.axes if len(self.axes) > 1
                                       else self.axes[0]))
            self.pid = lax.axis_index(self.axes if len(self.axes) > 1
                                      else self.axes[0])
        else:
            self.p = 1
            self.pid = jnp.zeros((), jnp.int32)
        self.hardware = hardware
        #: memoised superstep plans; shared process-wide by default so
        #: repeated h-relations plan once across contexts and traces.
        self.plan_cache = plan_cache if plan_cache is not None \
            else global_plan_cache()
        #: memoised optimized traces for the record/replay program layer
        self.program_cache = program_cache if program_cache is not None \
            else global_program_cache()
        #: persistent program cache (``persist_dir=`` or the
        #: ``LPF_PROGRAM_CACHE_DIR`` env var): certified optimized
        #: programs are written next to the XLA compilation cache and
        #: warm-loaded by any later context/process sharing the
        #: directory — a restarted worker pays zero re-planning and
        #: zero schedule-search cost.  Loaded entries are re-verified
        #: (``verify_program``) against the actual recorded trace
        #: before they may execute.
        if persist_dir is None and _parent is None:
            persist_dir = os.environ.get("LPF_PROGRAM_CACHE_DIR") or None
        if persist_dir:
            self.program_cache.attach_store(persist_dir)
        self.registry = SlotRegistry(capacity=0)
        self.ledger = CostLedger()
        self._queue: List[Msg] = []
        self._queue_capacity = 0
        self._scratch: Optional[Slot] = None
        self._parent = _parent
        self._on_hold = False
        self._rec_depth = 0
        self._rec_labels: List[str] = []
        self._rec_pending: List[ProgramStep] = []
        self._rec_deferred_dereg: List[Slot] = []
        self._gate_machine: Optional[LPFMachine] = None
        #: the most recently executed (optimized) program — inspect the
        #: searched schedule with ``ctx.last_program.explain(machine)``
        self.last_program = None
        #: sanitizer mode (``LPF_SANITIZE=1`` or ``sanitize=True``):
        #: every staged message is checked against live registrations,
        #: every flushed trace is linted (``repro.analysis.linter``) —
        #: error diagnostics raise :class:`LPFAnalysisError` before any
        #: communication is issued, warnings accumulate on
        #: :attr:`diagnostics`.  Sub-contexts (hook/compile_loop)
        #: inherit the parent's setting and diagnostics list.
        if sanitize is None:
            sanitize = _parent.sanitize if _parent is not None \
                else os.environ.get("LPF_SANITIZE", "0") != "0"
        self.sanitize: bool = bool(sanitize)
        self.diagnostics: List[Any] = [] if _parent is None \
            else _parent.diagnostics
        self._rec_registered: List[Slot] = []
        #: per-nesting-level start indices into ``_rec_pending`` — what
        #: lets :meth:`program` *discard* the supersteps recorded at an
        #: aborted level instead of flushing (= executing) a partial
        #: trace when an exception propagates out of the body.  That
        #: discard is what keeps a capacity error side-effect-free, the
        #: precondition of the paper's resize-and-retry contract
        #: (:meth:`with_capacity`).
        self._rec_marks: List[int] = []
        # the deterministic fault-injection hook (LPF_FAULT_PLAN=...):
        # arming is lazy and idempotent — no plan, no injector, and the
        # seams stay single-pointer-compare no-ops
        if _parent is None and os.environ.get("LPF_FAULT_PLAN") \
                and not _fp.armed():
            from ..runtime.faults import ensure_env_plan
            ensure_env_plan()

    # ------------------------------------------------------------------
    # capacity management: lpf_resize_message_queue / _memory_register
    # ------------------------------------------------------------------
    def resize_message_queue(self, n_msgs: int,
                             valiant_payload: int = 0,
                             payload_dtype=jnp.float32) -> None:
        """Reserve queue capacity (O(N) as per the paper).  When
        ``valiant_payload`` > 0 a scratch slot of that many elements is
        provisioned for two-phase routing."""
        if n_msgs < 0:
            raise LPFFatalError("negative queue capacity")
        self._queue_capacity = n_msgs
        if valiant_payload > 0 and self._rec_pending:
            # re-provisioning replaces the scratch slot recorded supersteps
            # may reference — execute them against the current one first
            self._flush_program()
        if valiant_payload > 0:
            # re-provisioning replaces the previous scratch slot; keeping
            # the stale registration would leak register capacity on every
            # resize call
            if self._scratch is not None:
                self.registry.deregister(self._scratch)
                self._scratch = None
            if self.registry.capacity < self.registry.n_active + 1:
                self.registry.resize(self.registry.n_active + 1)
            self._scratch = self.registry.register(
                "__lpf_valiant_scratch", jnp.zeros(valiant_payload,
                                                   payload_dtype), "global")

    def resize_memory_register(self, n_slots: int) -> None:
        reserve = 1 if self._scratch is not None else 0
        self.registry.resize(n_slots + reserve)

    def with_capacity(self, fn: Callable[["LPFContext"], Any], *,
                      max_attempts: int = 3, grow: float = 2.0) -> Any:
        """Run ``fn(ctx)`` under the paper's *mitigable-error* contract:
        an :class:`LPFCapacityError` is side-effect-free, so the caller
        may resize and retry.  This method implements that retry — the
        staged queue (and any supersteps recorded inside the attempt,
        via :meth:`program`'s abort path) is rolled back, the exhausted
        resource (``e.kind``: message queue or memory register) is grown
        to ``max(e.required, current * grow)``, and ``fn`` runs again,
        up to ``max_attempts`` times.  The final attempt's capacity
        error propagates — still mitigable, for a caller with a better
        resize policy."""
        if max_attempts < 1:
            raise LPFFatalError("with_capacity needs max_attempts >= 1")
        for attempt in range(max_attempts):
            queue_snap = list(self._queue)
            pend_snap = len(self._rec_pending)
            try:
                return fn(self)
            except LPFCapacityError as e:
                if attempt == max_attempts - 1:
                    raise
                # the contract says the failed attempt staged nothing;
                # enforce it — drop anything the attempt left behind
                self._queue = queue_snap
                del self._rec_pending[pend_snap:]
                if e.kind == "register":
                    cap = self.registry.capacity
                    self.registry.resize(
                        max(e.required, int(cap * grow) + 1))
                else:
                    cap = self._queue_capacity
                    self.resize_message_queue(
                        max(e.required, int(cap * grow) + 1))
        raise AssertionError("unreachable")  # pragma: no cover

    # ------------------------------------------------------------------
    # registration: lpf_register_{global,local}, lpf_deregister
    # ------------------------------------------------------------------
    def register_global(self, name: str, value, flatten: bool = True) -> Slot:
        slot = self.registry.register(name, value, "global", flatten)
        if self._rec_depth and self.sanitize:
            self._rec_registered.append(slot)
        return slot

    def register_local(self, name: str, value, flatten: bool = True) -> Slot:
        slot = self.registry.register(name, value, "local", flatten)
        if self._rec_depth and self.sanitize:
            self._rec_registered.append(slot)
        return slot

    def deregister(self, slot: Slot) -> None:
        self._rec_registered = [
            s for s in self._rec_registered
            if not (s.sid == slot.sid and s.gen == slot.gen)]
        if self._rec_depth and self._pending_refs(slot):
            # a recorded superstep still moves data through this slot;
            # deregistration takes effect when the trace flushes
            self._rec_deferred_dereg.append(slot)
            return
        self.registry.deregister(slot)

    # ------------------------------------------------------------------
    # staging: lpf_put / lpf_get
    # ------------------------------------------------------------------
    def _require_active(self) -> None:
        if self._on_hold:
            raise LPFFatalError(
                "context is on hold while a rehook sub-program runs; "
                "active contexts must be disjoint (paper S2.2)")

    def _stage(self, msgs: List[Msg]) -> None:
        self._require_active()
        # fault seam: an armed plan may simulate capacity exhaustion
        # here — same mitigable LPFCapacityError, same resize-and-retry
        # contract (:meth:`with_capacity`) as the real check below
        _fp.fire("capacity", staged=len(self._queue), new=len(msgs),
                 capacity=self._queue_capacity)
        if len(self._queue) + len(msgs) > self._queue_capacity:
            raise LPFCapacityError(
                f"message queue capacity {self._queue_capacity} exceeded "
                f"({len(self._queue)} staged + {len(msgs)} new); call "
                f"resize_message_queue first",
                required=len(self._queue) + len(msgs),
                capacity=self._queue_capacity, kind="queue")
        # extents/dtypes/kinds are checked the moment a transfer is
        # staged — an out-of-bounds put fails at the ``ctx.put`` call
        # site, not at the (possibly much later) sync or flush
        for m in msgs:
            m.validate(self.p)
        if self.sanitize:
            for m in msgs:
                for slot in (m.src_slot, m.dst_slot):
                    if not slot.gen:
                        continue   # synthetic handle, never registered
                    if not self.registry.is_registered(slot) or any(
                            d.sid == slot.sid and d.gen == slot.gen
                            for d in self._rec_deferred_dereg):
                        raise LPFAnalysisError(
                            f"LPF003: staged transfer uses deregistered "
                            f"slot {slot}")
        self._queue.extend(msgs)

    def put(self, src_slot: Slot, dst_slot: Slot, *, to: PidFn,
            src_off: PidFn = 0, dst_off: PidFn = 0,
            size: Optional[PidFn] = None,
            where: Optional[Callable[[int], bool]] = None) -> None:
        """Stage a put from every process ``s`` to process ``to(s)``.

        Offsets/sizes may be ints (uniform), tables, or functions of the
        *sending* pid — all static, as BSP supersteps declare their
        h-relation up front.  ``where`` statically masks which pids
        participate.  O(1) per message, no communication (paper Fig. 1).
        """
        if size is None:
            size = src_slot.size
        soff = _per_pid(src_off, self.p, "src_off")
        doff = _per_pid(dst_off, self.p, "dst_off")
        dsts = _per_pid(to, self.p, "to")
        sizes = _per_pid(size, self.p, "size")
        msgs = [Msg(s, dsts[s], src_slot, soff[s], dst_slot, doff[s],
                    sizes[s], origin="put")
                for s in range(self.p)
                if (where is None or where(s)) and sizes[s] > 0]
        self._stage(msgs)

    def get(self, src_slot: Slot, dst_slot: Slot, *, frm: PidFn,
            src_off: PidFn = 0, dst_off: PidFn = 0,
            size: Optional[PidFn] = None,
            where: Optional[Callable[[int], bool]] = None) -> None:
        """Stage a get: every process ``s`` reads from ``frm(s)``.

        Tables are indexed by the *destination* pid ``s`` (the caller);
        the message table is globally known so a get is a put issued from
        the remote side."""
        if size is None:
            size = src_slot.size
        soff = _per_pid(src_off, self.p, "src_off")
        doff = _per_pid(dst_off, self.p, "dst_off")
        srcs = _per_pid(frm, self.p, "frm")
        sizes = _per_pid(size, self.p, "size")
        msgs = [Msg(srcs[s], s, src_slot, soff[s], dst_slot, doff[s],
                    sizes[s], origin="get")
                for s in range(self.p)
                if (where is None or where(s)) and sizes[s] > 0]
        self._stage(msgs)

    def put_msgs(self, msgs: Sequence[Tuple[int, int, Slot, int, Slot,
                                            int, int]]) -> None:
        """Stage an explicit message table [(src, dst, src_slot, src_off,
        dst_slot, dst_off, size), ...] — the fully general h-relation."""
        self._stage([Msg(*m) for m in msgs])

    # ------------------------------------------------------------------
    # the fence: lpf_sync
    # ------------------------------------------------------------------
    def sync(self, attrs: SyncAttributes = LPF_SYNC_DEFAULT,
             label: str = "") -> Optional[SuperstepCost]:
        """Plan (memoised), lower, and account one superstep; returns its
        ledger entry so callers can thread costs through without reading
        the ledger back.

        While a program is being recorded (:meth:`record` /
        :meth:`program`) the superstep is *deferred*: its table is
        snapshotted into the pending trace and executed at the next
        flush — a local read/write of a touched slot executes exactly
        the slot's dependency cone (see :meth:`_flush_cone`);
        :meth:`end_record` executes whatever remains — after trace
        optimization (coalescing, dead-transfer elimination, batching,
        split-phase overlap).  In that case ``sync`` returns ``None``
        and the ledger entries appear at flush time."""
        with span("lpf.sync"):
            return self._sync(attrs, label)

    def _sync(self, attrs: SyncAttributes,
              label: str) -> Optional[SuperstepCost]:
        self._require_active()
        if not label:
            prefix = next((l for l in reversed(self._rec_labels) if l), "")
            n = self.ledger.supersteps + len(self._rec_pending)
            label = f"{prefix}.superstep[{n}]" if prefix \
                else f"superstep[{n}]"
        if self._rec_depth:
            # messages were validated at stage time (see ``_stage``)
            self._rec_pending.append(
                ProgramStep(tuple(self._queue), attrs, label))
            self._queue = []
            return None
        if self.sanitize and self._queue:
            self._sanitize_lint(
                [ProgramStep(tuple(self._queue), attrs, label)])
        plan = self.plan_cache.get_or_plan(self._queue, self.p, attrs,
                                           self._scratch)
        cost = execute_plan(plan, self.registry, self._queue, self.p,
                            self.axes, self.pid, attrs, label,
                            scratch=self._scratch)
        self.ledger.add(cost)
        self._queue = []
        return cost

    # ------------------------------------------------------------------
    # program record/replay (see repro.core.program)
    # ------------------------------------------------------------------
    def record(self, label: str = "") -> None:
        """Start (or nest into) program recording: subsequent ``sync``
        calls defer into a trace that is optimized — coalesced,
        dead-transfer-eliminated, cost-gated superstep batching — and
        replayed through the program cache at flush time.  ``label``
        prefixes the default ledger labels of unlabelled syncs recorded
        at this level."""
        self._require_active()
        self._rec_depth += 1
        self._rec_labels.append(label)
        self._rec_marks.append(len(self._rec_pending))

    def end_record(self) -> None:
        """Leave one level of recording; the outermost level flushes any
        pending supersteps."""
        if self._rec_depth == 0:
            raise LPFFatalError("end_record without a matching record()")
        self._rec_depth -= 1
        self._rec_labels.pop()
        self._rec_marks.pop()
        if self._rec_depth == 0:
            self._flush_program()
            if self.sanitize and self._rec_registered:
                from ..analysis.linter import Diagnostic, WARNING
                for slot in self._rec_registered:
                    if self.registry.is_registered(slot):
                        self.diagnostics.append(Diagnostic(
                            "LPF003", WARNING, -1,
                            f"slot {slot} registered during the "
                            f"recording is still registered at "
                            f"end_record (leak?)"))
            self._rec_registered = []

    def abort_record(self) -> None:
        """Abandon one level of recording: the supersteps recorded at
        this level are *discarded*, not executed.  This is the
        exception path of :meth:`program` — flushing a partial trace
        when the body raised would issue communication the caller never
        completed, breaking the mitigable-error contract (a capacity
        error must be side-effect-free so :meth:`with_capacity` can
        resize and retry)."""
        if self._rec_depth == 0:
            raise LPFFatalError("abort_record without a matching record()")
        self._rec_depth -= 1
        self._rec_labels.pop()
        mark = self._rec_marks.pop()
        # steps recorded before the mark may have flushed already (a
        # dependency-cone read shrinks _rec_pending and rebases marks),
        # so the mark never exceeds the pending length
        del self._rec_pending[mark:]
        self._queue = []
        if self._rec_depth == 0:
            self._rec_registered = []

    @contextlib.contextmanager
    def program(self, label: str = ""):
        """``with ctx.program(): ...`` — record the body's supersteps as
        one :class:`repro.core.SuperstepProgram`; re-entrant (a recorded
        collective inside a recorded training step extends the outer
        trace).  If the body raises, the supersteps it recorded are
        discarded (:meth:`abort_record`) — never executed as a partial
        trace — and the exception propagates."""
        self.record(label)
        try:
            yield self
        except BaseException:
            self.abort_record()
            raise
        else:
            self.end_record()

    def _machine(self) -> LPFMachine:
        """The (g, l) machine the optimizer's cost gate prices with:
        the real per-axis probe, so a context spanning a DCN pod axis
        gates with DCN latencies, not the first axis's link class."""
        if self._gate_machine is None:
            axis_sizes = {a: int(lax.psum(1, a)) for a in self.axes}
            self._gate_machine = _probe(axis_sizes, self.hardware)
        return self._gate_machine

    def _pending_refs(self, slot: Slot, dst_only: bool = False) -> bool:
        """Does any pending recorded superstep reference ``slot``?"""
        for st in self._rec_pending:
            for m in st.msgs:
                if m.dst_slot.sid == slot.sid:
                    return True
                if not dst_only and m.src_slot.sid == slot.sid:
                    return True
        return False

    def _execute_steps(self, steps: List[ProgramStep]) -> None:
        """Optimize (or fetch the cached optimization of) one trace and
        execute it; the ledger gains one entry per *optimized* superstep
        — each exactly its plan's predicted cost — and one combined
        entry (``overlap_cost`` of the members' plans) per overlap
        group issued split-phase.  The searched schedule may *reorder*
        supersteps (non-adjacent hoists); ``materialize`` resolves the
        program's canonical ranks against this trace's own canonical
        order, so labels and staged-message reuse stay attached to the
        right recorded steps whatever order the scheduler emitted."""
        from .program import canonical_order
        order = canonical_order(steps)
        prog, key = self.program_cache.get_or_build_keyed(
            steps, self.p, self._machine(), plan_cache=self.plan_cache,
            scratch=self._scratch, order=order)
        self.last_program = prog
        # every schedule is certified (memoized per cache key) before it
        # may execute; a program the verifier cannot certify never
        # reaches the wire
        cert = self.program_cache.certify(key, steps, prog,
                                          scratch=self._scratch,
                                          order=order)
        if not cert.ok:
            raise LPFAnalysisError(
                "schedule verification failed; refusing to execute:\n  "
                + "\n  ".join(str(d) for d in cert.diagnostics))
        if self.sanitize:
            self._sanitize_lint(steps, prog, order)
        # fault seam: an armed plan may delay this flush (a straggler);
        # pure wall-clock — numerics and ledger are untouched, which is
        # exactly what the StragglerMonitor is built to notice
        d = _fp.delay("straggler")
        if d > 0:
            time.sleep(d)
        labels = [st.label for st in steps]
        entries = prog.materialize(steps, labels, order=order)
        costs = execute_schedule(entries, prog.groups(), self.registry,
                                 self.p, self.axes, self.pid,
                                 scratch=self._scratch)
        for cost in costs:
            self.ledger.add(cost)

    def _sanitize_lint(self, steps: List[ProgramStep],
                       prog=None, order=None) -> None:
        """Sanitizer hook: lint a trace about to execute.  Error
        diagnostics raise :class:`LPFAnalysisError` (before any
        communication); warnings accumulate on :attr:`diagnostics`."""
        from ..analysis.linter import ERROR, lint_program, lint_trace
        diags = list(lint_trace(steps, self.p, check_dead=False))
        if prog is not None:
            diags += lint_program(prog, steps, order=order)
        errors = [d for d in diags if d.severity == ERROR]
        if errors:
            raise LPFAnalysisError(
                "sanitize: " + "; ".join(str(d) for d in errors))
        self.diagnostics.extend(diags)

    def _drain_deferred_dereg(self) -> None:
        still: List[Slot] = []
        for slot in self._rec_deferred_dereg:
            if self._rec_pending and self._pending_refs(slot):
                still.append(slot)       # a deferred step still moves data
            else:
                self.registry.deregister(slot)
        self._rec_deferred_dereg = still

    def _flush_program(self) -> None:
        """Execute the whole pending trace (end of recording)."""
        if not self._rec_pending:
            return
        steps, self._rec_pending = self._rec_pending, []
        self._rec_marks = [0] * len(self._rec_marks)
        with span("lpf.flush"):
            self._execute_steps(steps)
        self._drain_deferred_dereg()

    def _flush_cone(self, slot: Slot, include_reads: bool) -> None:
        """Dataflow-precise flush: execute only the pending supersteps a
        local read (or write, with ``include_reads``) of ``slot``
        depends on — its dependency cone, a topological slice over the
        trace's slot-dataflow graph.  Independent supersteps stay
        recorded across the compute barrier, keeping the
        batching/overlap window open for later syncs."""
        if not self._rec_pending:
            return
        cone = dependency_cone(self._rec_pending, slot.sid, include_reads)
        if not cone:
            return
        if len(cone) == len(self._rec_pending):
            self._flush_program()
            return
        cone_set = set(cone)
        steps = [st for i, st in enumerate(self._rec_pending)
                 if i in cone_set]
        self._rec_pending = [st for i, st in enumerate(self._rec_pending)
                             if i not in cone_set]
        # rebase the per-level abort marks: indices below a mark that
        # just flushed no longer occupy pending positions
        self._rec_marks = [m - sum(1 for i in cone_set if i < m)
                           for m in self._rec_marks]
        with span("lpf.flush"):
            self._execute_steps(steps)
        self._drain_deferred_dereg()

    # ------------------------------------------------------------------
    # whole-loop compilation
    # ------------------------------------------------------------------
    def compile_loop(self, body: Callable[["LPFContext", Any], Any],
                     carry: Any, *, n_iters: Optional[int] = None,
                     cond: Optional[Callable[[Any], Any]] = None,
                     label: str = "loop",
                     collect: Optional[Callable[[Any], Any]] = None) -> Any:
        """Roll an iterated LPF program into ONE XLA loop.

        ``body(sub_ctx, carry) -> carry`` runs each iteration's compute
        and supersteps against a fresh sub-context whose trace records
        as one program (so the schedule search applies per iteration);
        the loop itself lowers through ``compat.scan`` (``n_iters``) or
        ``compat.while_loop`` (``cond(carry) -> bool``), so N iterations
        issue as a single XLA ``While`` computation instead of N
        Python-dispatched calls — the torch_xla ``fori_loop`` pattern.
        Exactly one of ``n_iters``/``cond`` must be given.

        The body traces ONCE: its per-iteration superstep costs are
        appended to this context's ledger once (the BSP model prices one
        iteration; multiply by the executed trip count for totals —
        which the trace cannot know for a ``cond`` loop).  With
        ``collect`` (scan only) each iteration's ``collect(carry)`` is
        stacked and ``(final_carry, stacked)`` is returned; otherwise
        just the final carry."""
        if (n_iters is None) == (cond is None):
            raise LPFFatalError(
                "compile_loop needs exactly one of n_iters= or cond=")
        if collect is not None and cond is not None:
            raise LPFFatalError(
                "collect= requires a counted loop (n_iters=): a "
                "while_loop's trip count is dynamic, so there is "
                "nothing static to stack into")
        self._require_active()
        ledgers: List[CostLedger] = []

        def one(c):
            sub = LPFContext(self.axes, hardware=self.hardware,
                             plan_cache=self.plan_cache,
                             program_cache=self.program_cache,
                             _parent=self)
            ledgers.append(sub.ledger)
            with sub.program(label):
                out = body(sub, c)
            return out

        if cond is not None:
            final, ys = compat.while_loop(cond, one, carry), None
        else:
            def step(c, _):
                out = one(c)
                return out, (None if collect is None else collect(out))

            final, ys = compat.scan(step, carry, None, length=n_iters)
        # guard against a double trace (e.g. dtype promotion in the
        # carry forcing a re-trace): ledger the first trace only
        if ledgers:
            for cost in ledgers[0].records:
                self.ledger.add(cost)
        return final if collect is None else (final, ys)

    @property
    def cache_stats(self) -> "_CacheStatsView":
        """Hit/miss/eviction counters of both memo layers; call
        ``.reset()`` on the returned view to zero the counters in place
        (the caches stay warm) for delta measurements."""
        return _CacheStatsView(plan=self.plan_cache.stats,
                               program=self.program_cache.stats)

    # ------------------------------------------------------------------
    # introspection: lpf_probe
    # ------------------------------------------------------------------
    def probe(self, axis_sizes: Optional[dict] = None) -> LPFMachine:
        if axis_sizes is None:
            if not self.axes:
                axis_sizes = {}
            else:
                axis_sizes = {a: int(lax.psum(1, a)) for a in self.axes}
        return _probe(axis_sizes, self.hardware)

    # ------------------------------------------------------------------
    # local access (between supersteps)
    # ------------------------------------------------------------------
    def value(self, slot: Slot) -> jnp.ndarray:
        # local compute is a barrier, but a *dataflow-precise* one: a
        # read executes only the pending supersteps in the slot's
        # dependency cone; independent supersteps stay recorded
        self._flush_cone(slot, include_reads=False)
        return self.registry.value(slot)

    def tensor(self, slot: Slot) -> jnp.ndarray:
        self._flush_cone(slot, include_reads=False)
        return self.registry.tensor(slot)

    def write(self, slot: Slot, value) -> None:
        """Local compute step writing a slot (allowed between supersteps)."""
        # recorded supersteps must observe the slot as it was when they
        # were staged; overwriting a slot flushes the cone of supersteps
        # that read *or* write it (WAR + WAW), and only that cone
        self._flush_cone(slot, include_reads=True)
        value = jnp.asarray(value).reshape(-1).astype(slot.dtype)
        self.registry.set_value(slot, value)

    # convenience mirrors of the C API's context queries
    @property
    def nprocs(self) -> int:
        return self.p


@dataclasses.dataclass
class _Args:
    """``lpf_args_t``: arbitrary input/output passing."""

    input: Any = None
    output: Any = None


def hook(axes: Sequence[str], spmd: Callable, args: Any = None, *,
         hardware: HardwareModel = TPU_V5E,
         plan_cache: Optional[PlanCache] = None,
         program_cache: Optional[ProgramCache] = None,
         parent: Optional[LPFContext] = None) -> Any:
    """``lpf_hook``: run an LPF SPMD function inside the *current* parallel
    environment (any traced program already under a mesh).  Returns the
    function's output.  O(1) setup — no processes are spawned.  The child
    context inherits the parent's plan/program caches (or explicit ones)
    so isolated caches stay isolated across hooked sub-programs."""
    if plan_cache is None and parent is not None:
        plan_cache = parent.plan_cache
    if program_cache is None and parent is not None:
        program_cache = parent.program_cache
    ctx = LPFContext(axes, hardware=hardware, plan_cache=plan_cache,
                     program_cache=program_cache, _parent=parent)
    return spmd(ctx, ctx.pid, ctx.p, args)


def rehook(ctx: LPFContext, spmd: Callable, args: Any = None, *,
           axes: Optional[Sequence[str]] = None) -> Any:
    """``lpf_rehook``: temporarily replace an active context with a
    pristine one (optionally over a sub-set of its axes) — the paper's
    sub-library encapsulation.  The parent context is on hold while the
    sub-program runs (active contexts are disjoint)."""
    sub_axes = tuple(axes) if axes is not None else ctx.axes
    for a in sub_axes:
        if a not in ctx.axes:
            raise LPFFatalError(f"rehook axis {a!r} not in parent context")
    ctx._on_hold = True
    try:
        return hook(sub_axes, spmd, args, hardware=ctx.hardware, parent=ctx)
    finally:
        ctx._on_hold = False


#: ``exec_``'s compiled programs by key (:func:`_exec_key`), least
#: recently used first: (executable, cost ledger of its trace).  As many
#: as a :class:`ProgramCache` holds.
_EXEC_CACHE_SIZE = 256
_EXEC_CACHE: "collections.OrderedDict[Hashable, Tuple[Any, CostLedger]]" = \
    collections.OrderedDict()
_EXEC_LOCK = threading.Lock()


def _spec_key(specs: Any) -> Tuple[Any, Tuple[Any, ...]]:
    leaves, tree = jax.tree.flatten(
        specs, is_leaf=lambda s: s is None or isinstance(s, P))
    return tree, tuple(leaves)


def _leaf_key(x: Any) -> Tuple[Any, ...]:
    aval = jax.typeof(x)
    placement = (x.sharding, x.committed) if isinstance(x, jax.Array) \
        else None
    return aval.shape, aval.dtype, aval.weak_type, placement


def _exec_key(spmd: Callable, mesh: jax.sharding.Mesh,
              axes: Tuple[str, ...], in_specs: Any, out_specs: Any,
              hardware: HardwareModel, args: Any) -> Optional[Hashable]:
    """Everything that decides the program ``exec_`` compiles, with no
    array in it; None where a part is unhashable."""
    leaves, tree = jax.tree.flatten(args)
    # what JAX and LPFContext read from the process while tracing
    env = (jax.config.jax_enable_x64,
           os.environ.get("LPF_SANITIZE", "0") != "0")
    key = (spmd, mesh, axes, _spec_key(in_specs), _spec_key(out_specs),
           hardware, tree, tuple(_leaf_key(x) for x in leaves), env)
    try:
        hash(key)
    except TypeError:
        return None
    return key


def _exec_lookup(key: Hashable) -> Optional[Tuple[Any, CostLedger]]:
    with _EXEC_LOCK:
        entry = _EXEC_CACHE.get(key)
        if entry is not None:
            with span("lpf.exec.hit"):
                _EXEC_CACHE.move_to_end(key)
    return entry


def _ledger_copy(ledger: CostLedger) -> CostLedger:
    """A ledger of its own for each caller, so that none edits the kept one."""
    out = CostLedger()
    out.records = list(ledger.records)
    return out


def _exec_store(key: Hashable, entry: Tuple[Any, CostLedger]) -> None:
    with _EXEC_LOCK:
        _EXEC_CACHE[key] = entry
        while len(_EXEC_CACHE) > _EXEC_CACHE_SIZE:
            _EXEC_CACHE.popitem(last=False)


def exec_(mesh: jax.sharding.Mesh, spmd: Callable, args: Any = None, *,
          axes: Optional[Sequence[str]] = None,
          in_specs: Any = None, out_specs: Any = P(),
          hardware: HardwareModel = TPU_V5E,
          jit: bool = True,
          return_ledger: bool = False) -> Any:
    """``lpf_exec``: launch ``spmd(ctx, s, p, args)`` on ``mesh``.

    ``args`` are replicated by default (``in_specs``) and outputs are
    expected replicated (``out_specs=P()``), mirroring the C API's
    broadcast args; pass explicit specs for distributed I/O.  With
    ``return_ledger=True`` also returns the cost ledger recorded at trace
    time, for compliance checking.

    The call is staged (trace, lower, compile, run), each stage a span
    of its own (:mod:`repro.core.spans`).  The executable and its ledger
    are kept, in a process-wide LRU, under the call's key: ``spmd`` (by
    hash and equality, as ``jax.jit`` keys a function), mesh, axes,
    specs, hardware and the arguments' structure, shapes, dtypes and
    placements.  A later call with an equal key runs the kept
    executable (span ``lpf.exec.hit``, then ``lpf.exec.run``) and
    returns the kept ledger; so pass a stable ``spmd``, not a closure
    made anew per call.  Inside a caller's trace (an enclosing ``jit``)
    it nests into that trace, as :func:`hook` does, and has no stages
    of its own."""
    axes = tuple(axes) if axes is not None else tuple(mesh.axis_names)
    if in_specs is None:
        in_specs = compat.tree_map(lambda _: P(), args)
    key = None
    if jit and not compat.tracing():
        key = _exec_key(spmd, mesh, axes, in_specs, out_specs, hardware,
                        args)
        # A hit skips LPF's trace-time work: planning, the program
        # cache, certification and the linter.  That is sound because an
        # equal key traces the same recorded program, which the miss
        # that stored the entry planned and certified.
        entry = _exec_lookup(key) if key is not None else None
        if entry is not None:
            compiled, ledger = entry
            with span("lpf.exec.run"):
                out = compiled(args)
            return (out, _ledger_copy(ledger)) if return_ledger else out
    ledger_box: List[CostLedger] = []

    def wrapped(a):
        ctx = LPFContext(axes, hardware=hardware)
        ledger_box.append(ctx.ledger)
        return spmd(ctx, ctx.pid, ctx.p, a)

    fn = compat.shard_map(wrapped, mesh=mesh, in_specs=(in_specs,),
                          out_specs=out_specs, check_vma=False)
    if compat.tracing():
        out = (jax.jit(fn) if jit else fn)(args)
    elif jit:
        with span("lpf.exec.trace"):
            traced = jax.jit(fn).trace(args)
        with span("lpf.exec.lower"):
            lowered = traced.lower()
        with span("lpf.exec.compile"):
            compiled = lowered.compile()
        if key is not None:
            _exec_store(key, (compiled, _ledger_copy(
                ledger_box[0] if ledger_box else CostLedger())))
        with span("lpf.exec.run"):
            out = compiled(args)
    else:
        with span("lpf.exec.run"):
            out = fn(args)
    if return_ledger:
        return out, (ledger_box[0] if ledger_box else CostLedger())
    return out
