"""The superstep compiler — ``lpf_sync``'s four phases on XLA.

The paper implements ``lpf_sync`` in four phases: (1) barrier + meta-data
exchange, (2) write-conflict resolution, (3) data exchange, (4) barrier.
On TPU/XLA the communication pattern of a BSP superstep is static at trace
time, so phases (1)-(2) run *in the compiler*.  Following pMR and the
plan-once/execute-many design of FFTW-style communication layers, the
compiler is split into three stages:

* **plan** — :func:`plan_sync` analyses the staged message table, resolves
  write conflicts by deterministic arbitration (ascending source PID; the
  last writer — highest PID — wins, a refinement of the paper's
  arbitrary-order CRCW), classifies fast paths, edge-colours the message
  multigraph, and predicts the superstep's :class:`SuperstepCost`.  The
  result is a :class:`SuperstepPlan` — a pure-Python IR with **no JAX
  ops**, so planning is unit-testable in microseconds and reusable across
  traces.
* **cache** — :class:`PlanCache` memoises plans under a canonical
  signature of ``(p, attributes, message table)`` with slot ids renamed to
  first-occurrence indices, so the per-layer gradient syncs and per-stage
  FFT supersteps that repeat the same h-relation (through freshly
  registered slots) hit the cache instead of re-colouring.
* **execute** — :func:`execute_plan` lowers a :class:`SuperstepPlan` to a
  minimal schedule of XLA collectives and appends the (already predicted)
  cost to the ledger.  Phase (4) is implicit in XLA's dataflow.

Three execution methods mirror the paper's Table 1:

* ``direct``  — greedy edge-colouring of the message multigraph into
  partial permutations; one ``ppermute`` per round (m rounds for an
  m-relation), plus fast paths for uniform permutations (1 static-slice
  ``ppermute``) and canonical total exchanges (1 ``all_to_all``).
* ``bruck``   — the randomised-Bruck flavour: ceil(log2 p) rounds in
  *relative-destination coordinates* (statically indexable rows), paying
  O(log p) x volume for O(log p) latency.
* ``valiant`` — two-phase randomised routing for skewed h-relations:
  messages bounce via a seeded-hash intermediate, each phase a ``direct``
  sync of a near-balanced relation.

On top of these, ``auto`` planning recognises canonical patterns and
lowers each onto the single native collective XLA offers for it (pMR's
transport selection), instead of generic permutation rounds:

* ``fused``         — total exchange           -> 1 ``lax.all_to_all``
* ``fused_ag``      — all-gather               -> 1 ``lax.all_gather``
* ``fused_rs``      — reduce-scatter (needs ``attrs.reduce_op``)
                      -> 1 ``lax.psum_scatter`` (sum) or masked
                      ``all_to_all`` + local combine (max/min)
* ``fused_scatter`` — root scatter             -> 1 masked ``all_to_all``
* ``fused_gather``  — gather to root           -> 1 masked ``all_gather``

``attrs.reduce_op`` turns a superstep into an *accumulating-put*
superstep: overlapping destination writes combine elementwise
(sum/max/min) instead of CRCW-arbitrating, which is what makes the
reduce-scatter relation expressible as a message table at all.

Every sync appends a :class:`SuperstepCost` to the context ledger so model
compliance can be audited against the compiled HLO; the executed ledger
entry is by construction identical to the plan's prediction.
"""

from __future__ import annotations

import collections
import dataclasses
import math
from typing import Callable, Dict, Hashable, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

from .attrs import SyncAttributes
from .cost import SuperstepCost, overlap_cost
from .errors import LPFFatalError
from .memslot import Slot, SlotRegistry

__all__ = [
    "Msg", "RoundPlan", "SuperstepPlan", "PlanCache", "CacheStats",
    "plan_sync", "plan_signature", "begin_plan", "execute_plan",
    "execute_overlapped", "execute_schedule", "execute_sync", "plan_cost",
    "conflict_free", "find_conflict", "global_plan_cache",
    "OVERLAPPABLE_METHODS",
]

AxisNames = Tuple[str, ...]


@dataclasses.dataclass(frozen=True)
class Msg:
    """One staged one-sided transfer (a ``lpf_put``; ``lpf_get`` is staged
    as a put from the remote side — the table is globally known)."""

    src: int
    dst: int
    src_slot: Slot
    src_off: int
    dst_slot: Slot
    dst_off: int
    size: int
    #: which call staged this: "put" (src is the caller's own memory, may
    #: be local-registered), "get" (dst is the caller's own), or "table"
    #: (fully general: both ends remotely referred -> both global)
    origin: str = "table"

    def validate(self, p: int) -> None:
        if not (0 <= self.src < p and 0 <= self.dst < p):
            raise LPFFatalError(f"pid out of range in {self}")
        if self.size < 0:
            raise LPFFatalError(f"negative size in {self}")
        if self.src_off < 0 or self.src_off + self.size > self.src_slot.size:
            raise LPFFatalError(f"source range OOB in {self}")
        if self.dst_off < 0 or self.dst_off + self.size > self.dst_slot.size:
            raise LPFFatalError(f"destination range OOB in {self}")
        if self.src_slot.dtype != self.dst_slot.dtype:
            raise LPFFatalError(f"dtype mismatch in {self}")
        if self.src != self.dst:
            # the remotely-referred side must be collectively registered
            # (paper S2.1); the caller's own side may be register_local
            need_global = {"put": (self.dst_slot,),
                           "get": (self.src_slot,),
                           "table": (self.src_slot, self.dst_slot)}
            for slot in need_global[self.origin]:
                if slot.kind != "global":
                    raise LPFFatalError(
                        f"remotely-referred slot {slot} must be "
                        f"register_global ({self.origin} in {self})")


def _itemsize(dtype) -> int:
    return np.dtype(dtype).itemsize


def _is_floating(dtype) -> bool:
    return np.issubdtype(np.dtype(dtype), np.floating)


#: elementwise combine functions for accumulating-put supersteps
_REDUCE_FNS = {"sum": jnp.add, "max": jnp.maximum, "min": jnp.minimum}


# ==========================================================================
# Stage 1: PLAN — pure Python, no JAX ops
# ==========================================================================

@dataclasses.dataclass(frozen=True)
class RoundPlan:
    """One partial permutation of the ``direct`` method.

    ``msg_idx`` indexes into the message list the plan was built from (the
    superstep queue, or a Valiant phase list); per-PID offset tables are
    rebuilt from those messages at lowering time — only the *decisions*
    (membership, order, padding, fast-path) are cached."""

    msg_idx: Tuple[int, ...]
    size: int                        # padded payload (elements)
    static_src_off: Optional[int]    # uniform-round fast path, else None


@dataclasses.dataclass(frozen=True)
class SuperstepPlan:
    """The planned superstep: everything ``lpf_sync`` decides at trace
    time, decoupled from slot identities and traced values.

    A plan built for one message table is valid for any table with the
    same :func:`plan_signature` — same ``p``, attributes, and per-message
    ``(src, dst, slot shape/dtype/kind pattern, offsets, size)`` with slot
    ids renamed by first occurrence."""

    #: noop | seq | direct | bruck | valiant | fused | fused_ag |
    #: fused_rs | fused_scatter | fused_gather
    method: str
    p: int
    n_msgs: int
    cost: SuperstepCost                                   # label == ""
    rounds: Tuple[RoundPlan, ...] = ()                    # direct
    seq_order: Tuple[int, ...] = ()                       # p == 1 memcpys
    fused_w: int = 0                                      # all fused methods
    ag_src_off: Tuple[int, ...] = ()                      # fused_ag, per pid
    ag_exclude_self: bool = False
    reduce_op: Optional[str] = None                       # accumulate mode
    rs_dst_off: Tuple[int, ...] = ()                      # fused_rs, per dst
    fused_root: int = -1                                  # scatter / gather
    sc_dst_off: Tuple[int, ...] = ()                      # fused_scatter
    sc_mask: Tuple[int, ...] = ()                         # fused_scatter
    g_src_off: Tuple[int, ...] = ()                       # fused_gather
    g_has_self: bool = False                              # fused_gather
    bruck_w: int = 0
    bruck_steps: Tuple[Tuple[int, Tuple[int, ...]], ...] = ()  # (step, rows)
    valiant_order: Tuple[int, ...] = ()                   # sorted msg indices
    valiant_via: Tuple[int, ...] = ()                     # intermediate pid
    valiant_off: Tuple[int, ...] = ()                     # scratch offset
    valiant_phase1: Tuple[RoundPlan, ...] = ()
    valiant_phase2: Tuple[RoundPlan, ...] = ()

    def cost_with_label(self, label: str) -> SuperstepCost:
        return dataclasses.replace(self.cost, label=label)


def _conflicts(a: Msg, b: Msg) -> bool:
    return (a.dst == b.dst and a.dst_slot.sid == b.dst_slot.sid
            and a.dst_off < b.dst_off + b.size
            and b.dst_off < a.dst_off + a.size)


def conflict_free(msgs: Sequence[Msg]) -> bool:
    """No two messages of the table write overlapping destination ranges.

    A conflict-free table's final state is independent of write
    arbitration order, which is the precondition for rewriting its
    execution *method*: ``direct`` arbitrates by ascending source pid
    while ``valiant`` phase 2 applies writes in intermediate-pid order,
    so the optimizer's Valiant-aware attr rewrite is only admissible on
    tables this predicate accepts (``reduce_op`` tables commute by
    construction but take no method rewrite — valiant cannot combine)."""
    return find_conflict(msgs) is None


def find_conflict(msgs: Sequence[Msg]) -> Optional[Tuple[Msg, Msg]]:
    """First pair of messages writing overlapping destination ranges,
    or ``None`` for a conflict-free table.  The witness pair is what the
    linter reports when a user-asserted ``no_conflict`` table races."""
    msgs = list(msgs)
    for i, a in enumerate(msgs):
        for b in msgs[i + 1:]:
            if _conflicts(a, b):
                return (a, b)
    return None


def _colour_rounds(idxs: Sequence[int], msgs: Sequence[Msg],
                   no_conflict: bool) -> List[List[int]]:
    """Greedy edge colouring preserving CRCW arbitration order.

    Messages are placed in ascending (src, dst, dst_off) order; a message
    that overlaps an earlier message's destination region must land in a
    strictly later round so that the higher-PID write is applied last.
    Returns rounds as lists of indices into ``msgs``.
    """
    order = sorted(idxs, key=lambda i: (msgs[i].src, msgs[i].dst,
                                        msgs[i].dst_off))
    rounds: List[List[int]] = []
    send_busy: List[set] = []
    recv_busy: List[set] = []
    placed: List[Tuple[int, int]] = []
    for i in order:
        m = msgs[i]
        floor = 0
        if not no_conflict:
            for prev, r in placed:
                if _conflicts(msgs[prev], m):
                    floor = max(floor, r + 1)
        r = floor
        while True:
            while r >= len(rounds):
                rounds.append([])
                send_busy.append(set())
                recv_busy.append(set())
            if m.src not in send_busy[r] and m.dst not in recv_busy[r]:
                rounds[r].append(i)
                send_busy[r].add(m.src)
                recv_busy[r].add(m.dst)
                placed.append((i, r))
                break
            r += 1
    return rounds


def _is_uniform(idxs: Sequence[int], msgs: Sequence[Msg]) -> bool:
    """True if all messages share offsets and size (static-slice fast path)."""
    m0 = msgs[idxs[0]]
    return all(msgs[i].src_off == m0.src_off and msgs[i].dst_off == m0.dst_off
               and msgs[i].size == m0.size for i in idxs)


def _detect_total_exchange(msgs: Sequence[Msg], p: int
                           ) -> Optional[Tuple[Slot, Slot, int]]:
    """Detect the canonical total exchange: every (s, d) pair sends ``w``
    elements with src_off = d*w and dst_off = s*w -> one ``all_to_all``."""
    if len(msgs) != p * p or p == 1:
        return None
    m0 = msgs[0]
    w = m0.size
    if w == 0:
        return None
    seen = set()
    for m in msgs:
        if (m.src_slot.sid != m0.src_slot.sid
                or m.dst_slot.sid != m0.dst_slot.sid
                or m.size != w or m.src_off != m.dst * w
                or m.dst_off != m.src * w or (m.src, m.dst) in seen):
            return None
        seen.add((m.src, m.dst))
    if m0.src_slot.size < p * w or m0.dst_slot.size < p * w:
        return None
    return (m0.src_slot, m0.dst_slot, w)


def _detect_allgather(msgs: Sequence[Msg], p: int
                      ) -> Optional[Tuple[Slot, Slot, int, np.ndarray]]:
    """Detect the canonical all-gather: every src sends the *same* ``w``
    elements (from a per-src constant offset) to every other process at
    dst_off = src*w -> one ``lax.all_gather``."""
    if p == 1 or len(msgs) not in (p * p, p * (p - 1)):
        return None
    m0 = msgs[0]
    w = m0.size
    if w == 0:
        return None
    seen = set()
    src_off = np.full(p, -1, np.int64)
    for m in msgs:
        if (m.src_slot.sid != m0.src_slot.sid
                or m.dst_slot.sid != m0.dst_slot.sid
                or m.size != w
                or m.dst_off != m.src * w or (m.src, m.dst) in seen):
            return None
        if src_off[m.src] == -1:
            src_off[m.src] = m.src_off
        elif src_off[m.src] != m.src_off:
            return None
        seen.add((m.src, m.dst))
    if m0.src_slot.size < w or m0.dst_slot.size < p * w:
        return None
    if len(msgs) == p * (p - 1) and any(s == d for s, d in seen):
        return None
    src_off[src_off == -1] = 0
    return (m0.src_slot, m0.dst_slot, w, src_off)


def _detect_reduce_scatter(msgs: Sequence[Msg], p: int,
                           attrs: SyncAttributes
                           ) -> Optional[Tuple[Slot, Slot, int, np.ndarray]]:
    """Detect the canonical reduce-scatter: every (s, d) pair sends ``w``
    elements with src_off = d*w to a per-destination constant offset,
    all p contributions combining under ``attrs.reduce_op`` -> one
    ``lax.psum_scatter`` (sum) or ``all_to_all`` + local combine."""
    if attrs.reduce_op is None or attrs.compress is not None:
        return None
    if p == 1 or len(msgs) != p * p:
        return None
    m0 = msgs[0]
    w = m0.size
    if w == 0:
        return None
    seen = set()
    dst_off = np.full(p, -1, np.int64)
    for m in msgs:
        if (m.src_slot.sid != m0.src_slot.sid
                or m.dst_slot.sid != m0.dst_slot.sid
                or m.size != w or m.src_off != m.dst * w
                or (m.src, m.dst) in seen):
            return None
        if dst_off[m.dst] == -1:
            dst_off[m.dst] = m.dst_off
        elif dst_off[m.dst] != m.dst_off:
            return None
        seen.add((m.src, m.dst))
    if m0.src_slot.size < p * w:
        return None
    return (m0.src_slot, m0.dst_slot, w, dst_off)


def _detect_scatter(msgs: Sequence[Msg], p: int
                    ) -> Optional[Tuple[Slot, Slot, int, int,
                                        np.ndarray, np.ndarray]]:
    """Detect the canonical root scatter: one source sends chunk d
    (src_off = d*w) to every process d at a per-destination offset ->
    one masked ``all_to_all`` (1 round instead of p-1 ppermutes; equal
    h, so the fused schedule strictly dominates on latency)."""
    if p == 1 or len(msgs) not in (p, p - 1):
        return None
    m0 = msgs[0]
    root = m0.src
    w = m0.size
    if w == 0:
        return None
    seen_dst = set()
    dst_off = np.zeros(p, np.int64)
    mask = np.zeros(p, np.int8)
    for m in msgs:
        if (m.src != root or m.src_slot.sid != m0.src_slot.sid
                or m.dst_slot.sid != m0.dst_slot.sid
                or m.size != w or m.src_off != m.dst * w
                or m.dst in seen_dst):
            return None
        seen_dst.add(m.dst)
        dst_off[m.dst] = m.dst_off
        mask[m.dst] = 1
    if len(msgs) == p - 1 and root in seen_dst:
        return None   # the p-1 variant is exactly "everyone but root"
    if m0.src_slot.size < p * w:
        return None
    return (m0.src_slot, m0.dst_slot, w, root, dst_off, mask)


def _detect_gather(msgs: Sequence[Msg], p: int
                   ) -> Optional[Tuple[Slot, Slot, int, int,
                                       np.ndarray, bool]]:
    """Detect the canonical gather to root: every process sends ``w``
    elements (from a per-source constant offset) to one root at
    dst_off = src*w -> one masked ``lax.all_gather``."""
    if p == 1 or len(msgs) not in (p, p - 1):
        return None
    m0 = msgs[0]
    root = m0.dst
    w = m0.size
    if w == 0:
        return None
    seen_src = set()
    src_off = np.zeros(p, np.int64)
    for m in msgs:
        if (m.dst != root or m.src_slot.sid != m0.src_slot.sid
                or m.dst_slot.sid != m0.dst_slot.sid
                or m.size != w or m.dst_off != m.src * w
                or m.src in seen_src):
            return None
        seen_src.add(m.src)
        src_off[m.src] = m.src_off
    has_self = root in seen_src
    if len(msgs) == p - 1 and has_self:
        return None   # the p-1 variant is exactly "everyone but root"
    if m0.dst_slot.size < p * w or m0.src_slot.size < w:
        return None
    return (m0.src_slot, m0.dst_slot, w, root, src_off, has_self)


def plan_cost(msgs: Sequence[Msg], p: int, attrs: SyncAttributes,
              label: str, method: str, rounds: int,
              wire_sent: Dict[int, int], wire_recv: Dict[int, int]) -> SuperstepCost:
    sent = np.zeros(p, dtype=np.int64)
    recv = np.zeros(p, dtype=np.int64)
    for m in msgs:
        if m.src != m.dst:
            nbytes = m.size * _itemsize(m.src_slot.dtype)
            sent[m.src] += nbytes
            recv[m.dst] += nbytes
    h_bytes = int(max(np.max(sent, initial=0), np.max(recv, initial=0)))
    wire = 0
    total = 0
    for pid in range(p):
        wire = max(wire, wire_sent.get(pid, 0), wire_recv.get(pid, 0))
        total += wire_sent.get(pid, 0)
    return SuperstepCost(label=label, h_bytes=h_bytes, wire_bytes=wire,
                         total_wire_bytes=total, rounds=rounds,
                         n_msgs=len(msgs), method=method)


def _round_compressed(rd: RoundPlan, msgs: Sequence[Msg],
                      attrs: SyncAttributes) -> bool:
    """Whether int8 wire compression applies to this round's payload."""
    return (attrs.compress is not None
            and _is_floating(msgs[rd.msg_idx[0]].src_slot.dtype))


def _plan_direct(msgs: Sequence[Msg], attrs: SyncAttributes,
                 wire_sent: Dict[int, int], wire_recv: Dict[int, int]
                 ) -> Tuple[Tuple[RoundPlan, ...], int]:
    """Group by slot pair, colour each group, and account wire traffic.

    Groups are ordered by first occurrence in the message list (never by
    raw slot id) so that equivalent tables — same pattern through freshly
    registered slots — produce identical plans and can share one cache
    entry."""
    groups: "collections.OrderedDict[Tuple[int, int], List[int]]" = \
        collections.OrderedDict()
    for i, m in enumerate(msgs):
        groups.setdefault((m.src_slot.sid, m.dst_slot.sid), []).append(i)
    rounds: List[RoundPlan] = []
    # combining writes are order-free (sum/max/min commute), so reduce
    # supersteps pack rounds as tightly as a no-conflict assertion
    relaxed = attrs.no_conflict or attrs.reduce_op is not None
    for idxs in groups.values():
        for round_idxs in _colour_rounds(idxs, msgs, relaxed):
            size = max((msgs[i].size for i in round_idxs), default=0)
            static = msgs[round_idxs[0]].src_off \
                if round_idxs and _is_uniform(round_idxs, msgs) else None
            rounds.append(RoundPlan(tuple(round_idxs), size, static))

    n_collectives = 0
    for rd in rounds:
        remote = [(msgs[i].src, msgs[i].dst) for i in rd.msg_idx
                  if msgs[i].src != msgs[i].dst]
        if not remote:
            continue
        compressed = _round_compressed(rd, msgs, attrs)
        itemsize = _itemsize(msgs[rd.msg_idx[0]].dst_slot.dtype)
        wire_elem = (rd.size // 4 + 1) if compressed else rd.size
        n_collectives += 2 if compressed else 1
        for s, d in remote:
            wire_sent[s] = wire_sent.get(s, 0) + wire_elem * itemsize
            wire_recv[d] = wire_recv.get(d, 0) + wire_elem * itemsize
    return tuple(rounds), max(n_collectives, 1)


def _plan_bruck(msgs: Sequence[Msg], p: int, attrs: SyncAttributes,
                wire_sent: Dict[int, int], wire_recv: Dict[int, int]
                ) -> Tuple[int, Tuple[Tuple[int, Tuple[int, ...]], ...], int]:
    pairs = set()
    for m in msgs:
        key = (m.src, m.dst)
        if key in pairs:
            raise LPFFatalError("bruck method requires unique (src,dst) pairs; "
                                "use method='direct' for multigraphs")
        pairs.add(key)
    m0 = msgs[0]
    for m in msgs:
        if (m.src_slot.sid != m0.src_slot.sid
                or m.dst_slot.sid != m0.dst_slot.sid):
            raise LPFFatalError("bruck method requires a single slot pair")
    w = max(m.size for m in msgs)
    itemsize = _itemsize(m0.src_slot.dtype)
    nrounds = max(1, math.ceil(math.log2(p))) if p > 1 else 0
    steps: List[Tuple[int, Tuple[int, ...]]] = []
    n_collectives = 0
    for k in range(nrounds):
        step = 1 << k
        rows = tuple(r for r in range(1, p) if r & step)
        if not rows:
            continue
        steps.append((step, rows))
        n_collectives += 1
        vol = len(rows) * w * itemsize
        for pid in range(p):
            wire_sent[pid] = wire_sent.get(pid, 0) + vol
            wire_recv[pid] = wire_recv.get(pid, 0) + vol
    return w, tuple(steps), max(n_collectives, 1)


def _plan_valiant_split(msgs: Sequence[Msg], p: int, seed: int,
                        scratch: Slot
                        ) -> Tuple[List[int], List[int], List[int]]:
    """Assign each message a seeded-hash intermediate and scratch offset."""
    cursor = np.zeros(p, dtype=np.int64)
    order = sorted(range(len(msgs)),
                   key=lambda i: (msgs[i].src, msgs[i].dst, msgs[i].dst_off))
    via: List[int] = []
    offs: List[int] = []
    for rank, i in enumerate(order):
        m = msgs[i]
        t = (m.src * 2654435761 + m.dst * 40503 + rank * 97 + seed) % p
        off = int(cursor[t])
        if off + m.size > scratch.size:
            raise LPFFatalError(
                "valiant scratch overflow; resize_message_queue with a "
                "larger payload capacity")
        cursor[t] += m.size
        via.append(t)
        offs.append(off)
    return order, via, offs


def _valiant_phase_msgs(msgs: Sequence[Msg], order: Sequence[int],
                        via: Sequence[int], offs: Sequence[int],
                        scratch: Slot) -> Tuple[List[Msg], List[Msg]]:
    phase1 = [Msg(msgs[i].src, t, msgs[i].src_slot, msgs[i].src_off,
                  scratch, off, msgs[i].size)
              for i, t, off in zip(order, via, offs)]
    phase2 = [Msg(t, msgs[i].dst, scratch, off,
                  msgs[i].dst_slot, msgs[i].dst_off, msgs[i].size)
              for i, t, off in zip(order, via, offs)]
    return phase1, phase2


def plan_sync(msgs: Sequence[Msg], p: int, attrs: SyncAttributes,
              scratch: Optional[Slot] = None) -> SuperstepPlan:
    """Phases (1)-(2): validate, arbitrate, classify, colour, and cost one
    superstep.  Pure Python on static metadata — no JAX ops, no traced
    values — so it can run (and be property-tested) without any mesh."""
    msgs = list(msgs)
    for m in msgs:
        m.validate(p)
    if attrs.reduce_op is not None:
        if attrs.reduce_op not in _REDUCE_FNS:
            raise LPFFatalError(
                f"unknown reduce_op {attrs.reduce_op!r}; expected one of "
                f"{sorted(_REDUCE_FNS)}")
        if attrs.method in ("bruck", "valiant"):
            raise LPFFatalError(
                "reduce_op supersteps support method 'auto' or 'direct' "
                f"only, not {attrs.method!r}")
    wire_sent: Dict[int, int] = {}
    wire_recv: Dict[int, int] = {}

    if not msgs or p == 0:
        return SuperstepPlan(
            method="noop", p=max(p, 1), n_msgs=len(msgs),
            cost=plan_cost(msgs, max(p, 1), attrs, "", "noop", 0,
                           wire_sent, wire_recv))

    if p == 1:
        # LPF_ROOT / sequential context: puts degenerate to memcpys.
        order = tuple(sorted(range(len(msgs)),
                             key=lambda i: (msgs[i].src, msgs[i].dst,
                                            msgs[i].dst_off)))
        return SuperstepPlan(
            method="seq", p=p, n_msgs=len(msgs), seq_order=order,
            reduce_op=attrs.reduce_op,
            cost=plan_cost(msgs, p, attrs, "", "noop", 0,
                           wire_sent, wire_recv))

    method = attrs.method
    det_rs = det_te = det_ag = det_sc = det_ga = None
    if method == "auto":
        if (det_rs := _detect_reduce_scatter(msgs, p, attrs)) is not None:
            method = "fused_rs"
        elif (det_te := _detect_total_exchange(msgs, p)) is not None:
            method = "fused"
        elif (det_ag := _detect_allgather(msgs, p)) is not None:
            method = "fused_ag"
        elif attrs.compress is None and \
                (det_sc := _detect_scatter(msgs, p)) is not None:
            method = "fused_scatter"
        elif attrs.compress is None and \
                (det_ga := _detect_gather(msgs, p)) is not None:
            method = "fused_gather"
        elif attrs.reduce_op is not None:
            method = "direct"    # bruck cannot combine conflicting writes
        else:
            # latency heuristic: many small messages per process -> bruck
            per_src: Dict[int, int] = {}
            for m in msgs:
                per_src[m.src] = per_src.get(m.src, 0) + 1
            max_deg = max(per_src.values())
            uniq = len({(m.src, m.dst) for m in msgs}) == len(msgs)
            one_pair = len({(m.src_slot.sid, m.dst_slot.sid)
                            for m in msgs}) == 1
            sizes = [m.size for m in msgs]
            small = max(sizes) <= 4 * max(1, min(sizes))
            if uniq and one_pair and small and max_deg > 4 * math.ceil(
                    math.log2(p)):
                method = "bruck"
            else:
                method = "direct"

    if method == "fused_rs":
        src_slot, dst_slot, w, rs_off = det_rs
        itemsize = _itemsize(src_slot.dtype)
        for pid in range(p):
            wire_sent[pid] = (p - 1) * w * itemsize
            wire_recv[pid] = (p - 1) * w * itemsize
        return SuperstepPlan(
            method="fused_rs", p=p, n_msgs=len(msgs), fused_w=w,
            reduce_op=attrs.reduce_op,
            rs_dst_off=tuple(int(o) for o in rs_off),
            cost=plan_cost(msgs, p, attrs, "", "fused_rs", 1,
                           wire_sent, wire_recv))

    if method == "fused_scatter":
        src_slot, dst_slot, w, root, sc_off, sc_mask = det_sc
        itemsize = _itemsize(src_slot.dtype)
        # the all_to_all schedule moves (p-1)*w per process — same h as
        # the root's send volume, for a single l instead of p-1
        for pid in range(p):
            wire_sent[pid] = (p - 1) * w * itemsize
            wire_recv[pid] = (p - 1) * w * itemsize
        return SuperstepPlan(
            method="fused_scatter", p=p, n_msgs=len(msgs), fused_w=w,
            fused_root=root, reduce_op=attrs.reduce_op,
            sc_dst_off=tuple(int(o) for o in sc_off),
            sc_mask=tuple(int(m_) for m_ in sc_mask),
            cost=plan_cost(msgs, p, attrs, "", "fused_scatter", 1,
                           wire_sent, wire_recv))

    if method == "fused_gather":
        src_slot, dst_slot, w, root, g_off, g_self = det_ga
        itemsize = _itemsize(src_slot.dtype)
        for pid in range(p):
            wire_sent[pid] = (p - 1) * w * itemsize
            wire_recv[pid] = (p - 1) * w * itemsize
        return SuperstepPlan(
            method="fused_gather", p=p, n_msgs=len(msgs), fused_w=w,
            fused_root=root, reduce_op=attrs.reduce_op,
            g_src_off=tuple(int(o) for o in g_off), g_has_self=g_self,
            cost=plan_cost(msgs, p, attrs, "", "fused_gather", 1,
                           wire_sent, wire_recv))

    if method == "fused_ag":
        src_slot, dst_slot, w, src_off = det_ag
        compressed = attrs.compress is not None and _is_floating(
            src_slot.dtype)
        itemsize = 1 if compressed else _itemsize(src_slot.dtype)
        for pid in range(p):
            wire_sent[pid] = (p - 1) * w * itemsize
            wire_recv[pid] = (p - 1) * w * itemsize
        return SuperstepPlan(
            method="fused_ag", p=p, n_msgs=len(msgs), fused_w=w,
            ag_src_off=tuple(int(o) for o in src_off),
            ag_exclude_self=len(msgs) == p * (p - 1),
            cost=plan_cost(msgs, p, attrs, "", "fused_ag", 1,
                           wire_sent, wire_recv))

    if method == "fused":
        src_slot, dst_slot, w = det_te
        compressed = attrs.compress is not None and _is_floating(
            src_slot.dtype)
        itemsize = 1 if compressed else _itemsize(src_slot.dtype)
        for pid in range(p):
            wire_sent[pid] = (p - 1) * w * itemsize
            wire_recv[pid] = (p - 1) * w * itemsize
        return SuperstepPlan(
            method="fused", p=p, n_msgs=len(msgs), fused_w=w,
            cost=plan_cost(msgs, p, attrs, "", "fused", 1,
                           wire_sent, wire_recv))

    if method == "valiant":
        if scratch is None:
            raise LPFFatalError("valiant routing needs a scratch slot; the "
                                "context provisions one via "
                                "resize_message_queue(payload=...)")
        order, via, offs = _plan_valiant_split(msgs, p, attrs.valiant_seed,
                                               scratch)
        ph1, ph2 = _valiant_phase_msgs(msgs, order, via, offs, scratch)
        sub = attrs.replace(method="direct")
        rounds1, r1 = _plan_direct(ph1, sub, wire_sent, wire_recv)
        rounds2, r2 = _plan_direct(ph2, sub, wire_sent, wire_recv)
        return SuperstepPlan(
            method="valiant", p=p, n_msgs=len(msgs),
            valiant_order=tuple(order), valiant_via=tuple(via),
            valiant_off=tuple(offs),
            valiant_phase1=rounds1, valiant_phase2=rounds2,
            cost=plan_cost(msgs, p, attrs, "", "valiant", r1 + r2,
                           wire_sent, wire_recv))

    if method == "bruck":
        w, steps, rounds = _plan_bruck(msgs, p, attrs, wire_sent, wire_recv)
        return SuperstepPlan(
            method="bruck", p=p, n_msgs=len(msgs), bruck_w=w,
            bruck_steps=steps,
            cost=plan_cost(msgs, p, attrs, "", "bruck", rounds,
                           wire_sent, wire_recv))

    rounds_plan, rounds = _plan_direct(msgs, attrs, wire_sent, wire_recv)
    return SuperstepPlan(
        method="direct", p=p, n_msgs=len(msgs), rounds=rounds_plan,
        reduce_op=attrs.reduce_op,
        cost=plan_cost(msgs, p, attrs, "", "direct", rounds,
                       wire_sent, wire_recv))


# ==========================================================================
# Stage 2: CACHE — canonical signatures and memoised plans
# ==========================================================================

def plan_signature(msgs: Sequence[Msg], p: int, attrs: SyncAttributes,
                   scratch: Optional[Slot] = None) -> Hashable:
    """A hashable key identifying every input :func:`plan_sync` reads.

    Slot ids are renamed to first-occurrence indices and described by
    ``(size, dtype, kind)``, so the same h-relation staged through freshly
    registered slots (a collective called in a loop, a per-layer gradient
    sync) maps to the same key.  Message *order* is part of the key: CRCW
    arbitration is order-sensitive, so a permuted table is a different
    plan."""
    canon: Dict[int, int] = {}
    slots: List[Tuple[int, str, str]] = []

    def slot_key(slot: Slot) -> int:
        idx = canon.get(slot.sid)
        if idx is None:
            idx = canon[slot.sid] = len(canon)
            slots.append((slot.size, str(np.dtype(slot.dtype)), slot.kind))
        return idx

    table = tuple((m.src, m.dst, slot_key(m.src_slot), m.src_off,
                   slot_key(m.dst_slot), m.dst_off, m.size, m.origin)
                  for m in msgs)
    if attrs.method == "valiant":
        scratch_sig = (attrs.valiant_seed,
                       None if scratch is None
                       else (scratch.size, str(np.dtype(scratch.dtype))))
    else:
        scratch_sig = None
    return (p, attrs.method, attrs.no_conflict, attrs.reduce_op,
            attrs.compress, scratch_sig, tuple(slots), table)


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    evictions: int = 0
    #: warm starts served from a persistent store (entry loaded from
    #: disk, re-verified, promoted to memory — no re-plan, no re-search)
    disk_hits: int = 0
    #: in-memory misses that also found no usable entry on disk (only
    #: counted while a persistent store is attached)
    disk_misses: int = 0
    #: on-disk entries rejected — corruption, version skew, signature
    #: mismatch, or failed re-verification — each degraded to a cold miss
    invalidated: int = 0
    #: persistent-store I/O failures (full disk, read-only dir, read
    #: errors) absorbed by the degradation ladder: each cost a retry
    #: loop and at worst the warm start, never the execution.  Past
    #: ``ProgramCache.DISK_STRIKE_LIMIT`` consecutive failures the
    #: cache detaches its store and runs memory-only.
    disk_errors: int = 0

    @property
    def plans(self) -> int:
        """Planning passes actually run (== misses)."""
        return self.misses

    def reset(self) -> None:
        """Zero the counters in place (the cache contents stay warm) —
        benchmarks and replay tests measure hit/miss deltas without a
        process restart or a cold cache."""
        self.hits = self.misses = self.evictions = 0
        self.disk_hits = self.disk_misses = self.invalidated = 0
        self.disk_errors = 0


class PlanCache:
    """LRU memo of :class:`SuperstepPlan` keyed by :func:`plan_signature`.

    Planning is trace-time Python, so a 64-superstep FFT whose stages
    repeat a handful of distinct relations re-plans each relation once and
    replays the cached IR for the other supersteps."""

    def __init__(self, maxsize: int = 1024):
        self.maxsize = maxsize
        self._plans: "collections.OrderedDict[Hashable, SuperstepPlan]" = \
            collections.OrderedDict()
        self.stats = CacheStats()

    def __len__(self) -> int:
        return len(self._plans)

    def clear(self) -> None:
        self._plans.clear()
        self.stats = CacheStats()

    def get_or_plan(self, msgs: Sequence[Msg], p: int,
                    attrs: SyncAttributes,
                    scratch: Optional[Slot] = None) -> SuperstepPlan:
        key = plan_signature(msgs, p, attrs, scratch)
        plan = self._plans.get(key)
        if plan is not None:
            self.stats.hits += 1
            self._plans.move_to_end(key)
            return plan
        plan = plan_sync(msgs, p, attrs, scratch)
        self.stats.misses += 1
        self._plans[key] = plan
        if len(self._plans) > self.maxsize:
            self._plans.popitem(last=False)
            self.stats.evictions += 1
        return plan


_GLOBAL_PLAN_CACHE = PlanCache()


def global_plan_cache() -> PlanCache:
    """The process-wide plan cache (shared across contexts and traces)."""
    return _GLOBAL_PLAN_CACHE


# ==========================================================================
# Stage 3: EXECUTE — lowering a plan to XLA collectives (traced)
# ==========================================================================

def _gather_payload(val: jnp.ndarray, offs: np.ndarray, size: int,
                    myid: jnp.ndarray, static_off: Optional[int]) -> jnp.ndarray:
    """Extract ``size`` elements starting at a per-PID offset."""
    if static_off is not None:
        return lax.dynamic_slice(val, (static_off,), (size,)) \
            if static_off + size <= val.shape[0] else \
            jnp.take(val, static_off + jnp.arange(size), mode="fill",
                     fill_value=0)
    off = jnp.asarray(offs)[myid]
    if int(np.max(offs)) + size <= val.shape[0]:
        return lax.dynamic_slice(val, (off,), (size,))
    idx = off + jnp.arange(size)
    return jnp.take(val, idx, mode="fill", fill_value=0)


def _scatter_payload(val: jnp.ndarray, payload: jnp.ndarray,
                     offs: np.ndarray, sizes: np.ndarray, mask: np.ndarray,
                     myid: jnp.ndarray) -> jnp.ndarray:
    """Blend ``payload`` into ``val`` at a per-PID offset with per-PID
    length; PIDs with ``mask == 0`` keep their data untouched."""
    size = payload.shape[0]
    off = jnp.asarray(offs)[myid]
    nrecv = jnp.asarray(sizes)[myid]
    active = jnp.asarray(mask)[myid]
    keep = (jnp.arange(size) < nrecv) & (active > 0)
    if int(np.max(offs)) + size <= val.shape[0]:
        cur = lax.dynamic_slice(val, (off,), (size,))
        new = jnp.where(keep, payload, cur)
        return lax.dynamic_update_slice(val, new, (off,))
    idx = off + jnp.arange(size)
    return val.at[idx].set(jnp.where(keep, payload, val.at[idx].get(
        mode="fill", fill_value=0)), mode="drop")


def _scatter_payload_acc(val: jnp.ndarray, written: jnp.ndarray,
                         payload: jnp.ndarray, offs: np.ndarray,
                         sizes: np.ndarray, mask: np.ndarray, myid,
                         op) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Accumulating delivery: masked elements combine via ``op`` with
    writes applied earlier in the same superstep (``written`` tracks
    them); the first write to an element replaces its old value."""
    size = payload.shape[0]
    off = jnp.asarray(offs)[myid]
    nrecv = jnp.asarray(sizes)[myid]
    active = jnp.asarray(mask)[myid]
    keep = (jnp.arange(size) < nrecv) & (active > 0)
    if int(np.max(offs)) + size <= val.shape[0]:
        cur = lax.dynamic_slice(val, (off,), (size,))
        wr = lax.dynamic_slice(written, (off,), (size,))
        new = jnp.where(keep, jnp.where(wr, op(cur, payload), payload), cur)
        val = lax.dynamic_update_slice(val, new, (off,))
        written = lax.dynamic_update_slice(written, wr | keep, (off,))
        return val, written
    idx = off + jnp.arange(size)
    cur = val.at[idx].get(mode="fill", fill_value=0)
    wr = written.at[idx].get(mode="fill", fill_value=False)
    new = jnp.where(keep, jnp.where(wr, op(cur, payload), payload), cur)
    val = val.at[idx].set(new, mode="drop")
    written = written.at[idx].set(wr | keep, mode="drop")
    return val, written


def _maybe_compress(payload: jnp.ndarray, attrs: SyncAttributes):
    """int8 symmetric quantisation of a float payload (lower effective g)."""
    spec = attrs.compress
    if spec is None or not jnp.issubdtype(payload.dtype, jnp.floating):
        return payload, None
    if spec.bits != 8:
        raise LPFFatalError(f"unsupported compression bits={spec.bits}")
    scale = jnp.max(jnp.abs(payload)) / 127.0 + 1e-30
    q = jnp.clip(jnp.round(payload / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def _maybe_decompress(payload, scale, dtype):
    if scale is None:
        return payload
    return (payload.astype(jnp.float32) * scale).astype(dtype)


def _ppermute(x, axes: AxisNames, perm: List[Tuple[int, int]]):
    return lax.ppermute(x, axes if len(axes) > 1 else axes[0], perm)


def _direct_begin(registry: SlotRegistry, msgs: Sequence[Msg],
                  rounds: Sequence[RoundPlan], p: int, axes: AxisNames,
                  myid, attrs: SyncAttributes,
                  reduce_op: Optional[str] = None) -> Callable[[], None]:
    """Split-phase lowering of planned ``direct`` rounds: the *start*
    phase extracts every payload from the pre-sync slot values (LPF reads
    observe the pre-superstep state) and issues one ``ppermute`` per
    round; the returned *finish* closure applies the ordered deliveries.
    With ``reduce_op``, deliveries that overlap earlier deliveries of
    this superstep combine elementwise instead of overwriting."""
    reduce_fn = _REDUCE_FNS[reduce_op] if reduce_op is not None else None
    # ---- start: extraction (reads observe pre-sync values) ----
    extracted: List[jnp.ndarray] = []
    scales: List[Optional[jnp.ndarray]] = []
    for rd in rounds:
        src_slot = msgs[rd.msg_idx[0]].src_slot
        offs = np.zeros(p, dtype=np.int32)
        for i in rd.msg_idx:
            offs[msgs[i].src] = msgs[i].src_off
        payload = _gather_payload(registry.value(src_slot), offs, rd.size,
                                  myid, rd.static_src_off)
        payload, scale = _maybe_compress(payload, attrs)
        extracted.append(payload)
        scales.append(scale)

    # ---- start: the exchanges (no slot writes yet) ----
    deliveries: List[Tuple[Slot, jnp.ndarray, np.ndarray, np.ndarray,
                           np.ndarray]] = []
    for rd, payload, scale in zip(rounds, extracted, scales):
        rd_msgs = [msgs[i] for i in rd.msg_idx]
        remote = [(m.src, m.dst) for m in rd_msgs if m.src != m.dst]
        dst_slot = rd_msgs[0].dst_slot
        if remote:
            arrived = _ppermute(payload, axes, remote)
            if scale is not None:
                arrived_scale = _ppermute(scale, axes, remote)
        else:
            arrived, arrived_scale = payload, scale
        # self-messages bypass the wire (a local memcpy, as in the paper's
        # shared-memory backend)
        selfs = [(m.src, m.dst) for m in rd_msgs if m.src == m.dst]
        if selfs and remote:
            self_mask = np.zeros(p, np.int8)
            for s, _ in selfs:
                self_mask[s] = 1
            pick = jnp.asarray(self_mask)[myid] > 0
            arrived = jnp.where(pick, payload, arrived)
            if scale is not None:
                arrived_scale = jnp.where(pick, scale, arrived_scale)
        arrived = _maybe_decompress(
            arrived, arrived_scale if scale is not None else None,
            dst_slot.dtype)

        offs = np.zeros(p, dtype=np.int32)
        sizes = np.zeros(p, dtype=np.int32)
        mask = np.zeros(p, dtype=np.int8)
        for m in rd_msgs:
            offs[m.dst] = m.dst_off
            sizes[m.dst] = m.size
            mask[m.dst] = 1
        deliveries.append((dst_slot, arrived, offs, sizes, mask))

    def finish() -> None:
        written: Dict[int, jnp.ndarray] = {}   # dst sid -> delivered mask
        for dst_slot, arrived, offs, sizes, mask in deliveries:
            if reduce_fn is None:
                registry.set_value(dst_slot, _scatter_payload(
                    registry.value(dst_slot), arrived, offs, sizes, mask,
                    myid))
            else:
                wr = written.get(dst_slot.sid)
                if wr is None:
                    wr = jnp.zeros(dst_slot.size, jnp.bool_)
                val, wr = _scatter_payload_acc(
                    registry.value(dst_slot), wr, arrived, offs, sizes,
                    mask, myid, reduce_fn)
                written[dst_slot.sid] = wr
                registry.set_value(dst_slot, val)

    return finish


def _execute_direct(registry: SlotRegistry, msgs: Sequence[Msg],
                    rounds: Sequence[RoundPlan], p: int, axes: AxisNames,
                    myid, attrs: SyncAttributes,
                    reduce_op: Optional[str] = None) -> None:
    _direct_begin(registry, msgs, rounds, p, axes, myid, attrs,
                  reduce_op)()


def _bruck_begin(registry: SlotRegistry, msgs: Sequence[Msg],
                 plan: SuperstepPlan, p: int, axes: AxisNames,
                 myid) -> Callable[[], None]:
    """Split-phase lowering of planned Bruck rounds.

    Row ``r`` of the working matrix holds the payload this process
    currently carries whose *original* relative distance (dst - origin
    mod p) is ``r``.  All blocks of equal original distance move through
    identical hop sequences, so row sets per round are static.  The start
    phase runs the log-rounds exchange; the finish closure applies the
    deliveries."""
    w = plan.bruck_w
    m0 = msgs[0]
    src_slot, dst_slot = m0.src_slot, m0.dst_slot

    # tables[src, rel] -> offset/size/mask of the message src -> src+rel
    src_off = np.zeros((p, p), np.int32)
    dst_off = np.zeros((p, p), np.int32)
    sizes = np.zeros((p, p), np.int32)
    mask = np.zeros((p, p), np.int8)
    for m in msgs:
        rel = (m.dst - m.src) % p
        src_off[m.src, rel] = m.src_off
        dst_off[m.dst, rel] = m.dst_off   # indexed by *receiver* pid
        sizes[m.src, rel] = m.size
        mask[m.src, rel] = 1
    val = registry.value(src_slot)
    my_off = jnp.asarray(src_off)[myid]                       # [p]
    idx = my_off[:, None] + jnp.arange(w)[None, :]            # [p, w]
    buf = jnp.take(val, idx.reshape(-1), mode="fill",
                   fill_value=0).reshape(p, w)

    for step, rows in plan.bruck_steps:
        sub = buf[np.asarray(rows)]
        perm = [(i, (i + step) % p) for i in range(p)]
        sub = _ppermute(sub, axes, perm)
        buf = buf.at[np.asarray(rows)].set(sub)

    def finish() -> None:
        # delivery: row r arrived from origin (me - r) % p; write at the
        # receiver-side offset table entries.
        out = registry.value(dst_slot)
        my_dst_off = jnp.asarray(dst_off)[myid]               # [p]
        my_sizes = jnp.asarray(sizes)                         # [p(src), p(rel)]
        origin = (myid - jnp.arange(p)) % p
        my_len = my_sizes[origin, jnp.arange(p)]              # [p]
        my_mask = jnp.asarray(mask)[origin, jnp.arange(p)]    # [p]
        # apply rows in ascending origin pid order for CRCW determinism
        for r in range(p):
            keep = (jnp.arange(w) < my_len[r]) & (my_mask[r] > 0)
            tgt = my_dst_off[r] + jnp.arange(w)
            cur = out.at[tgt].get(mode="fill",
                                  fill_value=0)
            out2 = out.at[tgt].set(jnp.where(keep, buf[r], cur),
                                   mode="drop")
            out = out2
        registry.set_value(dst_slot, out)

    return finish


def begin_plan(plan: SuperstepPlan, registry: SlotRegistry,
               msgs: Sequence[Msg], p: int, axes: AxisNames, myid,
               attrs: SyncAttributes,
               scratch: Optional[Slot] = None) -> Callable[[], None]:
    """Phase (3), split-phase: issue the superstep's reads and collectives
    (the *start* half) and return a finish closure that applies its slot
    writes (the *done* half).

    The contract that makes overlap legal: the start half reads source
    payloads from the current slot values and launches the exchanges, but
    performs **no** slot writes; every destination-slot read and write
    happens inside the returned closure.  :func:`execute_overlapped` runs
    all starts of an overlap group before any finish, so every member
    observes the group-entry state — exactly the semantics of independent
    supersteps whose order cannot matter.  (``valiant`` is the exception:
    its phase-1 scratch writes land in the start half, which is why the
    optimizer never overlaps valiant supersteps.)"""
    if plan.method == "noop":
        return lambda: None

    if plan.method == "seq":
        reduce_fn = _REDUCE_FNS[plan.reduce_op] if plan.reduce_op else None
        # extract every payload before any write lands (LPF reads
        # observe the pre-superstep state, exactly as the direct path)
        pre = {m.src_slot.sid: registry.value(m.src_slot)
               for i in plan.seq_order for m in (msgs[i],)}
        chunks = [lax.dynamic_slice(pre[msgs[i].src_slot.sid],
                                    (msgs[i].src_off,), (msgs[i].size,))
                  for i in plan.seq_order]

        def finish_seq() -> None:
            written: Dict[int, np.ndarray] = {}   # static masks: p == 1
            for i, chunk in zip(plan.seq_order, chunks):
                m = msgs[i]
                dst = registry.value(m.dst_slot)
                piece = chunk
                if reduce_fn is not None:
                    wr = written.setdefault(m.dst_slot.sid,
                                            np.zeros(m.dst_slot.size, bool))
                    seg = wr[m.dst_off:m.dst_off + m.size].copy()
                    if seg.any():
                        cur = lax.dynamic_slice(dst, (m.dst_off,),
                                                (m.size,))
                        piece = jnp.where(jnp.asarray(seg),
                                          reduce_fn(cur, piece), piece)
                    wr[m.dst_off:m.dst_off + m.size] = True
                registry.set_value(m.dst_slot,
                                   lax.dynamic_update_slice(dst, piece,
                                                            (m.dst_off,)))

        return finish_seq

    if plan.method == "fused_rs":
        w = plan.fused_w
        m0 = msgs[0]
        src_slot, dst_slot = m0.src_slot, m0.dst_slot
        x = registry.value(src_slot)[: p * w].reshape(p, w)
        axis = axes if len(axes) > 1 else axes[0]
        if plan.reduce_op == "sum":
            y = lax.psum_scatter(x, axis, scatter_dimension=0, tiled=False)
        else:
            # row s of the exchange holds process s's contribution to me
            contrib = lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                     tiled=False)
            y = (jnp.max if plan.reduce_op == "max" else jnp.min)(
                contrib, axis=0)
        off = jnp.asarray(np.asarray(plan.rs_dst_off, np.int32))[myid]

        def finish_rs() -> None:
            dst = registry.value(dst_slot)
            registry.set_value(dst_slot, lax.dynamic_update_slice(
                dst, y.astype(dst_slot.dtype), (off,)))

        return finish_rs

    if plan.method == "fused_scatter":
        w = plan.fused_w
        m0 = msgs[0]
        src_slot, dst_slot = m0.src_slot, m0.dst_slot
        x = registry.value(src_slot)[: p * w].reshape(p, w)
        axis = axes if len(axes) > 1 else axes[0]
        # row r of the result is what process r sent me; only the root's
        # row carries data — the rest is the masked schedule's padding
        y = lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                           tiled=False)
        chunk = y[plan.fused_root]
        off = jnp.asarray(np.asarray(plan.sc_dst_off, np.int32))[myid]
        active = jnp.asarray(np.asarray(plan.sc_mask, np.int8))[myid] > 0

        def finish_sc() -> None:
            dst = registry.value(dst_slot)
            cur = lax.dynamic_slice(dst, (off,), (w,))
            new = jnp.where(active, chunk.astype(dst_slot.dtype), cur)
            registry.set_value(dst_slot,
                               lax.dynamic_update_slice(dst, new, (off,)))

        return finish_sc

    if plan.method == "fused_gather":
        w = plan.fused_w
        m0 = msgs[0]
        src_slot, dst_slot = m0.src_slot, m0.dst_slot
        src_off = np.asarray(plan.g_src_off, np.int32)
        sval = registry.value(src_slot)
        if (src_off == src_off[0]).all():
            x = lax.dynamic_slice(sval, (int(src_off[0]),), (w,))
        else:
            x = _gather_payload(sval, src_off, w, myid, None)
        axis = axes if len(axes) > 1 else axes[0]
        y_gathered = lax.all_gather(x, axis, tiled=True)     # [p * w]

        def finish_ga() -> None:
            y = y_gathered
            dst = registry.value(dst_slot)
            if not plan.g_has_self:
                # root keeps its own chunk: no root -> root msg was staged
                own = lax.dynamic_slice(dst, (plan.fused_root * w,), (w,))
                y = lax.dynamic_update_slice(y, own, (plan.fused_root * w,))
            is_root = myid == plan.fused_root
            new = jnp.where(is_root, y.astype(dst_slot.dtype), dst[: p * w])
            registry.set_value(dst_slot,
                               lax.dynamic_update_slice(dst, new, (0,)))

        return finish_ga

    if plan.method == "fused_ag":
        w = plan.fused_w
        m0 = msgs[0]
        src_slot, dst_slot = m0.src_slot, m0.dst_slot
        src_off = np.asarray(plan.ag_src_off, np.int32)
        sval = registry.value(src_slot)
        if (src_off == src_off[0]).all():
            x = lax.dynamic_slice(sval, (int(src_off[0]),), (w,))
        else:
            x = _gather_payload(sval, src_off, w, myid, None)
        axis = axes if len(axes) > 1 else axes[0]
        x, scale = _maybe_compress(x, attrs)
        y_gathered = lax.all_gather(x, axis, tiled=True)
        if scale is not None:
            scales = lax.all_gather(scale, axis, tiled=False)  # [p]
            y_gathered = (y_gathered.reshape(p, w).astype(jnp.float32)
                          * scales[:, None]).reshape(p * w).astype(
                              src_slot.dtype)

        def finish_ag() -> None:
            y = y_gathered
            dst = registry.value(dst_slot)
            if plan.ag_exclude_self:
                # exclude-self variant: keep own chunk as-is
                own = lax.dynamic_slice(dst, (myid * w,), (w,))
                y = lax.dynamic_update_slice(y, own, (myid * w,))
            registry.set_value(dst_slot,
                               lax.dynamic_update_slice(dst, y, (0,)))

        return finish_ag

    if plan.method == "fused":
        w = plan.fused_w
        m0 = msgs[0]
        src_slot, dst_slot = m0.src_slot, m0.dst_slot
        x = registry.value(src_slot)[: p * w].reshape(p, w)
        axis = axes if len(axes) > 1 else axes[0]
        scale = None
        if attrs.compress is not None and jnp.issubdtype(
                x.dtype, jnp.floating):
            # per-destination-row scales travel alongside the payload
            scale = jnp.max(jnp.abs(x), axis=1) / 127.0 + 1e-30  # [p]
            x = jnp.clip(jnp.round(x / scale[:, None]),
                         -127, 127).astype(jnp.int8)
        y = lax.all_to_all(x, axis, split_axis=0, concat_axis=0, tiled=False)
        if scale is not None:
            scales = lax.all_to_all(scale, axis, split_axis=0,
                                    concat_axis=0, tiled=False)  # [p]
            y = (y.astype(jnp.float32) * scales[:, None]).astype(
                src_slot.dtype)
        y = y.reshape(p * w)

        def finish_fused() -> None:
            dst = registry.value(dst_slot)
            registry.set_value(dst_slot,
                               lax.dynamic_update_slice(dst, y, (0,)))

        return finish_fused

    if plan.method == "valiant":
        if scratch is None:
            raise LPFFatalError("valiant plan lowered without a scratch slot")
        ph1, ph2 = _valiant_phase_msgs(msgs, plan.valiant_order,
                                       plan.valiant_via, plan.valiant_off,
                                       scratch)
        sub = attrs.replace(method="direct")
        # phase 2 reads the scratch slot phase 1 writes — an internal
        # barrier, so phase 1 completes inside the start half (the
        # optimizer's overlap gate excludes valiant for exactly this)
        _execute_direct(registry, ph1, plan.valiant_phase1, p, axes, myid,
                        sub)
        return _direct_begin(registry, ph2, plan.valiant_phase2, p, axes,
                             myid, sub)

    if plan.method == "bruck":
        return _bruck_begin(registry, msgs, plan, p, axes, myid)

    return _direct_begin(registry, msgs, plan.rounds, p, axes, myid, attrs,
                         reduce_op=plan.reduce_op)


#: methods the overlap rewrite may schedule split-phase: their start
#: half performs no slot writes (valiant's phase-1 scratch writes land in
#: start, so two overlapped valiant supersteps would race the scratch)
OVERLAPPABLE_METHODS = frozenset(
    {"noop", "seq", "direct", "bruck", "fused", "fused_ag", "fused_rs",
     "fused_scatter", "fused_gather"})


def execute_plan(plan: SuperstepPlan, registry: SlotRegistry,
                 msgs: Sequence[Msg], p: int, axes: AxisNames, myid,
                 attrs: SyncAttributes, label: str,
                 scratch: Optional[Slot] = None) -> SuperstepCost:
    """Phase (3): lower ``plan`` against the current slot values.

    ``msgs`` must be the table the plan was built from, or any table with
    the same :func:`plan_signature` (the cache guarantees this).  Mutates
    registry values; returns the superstep's ledger entry — identical to
    the plan's predicted cost, with the label attached."""
    begin_plan(plan, registry, msgs, p, axes, myid, attrs,
               scratch=scratch)()
    return plan.cost_with_label(label)


def execute_overlapped(items: Sequence[Tuple[SuperstepPlan, Sequence[Msg],
                                             SyncAttributes, str]],
                       registry: SlotRegistry, p: int, axes: AxisNames,
                       myid, scratch: Optional[Slot] = None
                       ) -> SuperstepCost:
    """Issue one overlap group of independent supersteps split-phase: all
    *start* halves first (every member reads the group-entry slot state
    and launches its collectives back-to-back — the double-buffered
    chain XLA's scheduler can pipeline), then all *done* halves in
    program order.  Returns the group's single ledger entry, by
    construction :func:`repro.core.cost.overlap_cost` of the members'
    planned costs."""
    finishes = [begin_plan(plan, registry, list(msgs), p, axes, myid,
                           attrs, scratch=scratch)
                for plan, msgs, attrs, _ in items]
    for finish in finishes:
        finish()
    return overlap_cost([plan.cost for plan, _, _, _ in items],
                        label="||".join(label for _, _, _, label in items))


def execute_schedule(entries, groups, registry, p: int, axes: AxisNames,
                     myid, scratch: Optional[Slot] = None
                     ) -> List[SuperstepCost]:
    """Issue one optimized program's schedule: ``entries`` are the
    materialized ``(msgs, attrs, label, plan)`` supersteps and ``groups``
    the issue partition (singletons via :func:`execute_plan`, overlap
    groups via :func:`execute_overlapped`).  Its one caller is
    ``LPFContext``'s flush of a recorded program; the returned ledger
    entries are the plans' predicted costs, as
    :meth:`repro.core.program.SuperstepProgram.ledger_costs` lists
    them."""
    costs: List[SuperstepCost] = []
    for grp in groups:
        if len(grp) == 1:
            msgs, attrs, label, plan = entries[grp[0]]
            costs.append(execute_plan(plan, registry, msgs, p, axes, myid,
                                      attrs, label, scratch=scratch))
        else:
            costs.append(execute_overlapped(
                [(entries[i][3], entries[i][0], entries[i][1],
                  entries[i][2]) for i in grp],
                registry, p, axes, myid, scratch=scratch))
    return costs


# ==========================================================================
# entry point (plan + execute in one call)
# ==========================================================================

def execute_sync(registry: SlotRegistry, queue: Sequence[Msg], p: int,
                 axes: AxisNames, myid, attrs: SyncAttributes,
                 label: str, scratch: Optional[Slot] = None,
                 cache: Optional[PlanCache] = None) -> SuperstepCost:
    """Run one superstep; mutates registry values; returns its cost record.

    With ``cache`` the planning stage is memoised; pass ``None`` to force
    a fresh planning pass (the original single-stage behaviour)."""
    msgs = list(queue)
    if cache is not None:
        plan = cache.get_or_plan(msgs, p, attrs, scratch)
    else:
        plan = plan_sync(msgs, p, attrs, scratch)
    return execute_plan(plan, registry, msgs, p, axes, myid, attrs, label,
                        scratch=scratch)
