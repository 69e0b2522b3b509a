"""The DAG list-scheduler: schedule search over the trace dependency DAG.

Properties the search must uphold (the satellite suite of the schedule
search PR):

1. the scheduler's output is never predicted slower than the in-order
   recorded trace (every rewrite — merge, Valiant attr rewrite, overlap
   group, hoist — is cost-gated);
2. ``simulate_program`` equivalence holds under *arbitrary legal
   reorderings*: any topological order of the must-precede DAG executes
   bit-identically, and the searched schedule of a reordered recording
   still matches eager execution of the original;
3. reordered-but-equivalent traces canonicalize to one
   ``program_signature`` and therefore share one ``ProgramCache``
   entry, whose cached program materializes correctly against either
   recording.

Targeted tests pin the behaviours the adjacent-only peephole could not
reach: non-adjacent merges, non-adjacent overlap hoists, the
Valiant-aware attr rewrite, and ``SuperstepProgram.explain``.  The
fast-tier guard at the bottom prices the canned benchmark traces
(``benchmarks/schedule_search.py``) on the DCN model and fails if any
optimized predicted cost regresses past its recorded bound.
"""

import numpy as np
import pytest

from repro.core import (LPF_SYNC_DEFAULT, Msg, ProgramCache, ProgramStep,
                        Slot, SyncAttributes, canonical_order,
                        optimize_program, plan_sync, program_signature,
                        simulate_program)
from repro.core.machine import CPU_HOST, probe
from repro.core.program import _must_precede, trace_slot_map

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

pytestmark = pytest.mark.fast

MACHINE = probe({"x": 8}, CPU_HOST)


def table_property(fn):
    if HAVE_HYPOTHESIS:
        return settings(deadline=None)(
            given(st.integers(0, 2**31 - 1))(fn))
    return pytest.mark.parametrize("seed", range(60))(fn)


def make_slot(sid, size, dtype="int32", kind="global"):
    return Slot(sid=sid, name=f"s{sid}", size=size, dtype=np.dtype(dtype),
                kind=kind, orig_shape=(size,))


def random_program(seed):
    """Random legal trace; slot sizes are pairwise distinct so step
    content keys referencing fresh slots are unambiguous (identical-key
    ties are then only between truly interchangeable steps, keeping the
    reorder-invariant-signature property exact)."""
    rng = np.random.default_rng(seed)
    p = int(rng.integers(2, 8))
    n_slots = int(rng.integers(2, 5))
    sizes = rng.choice(np.arange(8, 40), size=n_slots, replace=False)
    slots = [make_slot(100 + i, int(sizes[i])) for i in range(n_slots)]
    steps = []
    for k in range(int(rng.integers(2, 7))):
        reduce_op = [None, None, None, "sum", "max", "min"][
            int(rng.integers(6))]
        attrs = SyncAttributes(
            method=["auto", "direct"][int(rng.integers(2))],
            reduce_op=reduce_op)
        msgs = []
        for _ in range(int(rng.integers(0, 9))):
            a = slots[int(rng.integers(len(slots)))]
            b = slots[int(rng.integers(len(slots)))]
            size = int(rng.integers(1, min(a.size, b.size) + 1))
            msgs.append(Msg(
                src=int(rng.integers(p)), dst=int(rng.integers(p)),
                src_slot=a, src_off=int(rng.integers(a.size - size + 1)),
                dst_slot=b, dst_off=int(rng.integers(b.size - size + 1)),
                size=size))
        steps.append(ProgramStep(tuple(msgs), attrs, f"s{k}"))
    return p, slots, steps


def initial_values(slots, p, seed):
    rng = np.random.default_rng(seed + 1)
    return {s.sid: rng.integers(-10_000, 10_000,
                                size=(p, s.size)).astype(np.int32)
            for s in slots}


def legal_reordering(steps, seed):
    """A random topological order of the trace's must-precede DAG —
    an *arbitrary legal reordering* of the recording."""
    rng = np.random.default_rng(seed + 13)
    n = len(steps)
    npreds = [0] * n
    succs = [[] for _ in range(n)]
    for i in range(n):
        for j in range(i + 1, n):
            if _must_precede(steps[i], steps[j]):
                succs[i].append(j)
                npreds[j] += 1
    ready = [i for i in range(n) if npreds[i] == 0]
    perm = []
    while ready:
        k = ready.pop(int(rng.integers(len(ready))))
        perm.append(k)
        for j in succs[k]:
            npreds[j] -= 1
            if npreds[j] == 0:
                ready.append(j)
    return perm


# ---------------------------------------------------------------------------
# (1) the searched schedule is never predicted slower than in-order
# ---------------------------------------------------------------------------

@table_property
def test_search_never_slower_than_in_order(seed):
    """Every rewrite is cost-gated, so the searched schedule's
    predicted BSP time (overlap pricing included) never exceeds the
    recorded trace's.  (Against the *peephole* the greedy search wins
    on the canned traces — enforced below and in the benchmark — but
    carries no blanket guarantee: different group boundaries can
    occasionally trade.)"""
    p, slots, steps = random_program(seed)
    prog = optimize_program(steps, p, MACHINE)
    raw = sum(
        plan_sync(list(s.msgs), p, s.attrs).cost.predicted_seconds(MACHINE)
        for s in steps)
    assert prog.predicted_seconds(MACHINE) <= raw + 1e-15
    assert abs(prog.in_order_seconds(MACHINE) - raw) < 1e-15
    # the peephole obeys the same in-order bound
    peephole = optimize_program(steps, p, MACHINE, search=False)
    assert peephole.predicted_seconds(MACHINE) <= raw + 1e-15


# ---------------------------------------------------------------------------
# (2) equivalence under arbitrary legal reorderings
# ---------------------------------------------------------------------------

@table_property
def test_legal_reordering_preserves_semantics(seed):
    """Any topological order of the must-precede DAG — the space the
    list-scheduler searches — executes bit-identically to the recorded
    order, and the searched schedule of the *reordered* recording still
    matches eager execution of the original."""
    p, slots, steps = random_program(seed)
    perm = legal_reordering(steps, seed)
    reordered = [steps[i] for i in perm]
    values = initial_values(slots, p, seed)
    eager = simulate_program([(s.msgs, s.attrs) for s in steps], values)
    shuffled = simulate_program([(s.msgs, s.attrs) for s in reordered],
                                values)
    for sid in eager:
        assert (eager[sid] == shuffled[sid]).all(), sid
    prog = optimize_program(reordered, p, MACHINE)
    tables = [(m, a) for m, a, _, _
              in prog.materialize(trace_slot_map(reordered))]
    opt = simulate_program(tables, values)
    for sid in eager:
        assert (eager[sid] == opt[sid]).all(), sid


# ---------------------------------------------------------------------------
# (3) reordered-equivalent traces share one ProgramCache signature
# ---------------------------------------------------------------------------

@table_property
def test_reordered_traces_share_signature_and_cache(seed):
    p, slots, steps = random_program(seed)
    perm = legal_reordering(steps, seed)
    reordered = [steps[i] for i in perm]
    assert program_signature(steps, p) == program_signature(reordered, p)
    # one cache entry serves both recordings, and the shared program
    # materializes correctly against the reordered trace
    cache = ProgramCache()
    prog1 = cache.get_or_build(steps, p, MACHINE)
    prog2 = cache.get_or_build(reordered, p, MACHINE)
    assert prog1 is prog2
    assert cache.stats.hits == 1 and cache.stats.misses == 1
    values = initial_values(slots, p, seed)
    eager = simulate_program([(s.msgs, s.attrs) for s in steps], values)
    tables = [(m, a) for m, a, _, _ in prog2.materialize(reordered)]
    opt = simulate_program(tables, values)
    for sid in eager:
        assert (eager[sid] == opt[sid]).all(), sid


def test_canonical_order_is_reorder_invariant():
    """The bucketed DDP shape interleaved two ways canonicalizes to one
    sequence (content-keyed ready selection, not recorded position)."""
    p = 4
    from benchmarks.schedule_search import canned_bucketed_trace
    _, _, steps, _ = canned_bucketed_trace(p=p, n_buckets=2)
    rs0, ag0, rs1, ag1 = steps
    a = [rs0, ag0, rs1, ag1]
    b = [rs0, rs1, ag1, ag0]          # a legal interleaving
    ca = [a[i] for i in canonical_order(a)]
    cb = [b[i] for i in canonical_order(b)]
    assert [s.label for s in ca] == [s.label for s in cb]
    assert program_signature(a, p) == program_signature(b, p)


def test_canonical_order_breaks_structural_ties():
    """Symmetric steps 1-WL colour refinement cannot separate must still
    share one cache entry (the C6 + 2xC3 counterexample).

    Twelve bit-identical read-only steps whose slot-*sharing* graph is a
    6-cycle plus two triangles: every step reads two ring slots and
    writes its own private slot, so there are no precedence edges, every
    content key is equal, and WL refinement colours all twelve steps
    identically (2-regular, identical neighbourhoods at every round) —
    yet a C6 step and a C3 step are NOT interchangeable.  The old
    tie-break fell back to recorded position, so two recordings of the
    same program could canonicalize differently and miss each other's
    ProgramCache entry; the canonical-form comparison must map every
    shuffle to one signature: exactly 1 miss, then all hits."""
    p = 4
    rng = np.random.default_rng(5)
    ring = [make_slot(i, 8) for i in range(12)]

    def step(i, a, b):
        w = make_slot(100 + i, 8)
        return ProgramStep((Msg(0, 1, ring[a], 0, w, 0, 4),
                            Msg(2, 3, ring[b], 0, w, 4, 4)),
                           LPF_SYNC_DEFAULT, "t")
    # C6 over ring[0:6], two C3s over ring[6:9] and ring[9:12]
    steps = [step(i, i, (i + 1) % 6) for i in range(6)]
    steps += [step(6 + i, 6 + i, 6 + (i + 1) % 3) for i in range(3)]
    steps += [step(9 + i, 9 + i, 9 + (i + 1) % 3) for i in range(3)]

    sig = program_signature(steps, p)
    cache = ProgramCache()
    cache.get_or_build(steps, p, MACHINE)
    for _ in range(6):
        shuffled = [steps[i] for i in rng.permutation(len(steps))]
        assert program_signature(shuffled, p) == sig
        cache.get_or_build(shuffled, p, MACHINE)
    assert cache.stats.misses == 1 and cache.stats.hits == 6
    assert len(cache) == 1


# ---------------------------------------------------------------------------
# targeted: what the adjacent-only peephole could not find
# ---------------------------------------------------------------------------

def test_non_adjacent_merge_over_blocker():
    """[A, X, B]: A and B are equal-attrs independent shifts, X is a
    reduce superstep between them.  Adjacent-only batching cannot merge
    A+B (X differs in attrs); the list-scheduler hoists B over X."""
    p = 4
    A, B, C = make_slot(1, 16), make_slot(2, 16), make_slot(3, 16)
    s_a = ProgramStep((Msg(0, 1, A, 0, B, 0, 4),), LPF_SYNC_DEFAULT, "a")
    s_x = ProgramStep((Msg(2, 0, A, 8, C, 0, 4),),
                      SyncAttributes(reduce_op="sum"), "x")
    s_b = ProgramStep((Msg(2, 3, A, 4, B, 4, 4),), LPF_SYNC_DEFAULT, "b")
    searched = optimize_program([s_a, s_x, s_b], p, MACHINE)
    peephole = optimize_program([s_a, s_x, s_b], p, MACHINE, search=False)
    assert peephole.n_merged == 0
    assert searched.n_merged == 1
    merged = next(s for s in searched.steps if len(s.merged_from) > 1)
    assert merged.label == "a+b"
    assert searched.n_hoisted >= 1
    assert searched.predicted_seconds(MACHINE) < \
        peephole.predicted_seconds(MACHINE)
    # semantics preserved
    values = initial_values([A, B, C], p, 3)
    eager = simulate_program([(s.msgs, s.attrs)
                              for s in (s_a, s_x, s_b)], values)
    tables = [(m, at) for m, at, _, _ in searched.materialize(
        trace_slot_map([s_a, s_x, s_b]))]
    opt = simulate_program(tables, values)
    for sid in eager:
        assert (eager[sid] == opt[sid]).all()


def test_non_adjacent_overlap_hoist():
    """[A, X, B]: X depends on A, B is independent of both and fat.
    The peephole's best is [A][X || B]; the search hoists B next to A —
    [A || B][X] — hiding the fat superstep under the other fat one."""
    p = 4
    w = 64
    SA, DA = make_slot(1, p * w), make_slot(2, p * w)
    SB, DB = make_slot(3, p * w), make_slot(4, p * w)
    XD = make_slot(5, 16)
    big_a = tuple(Msg(s, d, SA, d * w, DA, s * w, w)
                  for s in range(p) for d in range(p))
    big_b = tuple(Msg(s, d, SB, d * w, DB, s * w, w)
                  for s in range(p) for d in range(p))
    thin_x = (Msg(1, 2, DA, 0, XD, 0, 4),)       # reads A's output
    s_a = ProgramStep(big_a, LPF_SYNC_DEFAULT, "A")
    s_x = ProgramStep(thin_x, LPF_SYNC_DEFAULT, "X")
    s_b = ProgramStep(big_b, LPF_SYNC_DEFAULT, "B")
    searched = optimize_program([s_a, s_x, s_b], p, MACHINE)
    peephole = optimize_program([s_a, s_x, s_b], p, MACHINE, search=False)
    assert peephole.overlap_groups == ((0,), (1, 2),)
    # searched: A || B first (B hoisted over X), then X
    assert len(searched.groups()[0]) == 2
    labels = {searched.steps[i].label for i in searched.groups()[0]}
    assert labels == {"A", "B"}
    assert searched.n_hoisted >= 1
    assert searched.predicted_seconds(MACHINE) < \
        peephole.predicted_seconds(MACHINE)


def test_valiant_aware_rewrite_fires_and_is_exact():
    """The fragmented fat relation (WAR-coupled, so overlap is
    inadmissible): each 16-round direct superstep is rewritten to
    two-phase Valiant routing; the rewrite must be recorded,
    cost-improving, and bit-exact."""
    from benchmarks.schedule_search import (DCN, canned_fragmented_trace)
    p, slots, steps, scratch = canned_fragmented_trace()
    prog = optimize_program(steps, p, DCN, scratch=scratch)
    assert prog.n_rewritten == 2
    for st in prog.steps:
        assert st.rewrite == "valiant"
        assert st.attrs.method == "valiant"
        assert st.plan.method == "valiant"
        assert not st.unchanged
    assert prog.overlap_groups == ((0,), (1,))    # WAR: no overlap
    assert prog.predicted_seconds(DCN) < prog.in_order_seconds(DCN)
    # without a scratch slot the rewrite is inadmissible
    no_scratch = optimize_program(steps, p, DCN)
    assert no_scratch.n_rewritten == 0
    # semantics: simulate ignores the execution method — the rewrite is
    # only legal because the tables are conflict-free
    values = initial_values(slots, p, 5)
    eager = simulate_program([(s.msgs, s.attrs) for s in steps], values)
    tables = [(m, a) for m, a, _, _
              in prog.materialize(trace_slot_map(steps))]
    opt = simulate_program(tables, values)
    for sid in eager:
        assert (eager[sid] == opt[sid]).all()


def test_merged_valiant_rewrite():
    """When two fragmented supersteps share their slot-pair space (the
    merged table consolidates through few scratch groups) and a WAR
    coupling forbids overlap, the scheduler merges them AND rewrites
    the merged fat superstep to Valiant — the combined move of the
    merge gate and the attr rewrite."""
    p = 8
    A = [make_slot(300 + i, 32) for i in range(4)]
    B = [make_slot(310 + i, 32) for i in range(4)]
    C, scratch = make_slot(320, 32), make_slot(399, 4096)
    msgs1, msgs2 = [], []
    k = 0
    for a in A:
        for b in B:
            m = Msg((k * 3) % p, (k * 5 + 1) % p, a, (k * 2) % 16,
                    b, (k * 3) % 16, 4)
            (msgs1 if k % 2 == 0 else msgs2).append(m)
            k += 1
    # WAR coupling: frag2 writes the exact range frag1's first message
    # reads — overlap (commutation) is out, merging is still legal
    m0 = msgs1[0]
    msgs2.append(Msg(6, m0.src, C, 0, m0.src_slot, m0.src_off, m0.size))
    steps = [ProgramStep(tuple(msgs1), LPF_SYNC_DEFAULT, "frag1"),
             ProgramStep(tuple(msgs2), LPF_SYNC_DEFAULT, "frag2")]
    from benchmarks.schedule_search import DCN
    prog = optimize_program(steps, p, DCN, scratch=scratch)
    assert prog.n_merged == 1 and len(prog.steps) == 1
    assert prog.steps[0].rewrite == "valiant"
    assert prog.steps[0].merged_from == (0, 1)
    assert prog.predicted_seconds(DCN) < prog.in_order_seconds(DCN)
    values = initial_values(A + B + [C], p, 11)
    eager = simulate_program([(s.msgs, s.attrs) for s in steps], values)
    tables = [(m, a) for m, a, _, _
              in prog.materialize(trace_slot_map(steps))]
    opt = simulate_program(tables, values)
    for sid in eager:
        assert (eager[sid] == opt[sid]).all()


def test_valiant_rewrite_refused_on_conflicting_writes():
    """A method rewrite must never change CRCW winners: tables with
    overlapping destination writes keep their recorded method."""
    p = 8
    A, B = make_slot(1, 64), make_slot(2, 64)
    scratch = make_slot(99, 4096)
    # many messages all landing on the same destination range: heavily
    # round-coloured (rewrite-tempting) but arbitration-ordered
    msgs = tuple(Msg(s, 0, A, s * 4, B, 0, 4) for s in range(1, p))
    steps = [ProgramStep(msgs, LPF_SYNC_DEFAULT, "hot1"),
             ProgramStep(msgs, LPF_SYNC_DEFAULT, "hot2")]
    prog = optimize_program(steps, p, MACHINE, scratch=scratch)
    assert prog.n_rewritten == 0
    assert all(s.attrs.method != "valiant" for s in prog.steps)


def test_peephole_program_materializes_in_recorded_order():
    """A ``search=False`` program assigns ranks and canonical slot
    indices in RECORDED order; ``materialize`` must resolve them the
    same way even when the trace's canonical order differs (regression:
    ranks used to be resolved through canonical_order unconditionally,
    rebinding the wrong slots/labels)."""
    p = 4
    A, B, C = make_slot(1, 16), make_slot(2, 16), make_slot(3, 24)
    # canonical order sorts the reduce step differently than recorded,
    # and the distinct source slots make the two slot maps differ
    s_zz = ProgramStep((Msg(0, 1, A, 0, B, 0, 4),),
                       SyncAttributes(reduce_op="sum"), "zz")
    s_aa = ProgramStep((Msg(2, 3, C, 8, B, 8, 4),), LPF_SYNC_DEFAULT,
                       "aa")
    steps = [s_zz, s_aa]
    assert canonical_order(steps) == [1, 0]      # the interesting case
    prog = optimize_program(steps, p, MACHINE, search=False)
    assert not prog.canonical
    values = initial_values([A, B, C], p, 9)
    eager = simulate_program([(s.msgs, s.attrs) for s in steps], values)
    tables = [(m, a) for m, a, _, _ in prog.materialize(steps)]
    opt = simulate_program(tables, values)
    for sid in eager:
        assert (eager[sid] == opt[sid]).all(), sid
    # the pre-computed slot-list path must honour the program's order
    # too: prog.slot_map uses recorded order for peephole programs
    tables2 = [(m, a) for m, a, _, _
               in prog.materialize(prog.slot_map(steps))]
    opt2 = simulate_program(tables2, values)
    for sid in eager:
        assert (eager[sid] == opt2[sid]).all(), sid
    # labels resolve against recorded positions too
    ents = prog.materialize(steps, labels=["zz", "aa"])
    assert sorted(e[2] for e in ents) == ["aa", "zz"]


def test_explain_renders_schedule():
    from benchmarks.schedule_search import DCN, canned_bucketed_trace
    p, _, steps, _ = canned_bucketed_trace(p=4, n_buckets=2)
    prog = optimize_program(steps, p, DCN)
    text = prog.explain(DCN)
    assert "issue groups" in text
    assert "non-adjacent hoists" in text
    assert "b0.rs || b1.rs" in text
    assert "in-order BSP time" in text and "x)" in text
    # without a machine the rendering still works (no cost comparison)
    assert "in-order BSP time" not in prog.explain()


# ---------------------------------------------------------------------------
# XLA: searched schedules on a real mesh
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_valiant_rewrite_executes_on_mesh(mesh8):
    """A recorded fragmented WAR-coupled trace must (a) take the
    Valiant attr rewrite at flush time, (b) lower and execute through
    the two-phase routing, producing values bit-identical to eager
    per-superstep sync, and (c) ledger the rewritten method."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import core as lpf
    from repro.core import compat

    boxes = {}

    def run(recorded):
        def wrapped(_):
            ctx = lpf.LPFContext(("x",))
            boxes[recorded] = ctx
            p = ctx.p
            ctx.resize_message_queue(40, valiant_payload=1024,
                                     payload_dtype=jnp.int32)
            ctx.resize_memory_register(12)   # + the valiant scratch
            A = [ctx.register_global(
                f"A{i}", (jnp.arange(32) + 100 * ctx.pid + i).astype(
                    jnp.int32)) for i in range(4)]
            B = [ctx.register_global(f"B{i}", jnp.zeros(32, jnp.int32))
                 for i in range(4)]
            C = [ctx.register_global(
                f"C{i}", (jnp.arange(32) * 2 - ctx.pid + i).astype(
                    jnp.int32)) for i in range(4)]
            msgs1, msgs2 = [], []
            for ai in range(4):
                for bi in range(4):
                    k = 4 * ai + bi
                    src = (k * 3) % p
                    msgs1.append((src, (k * 5 + 1) % p, A[ai], 8 * bi,
                                  B[bi], (k * 3) % 16, 4))
                    msgs2.append(((k * 7 + 2) % p, src, C[bi], 8 * ai,
                                  A[ai], 8 * bi, 4))

            def steps():
                ctx.put_msgs(msgs1)
                ctx.sync(label="frag1")
                ctx.put_msgs(msgs2)
                ctx.sync(label="frag2")

            if recorded:
                with ctx.program():
                    steps()
            else:
                steps()
            return tuple(ctx.value(s) for s in A + B)

        fn = jax.jit(compat.shard_map(
            wrapped, mesh=mesh8, in_specs=(P(),),
            out_specs=tuple(P("x") for _ in range(8)), check_vma=False))
        return [np.asarray(v) for v in fn(jnp.zeros(1))]

    eager = run(False)
    searched = run(True)
    for e, s in zip(eager, searched):
        np.testing.assert_array_equal(e, s)
    prog = boxes[True].last_program
    assert prog.n_rewritten >= 1
    records = boxes[True].ledger.records
    assert any(r.method == "valiant" for r in records), records
    # every ledger entry equals its plan's cost (label aside)
    import dataclasses
    for rec, st in zip(records, prog.steps):
        assert dataclasses.replace(st.plan.cost, label=rec.label) == rec


@pytest.mark.slow
def test_reordered_recordings_share_cache_on_mesh(mesh8):
    """Recording the same two independent shifts in either order must
    hit one ProgramCache entry on the real ``ctx.program()`` path (the
    canonical signature is reorder-invariant), with correct values and
    labels either way."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro import core as lpf
    from repro.core import compat

    plan_cache = lpf.PlanCache()
    program_cache = lpf.ProgramCache()
    boxes = {}

    def spmd(ctx, swap):
        p = ctx.p
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(2 * p)
        a = ctx.register_global("a", jnp.arange(4.0) + ctx.pid)
        b = ctx.register_global("b", jnp.zeros(8))

        def shift1():
            ctx.put(a, b, to=lambda s: (s + 1) % p, size=4)
            ctx.sync(label="shift1")

        def shift2():
            ctx.put(a, b, to=lambda s: (s + 2) % p, dst_off=4, size=4)
            ctx.sync(label="shift2")

        with ctx.program():
            if swap:
                shift2()
                shift1()
            else:
                shift1()
                shift2()
        return ctx.value(b)

    for swap in (False, True):
        def wrapped(_, swap=swap):
            ctx = lpf.LPFContext(("x",), plan_cache=plan_cache,
                                 program_cache=program_cache)
            boxes[swap] = ctx
            return spmd(ctx, swap)

        fn = jax.jit(compat.shard_map(wrapped, mesh=mesh8,
                                      in_specs=(P(),), out_specs=P("x"),
                                      check_vma=False))
        out = np.asarray(fn(jnp.zeros(1))).reshape(8, 8)
        for d in range(8):
            np.testing.assert_allclose(out[d, :4],
                                       np.arange(4.0) + (d - 1) % 8)
            np.testing.assert_allclose(out[d, 4:],
                                       np.arange(4.0) + (d - 2) % 8)
    # the swapped recording replays the cached program of the first
    assert program_cache.stats.misses == 1
    assert program_cache.stats.hits == 1
    assert boxes[False].last_program is boxes[True].last_program


# ---------------------------------------------------------------------------
# fast-tier guard: canned traces must not regress past their bounds
# ---------------------------------------------------------------------------

def test_canned_trace_costs_within_guard_bounds():
    """The canned benchmark traces' searched DCN-model costs are the
    PR's enforceable perf claim: fail when any optimized predicted cost
    regresses past its recorded bound, stops beating the peephole, or
    stops finding a non-adjacent/rewrite opportunity."""
    from benchmarks.schedule_search import (CANNED, DCN, GUARD_BOUNDS_US,
                                            run_canned)
    for name in CANNED:
        searched, peephole, _, _ = run_canned(name)
        s_us = searched.predicted_seconds(DCN) * 1e6
        assert s_us <= GUARD_BOUNDS_US[name], \
            f"{name}: {s_us:.1f}us > guard {GUARD_BOUNDS_US[name]}us"
        assert s_us < peephole.predicted_seconds(DCN) * 1e6, name
        assert searched.n_hoisted + searched.n_rewritten >= 1, name


# ---------------------------------------------------------------------------
# canonical-order tie-break: bit-identical symmetric steps
# ---------------------------------------------------------------------------

def test_canonical_tie_break_is_structural():
    """Two content-identical steps (same msg tuples over fresh slots of
    the same shape) tie on the content key; the winner must be chosen
    structurally (conflict-DAG + slot-sharing refinement), not by
    recorded position — otherwise the two interleavings below number
    the canonical slots differently and one program costs two
    ProgramCache entries."""
    p = 4
    a1, b1 = make_slot(201, 16), make_slot(202, 16)
    a2, b2 = make_slot(203, 16), make_slot(204, 16)
    cs = make_slot(205, 8)
    attrs = SyncAttributes()

    def shift(src, dst):
        return ProgramStep(tuple(Msg(s, (s + 1) % p, src, 0, dst, 0, 16)
                                 for s in range(p)), attrs, "shift")

    A = shift(a1, b1)                 # bit-identical content to B ...
    B = shift(a2, b2)
    # ... but C reads A's output — the conflict edge distinguishes them
    C = ProgramStep(tuple(Msg(s, (s + 1) % p, b1, 0, cs, 0, 8)
                          for s in range(p)), attrs, "use")
    rec1 = [A, B, C]
    rec2 = [B, A, C]                  # a legal reordering (A,B commute)

    ca = canonical_order(rec1)
    cb = canonical_order(rec2)
    assert [rec1[i].label for i in ca] == [rec2[i].label for i in cb]
    assert program_signature(rec1, p) == program_signature(rec2, p)

    cache = ProgramCache()
    p1 = cache.get_or_build(rec1, p, MACHINE)
    p2 = cache.get_or_build(rec2, p, MACHINE)
    assert p1 is p2
    assert cache.stats.misses == 1 and cache.stats.hits == 1

    # the shared program still executes both recordings correctly
    slots = [a1, b1, a2, b2, cs]
    values = initial_values(slots, p, 7)
    eager = simulate_program([(s.msgs, s.attrs) for s in rec1], values)
    tables = [(m, a) for m, a, _, _ in p2.materialize(rec2)]
    opt = simulate_program(tables, values)
    for sid in eager:
        assert (eager[sid] == opt[sid]).all(), sid


def test_program_cache_lru_semantics():
    """Hits refresh recency (move_to_end) and eviction counts match:
    insert maxsize+2 distinct programs while touching the first — the
    hot entry survives, the two coldest leave, and each certificate
    leaves with its program."""
    cache = ProgramCache(maxsize=4)
    progs = []
    for k in range(6):
        # distinct signatures: one step shifting a distinctly-sized slot
        src = make_slot(300 + 2 * k, 8 + k)
        dst = make_slot(301 + 2 * k, 8 + k)
        steps = [ProgramStep(
            (Msg(0, 1, src, 0, dst, 0, 8 + k),), SyncAttributes(), "s")]
        if k in (2, 4):
            # touch the hot entry (k=0) between inserts — at k=4 the
            # cache is full and k=0 is oldest; without move_to_end the
            # next two inserts would evict it
            assert cache.get_or_build(progs[0], 4, MACHINE) is not None
        prog, key = cache.get_or_build_keyed(steps, 4, MACHINE)
        assert cache.certify(key, steps).ok
        progs.append(steps)
    assert cache.stats.evictions == 2
    # the hot entry survived 6 inserts into maxsize=4 ...
    before = cache.stats.misses
    cache.get_or_build(progs[0], 4, MACHINE)
    assert cache.stats.misses == before          # hit, not rebuild
    # ... and the evicted programs took their verifier certificates along
    assert len(cache._programs) == 4
    assert len(cache._certs) == 4
