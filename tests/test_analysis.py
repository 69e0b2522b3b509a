"""The static analyzer: race linter (LPF001–006), schedule verifier
(LPF101–107), sanitizer mode, and the certificate-gated program cache.

Four layers:

1. every linter code has a firing and a non-firing case;
2. the verifier accepts every schedule the real optimizer emits — a
   300-seed sweep over random and structured traces, both search modes,
   with and without scratch (zero false positives) — and rejects a
   hand-built negative fixture per LPF101–107;
3. sanitizer mode: ``LPFContext(sanitize=True)`` (or ``LPF_SANITIZE=1``)
   raises :class:`LPFAnalysisError` on error diagnostics before any
   communication and accumulates warnings on ``ctx.diagnostics``; slot
   generations catch stale handles after deregister-then-reuse;
4. a recorded program executes only under a passing certificate
   (memoized per ``ProgramCache`` key), and ``explain`` renders the
   certificate summary.
"""

import dataclasses
import pickle

import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import (CANNED_TRACES, canned_bucketed_trace,
                            canned_fft_trace, canned_fragmented_trace,
                            lint_program, lint_trace, verify_program)
from repro.analysis.__main__ import main as analysis_main
from repro.core import (LPF_SYNC_DEFAULT, LPFAnalysisError, LPFContext,
                        LPFFatalError, Msg, OptimizedStep, ProgramCache,
                        ProgramStep, Slot, SlotRegistry, SuperstepProgram,
                        SyncAttributes, optimize_program, plan_sync,
                        trace_slot_map)
from repro.core.machine import CPU_HOST, TPU_V5E, probe

pytestmark = pytest.mark.fast

MACHINE = probe({"x": 8}, CPU_HOST)


def make_slot(sid, size, dtype="int32", kind="global"):
    return Slot(sid=sid, name=f"s{sid}", size=size, dtype=np.dtype(dtype),
                kind=kind, orig_shape=(size,))


A, B, C, D = (make_slot(100 + i, 16) for i in range(4))


def step(msgs, attrs=LPF_SYNC_DEFAULT, label="s"):
    return ProgramStep(tuple(msgs), attrs, label)


def codes(diags):
    return {d.code for d in diags}


# ---------------------------------------------------------------------------
# (1) the linter: one firing + one non-firing case per code
# ---------------------------------------------------------------------------

def test_lpf001_no_conflict_race():
    racy = step([Msg(0, 1, A, 0, B, 0, 4), Msg(0, 1, A, 4, B, 2, 4)],
                SyncAttributes(no_conflict=True))
    assert "LPF001" in codes(lint_trace([racy], 2))
    # same table without the assertion: CRCW arbitration is defined
    assert "LPF001" not in codes(lint_trace(
        [step(racy.msgs)], 2))
    # reduce tables combine overlapping writes by construction
    assert "LPF001" not in codes(lint_trace(
        [step(racy.msgs, SyncAttributes(no_conflict=True,
                                        reduce_op="sum"))], 2))


def test_lpf002_read_of_undefined_region():
    trace = [step([Msg(0, 1, B, 0, C, 0, 4)])]          # reads B undefined
    assert "LPF002" in codes(lint_trace(trace, 2, undefined=[B.sid]))
    defined_first = [step([Msg(1, 0, A, 0, B, 0, 8)]),  # writes B[0:8) @0
                     step([Msg(0, 1, B, 0, C, 0, 4)])]
    assert "LPF002" not in codes(
        lint_trace(defined_first, 2, undefined=[B.sid]))
    # a partial write does not define the whole read range
    partial = [step([Msg(1, 0, A, 0, B, 0, 2)]),
               step([Msg(0, 1, B, 0, C, 0, 4)])]
    assert "LPF002" in codes(lint_trace(partial, 2, undefined=[B.sid]))


def test_lpf003_use_after_deregister_and_leak():
    trace = [step([Msg(0, 1, A, 0, B, 0, 4)]),
             step([Msg(0, 1, A, 0, B, 4, 4)])]
    fired = lint_trace(trace, 2, events=[(1, "deregister", A.sid)])
    assert any(d.code == "LPF003" and d.severity == "error" and d.step == 1
               for d in fired)
    # deregistered only after the last step: clean
    after = lint_trace(trace, 2, events=[(2, "deregister", A.sid)])
    assert not any(d.code == "LPF003" and d.severity == "error"
                   for d in after)
    # registered during the trace, never deregistered: a leak warning
    leak = lint_trace(trace, 2, events=[(0, "register", A.sid)])
    assert any(d.code == "LPF003" and d.severity == "warning"
               for d in leak)


def test_lpf004_out_of_bounds_extents():
    oob = [step([Msg(0, 1, A, 12, B, 0, 8)])]       # src [12,20) > 16
    assert "LPF004" in codes(lint_trace(oob, 2))
    assert "LPF004" in codes(lint_trace(
        [step([Msg(0, 1, A, 0, B, 10, 8)])], 2))    # dst [10,18) > 16
    assert "LPF004" in codes(lint_trace(
        [step([Msg(0, 5, A, 0, B, 0, 4)])], 2))     # pid out of range
    local = make_slot(500, 16, kind="local")
    assert "LPF004" in codes(lint_trace(
        [step([Msg(0, 1, A, 0, local, 0, 4)])], 2))  # remote local slot
    assert "LPF004" not in codes(lint_trace(
        [step([Msg(0, 1, A, 0, B, 8, 8)])], 2))     # exactly in bounds


def test_lpf005_aliasing_self_message():
    alias = [step([Msg(1, 1, A, 0, A, 2, 8)])]      # shifted overlap
    assert "LPF005" in codes(lint_trace(alias, 2))
    assert "LPF005" not in codes(lint_trace(
        [step([Msg(1, 1, A, 0, A, 8, 8)])], 2))     # disjoint move
    assert "LPF005" not in codes(lint_trace(
        [step([Msg(1, 1, A, 0, B, 2, 8)])], 2))     # different slot


def test_lpf006_dead_transfer_in_trace():
    dead = [step([Msg(0, 1, A, 0, B, 0, 8)], label="dead"),
            step([Msg(0, 1, C, 0, B, 0, 8)], label="clobber")]
    assert "LPF006" in codes(lint_trace(dead, 2))
    read_between = [dead[0],
                    step([Msg(1, 0, B, 0, C, 0, 4)]),   # observes B
                    dead[1]]
    assert "LPF006" not in codes(lint_trace(read_between, 2))


def test_lpf006_dead_transfer_surviving_optimization():
    # the union-of-two-writes overwrite is invisible to the optimizer's
    # single-message eliminator (and the halves cannot coalesce: their
    # src->dst shifts differ), so the dead transfer survives into the
    # schedule and lint_program reports it
    trace = [step([Msg(0, 1, A, 0, B, 0, 8)], label="dead"),
             step([Msg(0, 1, A, 8, B, 0, 4), Msg(0, 1, A, 0, B, 4, 4)],
                  label="clobber2")]
    prog = optimize_program(trace, 2, MACHINE)
    assert prog.n_eliminated == 0
    assert "LPF006" in codes(lint_program(prog, trace))
    assert verify_program(trace, prog).ok
    # the single-message overwrite IS eliminated -> nothing survives,
    # and the verifier accepts the drop (provably dead: LPF107 clean)
    trace2 = [step([Msg(0, 1, A, 0, B, 0, 8)], label="dead"),
              step([Msg(0, 1, C, 0, B, 0, 8)], label="clobber")]
    prog2 = optimize_program(trace2, 2, MACHINE)
    assert prog2.n_eliminated == 1
    assert "LPF006" not in codes(lint_program(prog2, trace2))
    assert verify_program(trace2, prog2).ok


# ---------------------------------------------------------------------------
# (2a) the verifier accepts everything the real optimizer emits
# ---------------------------------------------------------------------------

def _sweep_trace(seed):
    """Random or structured (merge/overlap/valiant-shaped) trace."""
    rng = np.random.default_rng(seed)
    pattern = seed % 4
    if pattern == 1:
        return canned_bucketed_trace(p=int(rng.choice([4, 8])),
                                     n_buckets=int(rng.integers(1, 4)),
                                     w=int(rng.integers(4, 17)))
    if pattern == 2:
        return canned_fft_trace(p=int(rng.choice([2, 4, 8])),
                                w=int(rng.integers(4, 17)))
    if pattern == 3:
        return canned_fragmented_trace(p=int(rng.choice([4, 8])))
    p = int(rng.integers(2, 9))
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
    scratch = make_slot(999, 4096) if seed % 3 == 0 else None
    return p, slots, steps, scratch


def test_verifier_accepts_every_searched_schedule():
    """300 seeds x {search, peephole}: zero false positives."""
    for seed in range(300):
        p, _slots, steps, scratch = _sweep_trace(seed)
        hw = TPU_V5E if seed % 5 == 0 else CPU_HOST
        machine = probe({"x": p}, hw)
        for search in (True, False):
            prog = optimize_program(steps, p, machine, scratch=scratch,
                                    search=search)
            rep = verify_program(steps, prog, scratch=scratch)
            assert rep.ok, (
                f"false positive at seed={seed} search={search}: "
                + "; ".join(str(d) for d in rep.diagnostics))


def test_verifier_accepts_canned_traces_on_dcn():
    dcn = probe({"pod": 8}, TPU_V5E)
    for name, build in CANNED_TRACES.items():
        p, _slots, steps, scratch = build()
        prog = optimize_program(steps, p, dcn, scratch=scratch)
        rep = verify_program(steps, prog, scratch=scratch)
        assert rep.ok, (name, rep.diagnostics)
        assert rep.summary().startswith("verified:")


# ---------------------------------------------------------------------------
# (2b) one hand-built negative fixture per verifier code
# ---------------------------------------------------------------------------

def _canon(msgs, sidx):
    return tuple((m.src, m.dst, sidx[m.src_slot.sid], m.src_off,
                  sidx[m.dst_slot.sid], m.dst_off, m.size, m.origin)
                 for m in msgs)


def _build_program(steps, p, partition, overlap_groups=(),
                   plan_scratch=None, rewrites=None):
    """Hand-assemble a recorded-order (``canonical=False``) program
    scheduling ``steps`` per ``partition`` — a list of merged_from
    tuples in emission order.  Bypasses the optimizer so tests can
    construct *illegal* schedules the optimizer would never emit."""
    order = list(range(len(steps)))
    smap = trace_slot_map(steps, order)
    sidx = {s.sid: i for i, s in enumerate(smap)}
    opt = []
    for gi, ranks in enumerate(partition):
        msgs = [m for r in ranks for m in steps[r].msgs]
        attrs = steps[ranks[0]].attrs
        rw = (rewrites or {}).get(gi, "")
        if rw == "valiant":
            attrs = dataclasses.replace(attrs, method="valiant")
        plan = plan_sync(msgs, p, attrs, plan_scratch)
        opt.append(OptimizedStep(
            _canon(msgs, sidx), attrs,
            "+".join(steps[r].label for r in ranks), plan,
            tuple(ranks), rewrite=rw))
    return SuperstepProgram(
        p=p, steps=tuple(opt), n_recorded=len(steps), n_coalesced=0,
        n_eliminated=0, n_merged=0, overlap_groups=tuple(overlap_groups),
        canonical=False)


W = step([Msg(0, 1, A, 0, B, 0, 4)], label="w")     # writes B on pid 1
R = step([Msg(1, 0, B, 0, C, 0, 4)], label="r")     # reads it (RAW)


def _verify(prog, steps=(W, R), scratch=None):
    return verify_program(list(steps), prog, scratch=scratch)


def test_handbuilt_legal_schedule_verifies():
    assert _verify(_build_program([W, R], 2, [(0,), (1,)])).ok


def test_lpf101_broken_partition():
    good = _build_program([W, R], 2, [(0,), (1,)])
    rep = _verify(dataclasses.replace(good, n_recorded=3))
    assert not rep.ok and "LPF101" in codes(rep.diagnostics)
    dup = dataclasses.replace(
        good, steps=(dataclasses.replace(good.steps[0],
                                         merged_from=(0, 0)),
                     good.steps[1]))
    rep = _verify(dup)
    assert not rep.ok and "LPF101" in codes(rep.diagnostics)


def test_lpf102_conflicting_steps_reordered():
    rep = _verify(_build_program([W, R], 2, [(1,), (0,)]))
    assert not rep.ok and "LPF102" in codes(rep.diagnostics)


def test_lpf103_raw_pair_merged():
    rep = _verify(_build_program([W, R], 2, [(0, 1)]))
    assert not rep.ok and "LPF103" in codes(rep.diagnostics)


def test_lpf103_waw_pair_merged():
    w2 = step([Msg(0, 1, C, 0, B, 2, 4)], label="w2")   # overlaps W's dst
    rep = verify_program([W, w2], _build_program([W, w2], 2, [(0, 1)]))
    assert not rep.ok and "LPF103" in codes(rep.diagnostics)


def test_lpf104_conflicting_overlap_group():
    rep = _verify(_build_program([W, R], 2, [(0,), (1,)],
                                 overlap_groups=((0, 1),)))
    assert not rep.ok and "LPF104" in codes(rep.diagnostics)


def test_lpf105_bogus_valiant_rewrite():
    # a declared valiant rewrite with no scratch slot to route through
    scratch = make_slot(999, 4096)
    prog = _build_program([W], 2, [(0,)], plan_scratch=scratch,
                          rewrites={0: "valiant"})
    rep = verify_program([W], prog, scratch=None)
    assert not rep.ok and "LPF105" in codes(rep.diagnostics)
    # an unknown rewrite tag is never certified
    good = _build_program([W, R], 2, [(0,), (1,)])
    bad = dataclasses.replace(
        good, steps=(dataclasses.replace(good.steps[0], rewrite="wat"),
                     good.steps[1]))
    rep = _verify(bad)
    assert not rep.ok and "LPF105" in codes(rep.diagnostics)


def test_lpf106_tampered_plan_cost():
    good = _build_program([W, R], 2, [(0,), (1,)])
    st0 = good.steps[0]
    cost = st0.plan.cost
    tampered = dataclasses.replace(
        good, steps=(dataclasses.replace(
            st0, plan=dataclasses.replace(
                st0.plan, cost=dataclasses.replace(
                    cost, wire_bytes=cost.wire_bytes + 64))),
            good.steps[1]))
    rep = _verify(tampered)
    assert not rep.ok and "LPF106" in codes(rep.diagnostics)


def test_lpf107_live_transfer_dropped():
    good = _build_program([W, R], 2, [(0,), (1,)])
    dropped = dataclasses.replace(
        good, steps=(dataclasses.replace(good.steps[0], table=()),
                     good.steps[1]))
    rep = _verify(dropped)
    assert not rep.ok and "LPF107" in codes(rep.diagnostics)


def test_lpf107_fabricated_transfer():
    good = _build_program([W, R], 2, [(0,), (1,)])
    smap = trace_slot_map([W, R], [0, 1])
    sidx = {s.sid: i for i, s in enumerate(smap)}
    extra = _canon([Msg(0, 1, A, 8, B, 8, 4)], sidx)    # never recorded
    fat = dataclasses.replace(
        good, steps=(dataclasses.replace(
            good.steps[0], table=good.steps[0].table + extra),
            good.steps[1]))
    rep = _verify(fat)
    assert not rep.ok and "LPF107" in codes(rep.diagnostics)


# ---------------------------------------------------------------------------
# (3) slot generations + sanitizer mode
# ---------------------------------------------------------------------------

def test_stale_handle_after_sid_reuse_is_fatal():
    reg = SlotRegistry(capacity=2)
    a = reg.register("a", jnp.zeros(4, jnp.int32), "global")
    reg.deregister(a)
    b = reg.register("b", jnp.zeros(8, jnp.int32), "global")
    assert b.sid == a.sid and b.gen != a.gen     # sid reused, new epoch
    with pytest.raises(LPFFatalError, match="stale"):
        reg.value(a)
    with pytest.raises(LPFFatalError, match="stale"):
        reg.deregister(a)
    assert not reg.is_registered(a)
    assert reg.is_registered(b)
    assert int(reg.value(b).shape[0]) == 8


def _eager_ctx(sanitize=None):
    ctx = LPFContext((), sanitize=sanitize)      # p = 1, no mesh needed
    ctx.resize_memory_register(4)
    ctx.resize_message_queue(16)
    return ctx


def test_put_validates_extents_at_stage_time():
    ctx = _eager_ctx()
    a = ctx.register_global("a", jnp.arange(8, dtype=jnp.int32))
    b = ctx.register_global("b", jnp.arange(4, dtype=jnp.int32))
    with pytest.raises(LPFFatalError, match="OOB"):
        ctx.put(a, b, to=0, size=8)              # dst extent 8 > 4
    assert not ctx._queue                        # nothing staged
    with ctx.program("rec"):                     # also under recording
        with pytest.raises(LPFFatalError, match="OOB"):
            ctx.put(a, b, to=0, src_off=6, size=4)


def test_sanitize_stale_handle_raises_at_put():
    ctx = _eager_ctx(sanitize=True)
    a = ctx.register_global("a", jnp.zeros(8, jnp.int32))
    ctx.deregister(a)
    c = ctx.register_global("c", jnp.zeros(8, jnp.int32))
    assert c.sid == a.sid and c.gen != a.gen
    with pytest.raises(LPFAnalysisError, match="LPF003"):
        ctx.put_msgs([(0, 0, c, 0, a, 0, 4)])    # stale dst handle
    assert not ctx._queue


def test_stale_handle_without_sanitize_still_fatal_at_sync():
    ctx = _eager_ctx(sanitize=False)
    a = ctx.register_global("a", jnp.zeros(8, jnp.int32))
    ctx.deregister(a)
    c = ctx.register_global("c", jnp.zeros(8, jnp.int32))
    ctx.put_msgs([(0, 0, c, 0, a, 0, 4)])
    with pytest.raises(LPFFatalError, match="stale"):
        ctx.sync()


def test_sanitize_no_conflict_race_raises_before_execution():
    ctx = _eager_ctx(sanitize=True)
    a = ctx.register_global("a", jnp.arange(8, dtype=jnp.int32))
    b = ctx.register_global("b", jnp.zeros(8, jnp.int32))
    ctx.put_msgs([(0, 0, a, 0, b, 0, 4), (0, 0, a, 4, b, 2, 4)])
    before = jnp.asarray(ctx.registry.value(b))
    with pytest.raises(LPFAnalysisError, match="LPF001"):
        ctx.sync(SyncAttributes(no_conflict=True))
    assert (np.asarray(ctx.registry.value(b)) ==
            np.asarray(before)).all()            # raised before execution


def test_sanitize_warnings_accumulate_on_diagnostics():
    ctx = _eager_ctx(sanitize=True)
    a = ctx.register_global("a", jnp.arange(8, dtype=jnp.int32))
    ctx.put_msgs([(0, 0, a, 0, a, 2, 4)])        # aliasing self-copy
    ctx.sync()
    assert any(d.code == "LPF005" for d in ctx.diagnostics)


def test_sanitize_recorded_trace_and_leak_warning():
    ctx = _eager_ctx(sanitize=True)
    a = ctx.register_global("a", jnp.arange(8, dtype=jnp.int32))
    with ctx.program("loop"):
        b = ctx.register_global("b", jnp.zeros(8, jnp.int32))
        ctx.put_msgs([(0, 0, a, 0, b, 0, 8)])
        ctx.sync()
    # b was registered inside the recording and never deregistered
    assert any(d.code == "LPF003" and d.severity == "warning"
               for d in ctx.diagnostics)


def test_sanitize_env_default(monkeypatch):
    monkeypatch.setenv("LPF_SANITIZE", "1")
    assert LPFContext(()).sanitize
    monkeypatch.setenv("LPF_SANITIZE", "0")
    assert not LPFContext(()).sanitize
    assert LPFContext((), sanitize=True).sanitize    # explicit overrides


# ---------------------------------------------------------------------------
# (4) certificate-gated program cache + explain
# ---------------------------------------------------------------------------

def test_set_compiled_requires_passing_certificate():
    """A recorded program executes only under a passing certificate:
    the first flush certifies it, a replay reuses the memoized
    certificate, and a failed one refuses execution with
    :class:`LPFAnalysisError` before any slot is written."""
    cache = ProgramCache(maxsize=4)
    ctx = LPFContext((), program_cache=cache)
    ctx.resize_memory_register(2)
    ctx.resize_message_queue(4)
    a = ctx.register_global("a", jnp.arange(8, dtype=jnp.int32))
    b = ctx.register_global("b", jnp.zeros(8, jnp.int32))

    def copy(shift):
        ctx.write(a, jnp.arange(8, dtype=jnp.int32) + shift)
        with ctx.program("copy"):
            ctx.put_msgs([(0, 0, a, 0, b, 0, 8)])
            ctx.sync()
        return np.asarray(ctx.registry.value(b))

    assert (copy(0) == np.arange(8)).all()
    (key,) = cache.keys()
    cert = cache.certificate(key)
    assert cert is not None and cert.ok
    assert (copy(100) == np.arange(8) + 100).all()
    assert cache.certificate(key) is cert            # memoized
    assert cache.stats.hits == 1
    cache._certs[key] = dataclasses.replace(cert, ok=False)
    with pytest.raises(LPFAnalysisError, match="verification failed"):
        copy(200)
    assert (np.asarray(ctx.registry.value(b)) == np.arange(8) + 100).all()
    cache.clear()
    assert not cache._certs


def test_explain_renders_certificate_summary():
    p, _slots, steps, scratch = canned_fft_trace(4, 8)
    prog = optimize_program(steps, p, MACHINE, scratch=scratch)
    txt = prog.explain(MACHINE, steps=steps, scratch=scratch)
    assert "verified:" in txt and "0 diagnostics" in txt
    # certify() attaches the certificate for later explain() calls
    cache = ProgramCache()
    prog2, key = cache.get_or_build_keyed(steps, p, MACHINE,
                                          scratch=scratch)
    cache.certify(key, steps, scratch=scratch)
    assert "verified:" in prog2.explain()


# ---------------------------------------------------------------------------
# (5) the CLI
# ---------------------------------------------------------------------------

def test_cli_canned_traces_exit_zero(capsys):
    assert analysis_main(["fft_redistribute", "pagerank"]) == 0
    out = capsys.readouterr().out
    assert "verified:" in out and "fft_redistribute" in out


def test_cli_pickled_racy_trace_exits_nonzero(tmp_path, capsys):
    racy = [step([Msg(0, 1, A, 0, B, 0, 4), Msg(0, 1, A, 4, B, 2, 4)],
                 SyncAttributes(no_conflict=True), label="racy")]
    path = tmp_path / "racy.pkl"
    with open(path, "wb") as fh:
        pickle.dump((2, racy), fh)
    assert analysis_main(["--pickle", str(path)]) == 1
    assert "LPF001" in capsys.readouterr().out
