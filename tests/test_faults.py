"""Fault-tolerant execution: deterministic injection, the graceful
degradation ladder, and recovery supervision.

Three layers under test:
  * the injection machinery itself — plan grammar roundtrip, seeded
    determinism, one counting point per seam, zero-fault transparency;
  * the degradation ladder — persist I/O retry -> disk_errors ->
    memory-only mode, the in-memory poison set for undeletable corrupt
    entries, and with_capacity's resize-and-retry;
  * the supervisor — transient errors absorbed via checkpoint-restore
    with a bounded restart budget, fatal errors propagated unchanged.
"""

import dataclasses
import errno
import os

import numpy as np
import pytest

pytestmark = pytest.mark.fast

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.core import (LPF_SYNC_DEFAULT, LPFCapacityError,  # noqa: E402
                        LPFError, LPFFatalError, LPFTransientError,
                        InjectedFault, LPFMachine, Msg, ProgramCache,
                        ProgramStep, Slot, classify)
from repro.core import faultpoints  # noqa: E402
from repro.core.persist import entry_filename  # noqa: E402
from repro.runtime import faults  # noqa: E402
from repro.runtime.faults import (FaultEvent, FaultPlan,  # noqa: E402
                                  FaultInjector, SMOKE_PLANS)
from repro.runtime.train_loop import (Anomaly, StepSupervisor,  # noqa: E402
                                      TrainLoopConfig, train_loop)

P = 4
MACHINE = LPFMachine(p=P, g=1e-9, l=1e-6, r=1e-10)


def make_slot(sid, size=16):
    return Slot(sid=sid, name=f"s{sid}", size=size,
                dtype=np.dtype("float32"), kind="global",
                orig_shape=(size,))


def shift_trace(n_steps=3, base_sid=0):
    steps = []
    for k in range(n_steps):
        a = make_slot(base_sid + 2 * k)
        b = make_slot(base_sid + 2 * k + 1)
        msgs = tuple(Msg(s, (s + k + 1) % P, a, 0, b, 0, 4 * (k + 1),
                         origin="put") for s in range(P))
        steps.append(ProgramStep(msgs, LPF_SYNC_DEFAULT, f"s{k}"))
    return steps


def build_and_certify(cache, steps=None, base_sid=0):
    steps = steps if steps is not None else shift_trace(base_sid=base_sid)
    prog, key = cache.get_or_build_keyed(steps, P, MACHINE)
    cert = cache.certify(key, steps, prog)
    assert cert.ok
    return prog, key, steps


# ---------------------------------------------------------------------------
# the error taxonomy
# ---------------------------------------------------------------------------

def test_classify_taxonomy():
    assert classify(LPFCapacityError("full")) == "mitigable"
    assert classify(LPFTransientError("blip")) == "transient"
    assert classify(LPFFatalError("broken")) == "fatal"
    assert classify(OSError(errno.EIO, "io")) == "transient"
    assert classify(TimeoutError()) == "transient"
    assert classify(InjectedFault("boom")) == "transient"
    # anything unclassified is fatal — never silently retried
    assert classify(ValueError("?")) == "fatal"
    assert classify(KeyboardInterrupt()) == "fatal"


def test_capacity_error_structured_fields():
    e = LPFCapacityError("full", required=12, capacity=4, kind="queue")
    assert (e.required, e.capacity, e.kind) == (12, 4, "queue")
    assert isinstance(e, LPFError)
    # default-constructed (legacy call sites) stays valid
    e2 = LPFCapacityError("full")
    assert (e2.required, e2.capacity, e2.kind) == (0, 0, "queue")


# ---------------------------------------------------------------------------
# plans: grammar, determinism, arming
# ---------------------------------------------------------------------------

def test_plan_spec_roundtrip():
    spec = ("persist_save@0;persist_load@1x2:bitflip;straggler@0x-1;"
            "straggler@2=0.005;capacity@1x3")
    plan = FaultPlan.parse(spec)
    assert plan.spec() == spec
    assert FaultPlan.parse(plan.spec()).spec() == spec
    assert plan.seams() == ("capacity", "persist_load", "persist_save",
                            "straggler")


@pytest.mark.parametrize("bad", [
    "nosuchseam@0", "persist_save@-1", "persist_save@0x0",
    "persist_save@0:nosuchmode", "straggler", "capacity@", "@0",
    "compile@0",
])
def test_plan_rejects_malformed(bad):
    with pytest.raises(ValueError):
        FaultPlan.parse(bad)


def test_random_plans_are_seed_deterministic():
    seams = ("persist_save", "straggler", "capacity")
    specs = [FaultPlan.random(seed, seams=seams).spec()
             for seed in range(50)]
    again = [FaultPlan.random(seed, seams=seams).spec()
             for seed in range(50)]
    assert specs == again
    assert len(set(specs)) > 10          # the space is actually explored
    for spec in specs:
        for e in FaultPlan.parse(spec).events:
            assert e.seam in seams


def test_event_due_semantics():
    one = FaultEvent(seam="capacity", at=2)
    assert [one.due(i) for i in range(5)] == [False, False, True, False,
                                              False]
    rep = FaultEvent(seam="capacity", at=1, repeat=2)
    assert [rep.due(i) for i in range(5)] == [False, True, True, False,
                                              False]
    forever = FaultEvent(seam="straggler", at=3, repeat=-1)
    assert [forever.due(i) for i in range(6)] == [False] * 3 + [True] * 3


def test_unarmed_seams_are_noops():
    assert faults.active() is None
    faultpoints.fire("persist_save")            # nothing raises
    assert faultpoints.corrupt("persist_load", b"abc") == b"abc"
    assert faultpoints.delay("straggler") == 0.0


def test_inject_restores_previous_injector():
    outer = faults.arm(FaultPlan.parse("capacity@50"))
    try:
        with faults.inject(FaultPlan.parse("capacity@60")) as inner:
            assert faults.active() is inner
        assert faults.active() is outer
    finally:
        faults.disarm()
    assert faults.active() is None


def test_env_plan_arming(monkeypatch):
    monkeypatch.setenv("LPF_FAULT_PLAN", "persist_save@0")
    try:
        inj = faults.ensure_env_plan()
        assert inj is not None
        assert inj.plan.spec() == "persist_save@0"
        # idempotent: a second root context must not reset the counters
        inj.counts["persist_save"] = 5
        assert faults.ensure_env_plan() is inj
    finally:
        faults.disarm()


def test_injector_counts_and_fired_log():
    inj = FaultInjector(FaultPlan.parse("persist_save@1"))
    with pytest.raises(OSError):
        try:
            inj.fire("persist_save")             # idx 0: pass
            inj.fire("persist_save")             # idx 1: ENOSPC
        except OSError as e:
            assert e.errno == errno.ENOSPC
            raise
    assert inj.counts["persist_save"] == 2
    assert inj.fired == [("persist_save", 1, "default")]


# ---------------------------------------------------------------------------
# the persist seams + the disk degradation ladder
# ---------------------------------------------------------------------------

def test_save_fault_is_absorbed_and_counted(tmp_path):
    """An injected ENOSPC during write-back costs the warm start (and
    bumps disk_errors), never the execution."""
    cache = ProgramCache(persist_dir=str(tmp_path))
    with faults.inject(FaultPlan.parse("persist_save@0x-1")) as inj:
        prog, key, steps = build_and_certify(cache)
    assert inj.fired
    assert prog is not None
    assert cache.stats.disk_errors >= 1
    assert not os.path.exists(tmp_path / entry_filename(key))
    # the entry is served from memory regardless
    prog2, _ = cache.get_or_build_keyed(steps, P, MACHINE)
    assert prog2 is prog


def test_persistent_disk_failure_degrades_to_memory_only(tmp_path):
    """DISK_STRIKE_LIMIT *consecutive* failed store operations detach
    the store: later lookups never touch the disk (no retry tax), and
    the reason is recorded.  (A single save failure does NOT detach —
    any successful disk op in between resets the strike counter.)"""
    seed = ProgramCache(persist_dir=str(tmp_path))
    traces = []
    for k in range(ProgramCache.DISK_STRIKE_LIMIT):
        # structurally distinct traces (slot renumbering canonicalizes
        # away a mere sid shift, which would collapse them to one key)
        steps = shift_trace(n_steps=k + 1)
        build_and_certify(seed, steps=steps)
        traces.append(steps)

    warm = ProgramCache(persist_dir=str(tmp_path))
    with faults.inject(FaultPlan.parse("persist_load@0x-1")):
        for steps in traces:      # every entry exists -> every read fails
            prog, _ = warm.get_or_build_keyed(steps, P, MACHINE)
            assert prog is not None              # cold build absorbed it
    assert warm.store is None
    assert warm.memory_only_reason is not None
    assert "consecutive" in warm.memory_only_reason
    assert warm.stats.disk_errors == warm.DISK_STRIKE_LIMIT
    # re-attaching resets the ladder
    warm.attach_store(str(tmp_path))
    assert warm.store is not None
    assert warm.memory_only_reason is None


def test_successful_disk_op_resets_strikes(tmp_path):
    """A working disk clears the consecutive-failure count: alternating
    one save failure per build with successful loads never detaches."""
    cache = ProgramCache(persist_dir=str(tmp_path))
    with faults.inject(FaultPlan.parse("persist_save@0x-1")):
        for k in range(cache.DISK_STRIKE_LIMIT + 1):
            build_and_certify(cache, steps=shift_trace(n_steps=k + 1))
    assert cache.store is not None               # still attached
    assert cache.memory_only_reason is None
    assert cache.stats.disk_errors == cache.DISK_STRIKE_LIMIT + 1


def test_transient_load_error_does_not_invalidate(tmp_path):
    """persist_load:oserror is transient: the warm start degrades to a
    cold miss, but the on-disk entry — which is perfectly fine — must
    survive for the next attempt."""
    seed = ProgramCache(persist_dir=str(tmp_path))
    _, key, steps = build_and_certify(seed)
    path = tmp_path / entry_filename(key)
    assert path.exists()

    warm = ProgramCache(persist_dir=str(tmp_path))
    with faults.inject(FaultPlan.parse("persist_load@0x-1")) as inj:
        prog, _ = warm.get_or_build_keyed(steps, P, MACHINE)
    assert inj.fired
    assert prog is not None                      # cold build succeeded
    assert warm.stats.invalidated == 0
    assert warm.stats.disk_errors >= 1
    assert path.exists()                         # NOT invalidated

    # with the fault gone, a fresh cache warm-starts from that entry
    clean = ProgramCache(persist_dir=str(tmp_path))
    clean.get_or_build_keyed(steps, P, MACHINE)
    assert clean.stats.disk_hits == 1


@pytest.mark.parametrize("mode", ["truncate", "bitflip"])
def test_corrupting_load_fault_invalidates(tmp_path, mode):
    """Corruption (vs transient I/O) is final: the entry is counted
    invalidated, removed, and rebuilt cold."""
    seed = ProgramCache(persist_dir=str(tmp_path))
    _, key, steps = build_and_certify(seed)

    warm = ProgramCache(persist_dir=str(tmp_path))
    with faults.inject(FaultPlan.parse(f"persist_load@0:{mode}")) as inj:
        prog, _ = warm.get_or_build_keyed(steps, P, MACHINE)
    assert inj.fired
    assert prog is not None
    assert warm.stats.invalidated == 1
    assert not (tmp_path / entry_filename(key)).exists()


def test_undeletable_invalid_entry_is_poisoned(tmp_path, monkeypatch):
    """When a corrupt entry cannot be removed (read-only cache dir),
    its filename is poisoned in memory: the decode+verify cost is paid
    once, later misses skip the file without touching the disk."""
    seed = ProgramCache(persist_dir=str(tmp_path))
    _, key, steps = build_and_certify(seed)
    fname = entry_filename(key)
    # corrupt the payload on disk (checksum now fails)
    path = tmp_path / fname
    blob = path.read_bytes()
    path.write_bytes(blob[:-4] + b"XXXX")

    warm = ProgramCache(persist_dir=str(tmp_path))
    monkeypatch.setattr(os, "remove",
                        lambda p: (_ for _ in ()).throw(
                            OSError(errno.EROFS, "read-only", str(p))))
    prog, _ = warm.get_or_build_keyed(steps, P, MACHINE)
    assert prog is not None
    assert warm.stats.invalidated == 1
    assert fname in warm._poisoned
    assert path.exists()                         # could not be removed

    # the poisoned entry short-circuits: no second decode, no second
    # invalidation — just a disk miss
    warm._programs.clear(); warm._certs.clear()  # force an in-memory miss
    before = warm.stats.invalidated
    prog2, _ = warm.get_or_build_keyed(steps, P, MACHINE)
    assert prog2 is not None
    assert warm.stats.invalidated == before


def test_attach_store_failure_is_memory_only(tmp_path):
    blocker = tmp_path / "blocker"
    blocker.write_text("not a directory")
    cache = ProgramCache(persist_dir=str(blocker / "sub"))
    assert cache.store is None
    assert cache.memory_only_reason is not None
    assert cache.stats.disk_errors == 1
    # and the cache still works
    prog, _, _ = build_and_certify(cache)
    assert prog is not None


# ---------------------------------------------------------------------------
# with_capacity: the paper's resize-and-retry contract
# ---------------------------------------------------------------------------

def _stage_ctx():
    from repro.core import LPFContext
    return LPFContext(())


def test_with_capacity_resizes_queue_and_retries():
    ctx = _stage_ctx()
    a, b = None, None
    ctx.resize_memory_register(2)
    a = ctx.register_global("a", jnp.zeros(8))
    b = ctx.register_global("b", jnp.zeros(8))
    attempts = []

    def body(c):
        attempts.append(c._queue_capacity)
        c.put_msgs([(0, 0, a, 0, b, 0, 8)])
        c._queue = []        # consume (p=1 has no real sync path here)
        return "done"

    assert ctx._queue_capacity == 0
    assert ctx.with_capacity(body) == "done"
    assert len(attempts) == 2                    # failed once, resized
    assert ctx._queue_capacity >= 1


def test_with_capacity_respects_required_field():
    ctx = _stage_ctx()
    calls = []

    def body(c):
        calls.append(True)
        if len(calls) == 1:
            raise LPFCapacityError("need much more", required=1000,
                                   capacity=0, kind="queue")
        return c._queue_capacity

    assert ctx.with_capacity(body) >= 1000


def test_with_capacity_resizes_register():
    ctx = _stage_ctx()

    def body(c):
        # registry capacity 0: first attempt raises kind="register"
        s = c.register_global("x", jnp.zeros(4))
        c.deregister(s)
        return c.registry.capacity

    assert ctx.with_capacity(body) >= 1


def test_with_capacity_bounded_attempts():
    ctx = _stage_ctx()
    calls = []

    def body(c):
        calls.append(True)
        raise LPFCapacityError("never enough", required=2, capacity=1)

    with pytest.raises(LPFCapacityError):
        ctx.with_capacity(body, max_attempts=3)
    assert len(calls) == 3


def test_with_capacity_other_errors_propagate_immediately():
    ctx = _stage_ctx()
    calls = []

    def body(c):
        calls.append(True)
        raise LPFFatalError("not a capacity problem")

    with pytest.raises(LPFFatalError):
        ctx.with_capacity(body)
    assert len(calls) == 1


def test_program_abort_discards_pending_steps():
    """An exception inside ``with ctx.program()`` discards the recorded
    supersteps — a failed region must not flush (execute) a partial
    trace, or the capacity error would have side effects."""
    ctx = _stage_ctx()
    ctx.resize_memory_register(2)
    ctx.resize_message_queue(4)
    a = ctx.register_global("a", jnp.zeros(8))
    b = ctx.register_global("b", jnp.zeros(8))

    class Boom(Exception):
        pass

    with pytest.raises(Boom):
        with ctx.program("doomed"):
            ctx.put_msgs([(0, 0, a, 0, b, 0, 8)])
            ctx.sync(label="recorded-then-aborted")
            raise Boom()
    assert ctx._rec_pending == []
    assert ctx._rec_depth == 0
    assert ctx._queue == []
    assert ctx.ledger.records == []              # nothing executed


# ---------------------------------------------------------------------------
# recovery supervision
# ---------------------------------------------------------------------------

def test_supervisor_absorbs_transient_within_budget():
    sup = StepSupervisor(max_restarts=2, backoff=0.0)
    assert sup.on_error(3, OSError(errno.EIO, "blip")) is True
    assert sup.on_error(5, InjectedFault("xla")) is True
    # budget exhausted: the third transient propagates
    assert sup.on_error(7, OSError(errno.EIO, "blip")) is False
    kinds = [(a.kind, a.action) for a in sup.anomalies]
    assert kinds == [("transient", "restore"), ("transient", "restore"),
                     ("transient", "propagate")]


def test_supervisor_never_retries_fatal_or_mitigable():
    sup = StepSupervisor(max_restarts=5, backoff=0.0)
    assert sup.on_error(0, LPFFatalError("contract")) is False
    assert sup.on_error(1, LPFCapacityError("full")) is False
    assert sup.on_error(2, ValueError("unclassified")) is False
    assert sup.restarts == 0
    assert all(a.action == "propagate" for a in sup.anomalies)


def test_supervisor_records_straggler_verdicts():
    from repro.runtime.monitor import StepVerdict
    sup = StepSupervisor()
    sup.on_verdict(StepVerdict(0, 0.1, 0.0, False, "ok"))
    sup.on_verdict(StepVerdict(1, 9.0, 8.0, True, "skip_sync"))
    sup.on_verdict(StepVerdict(2, 9.0, 8.0, True, "rescale"))
    assert [(a.step, a.action) for a in sup.anomalies] == [
        (1, "skip_sync"), (2, "rescale")]


class _FakeStream:
    def batch(self, step):
        return {"x": np.full((2,), float(step), np.float32)}

    def state(self, step):
        return {"step": step}


def _fake_train_step(fail_at=(), taken=None):
    """A TrainStep-shaped object whose step_fn fails transiently at the
    given global step indices (once each)."""
    from repro.runtime.train_step import TrainStep
    pending = set(fail_at)

    def init_fn(key):
        return {"w": jnp.zeros(2)}, {"m": jnp.zeros(2)}

    def step_fn(params, opt, batch):
        step = int(batch["x"][0])
        if taken is not None:
            taken.append(step)
        if step in pending:
            pending.discard(step)
            raise OSError(errno.EIO, f"injected transient at step {step}")
        params = {"w": params["w"] + batch["x"]}
        return params, opt, {"loss": jnp.sum(params["w"])}

    return TrainStep(step_fn=step_fn, init_fn=init_fn,
                     param_sharding=None, opt_sharding=None,
                     batch_sharding=None, rt=None, ledger=None)


def test_train_loop_restores_from_checkpoint_on_transient(tmp_path):
    taken = []
    ts = _fake_train_step(fail_at=(5,), taken=taken)
    out = train_loop(ts, _FakeStream(),
                     TrainLoopConfig(steps=8, ckpt_dir=str(tmp_path),
                                     ckpt_every=2, max_restarts=2,
                                     restart_backoff=0.0))
    assert out["restarts"] == 1
    restores = [a for a in out["anomalies"] if a.action == "restore"]
    assert len(restores) == 1 and restores[0].step == 5
    # rolled back to the newest published checkpoint (step 4) and
    # re-ran 4 and 5 — the loop still completes all 8 steps
    assert taken == [0, 1, 2, 3, 4, 5, 4, 5, 6, 7]
    assert len(out["losses"]) == 8
    # numerics equal the failure-free run (pure-function data pipeline)
    clean = train_loop(_fake_train_step(), _FakeStream(),
                       TrainLoopConfig(steps=8, ckpt_dir=None))
    assert out["losses"] == clean["losses"]


def test_train_loop_propagates_when_budget_exhausted(tmp_path):
    ts = _fake_train_step(fail_at=(2, 3, 4))
    with pytest.raises(OSError):
        train_loop(ts, _FakeStream(),
                   TrainLoopConfig(steps=8, ckpt_dir=str(tmp_path),
                                   ckpt_every=2, max_restarts=2,
                                   restart_backoff=0.0))


def test_train_loop_propagates_fatal_immediately(tmp_path):
    from repro.runtime.train_step import TrainStep

    def init_fn(key):
        return {"w": jnp.zeros(2)}, {"m": jnp.zeros(2)}

    def step_fn(params, opt, batch):
        raise LPFFatalError("one-sided contract violation")

    ts = TrainStep(step_fn=step_fn, init_fn=init_fn, param_sharding=None,
                   opt_sharding=None, batch_sharding=None, rt=None,
                   ledger=None)
    with pytest.raises(LPFFatalError):
        train_loop(ts, _FakeStream(),
                   TrainLoopConfig(steps=4, ckpt_dir=str(tmp_path),
                                   max_restarts=5, restart_backoff=0.0))


def test_restore_latest_roundtrip(tmp_path):
    from repro.checkpoint import AsyncCheckpointer
    ckpt = AsyncCheckpointer(str(tmp_path))
    like = {"w": jax.ShapeDtypeStruct((2,), jnp.float32)}
    step, state = ckpt.restore_latest(like)
    assert step is None and state is None
    ckpt.save(7, {"w": jnp.arange(2.0)})
    step, state = ckpt.restore_latest(like)
    assert step == 7
    np.testing.assert_array_equal(np.asarray(state["w"]), [0.0, 1.0])


# ---------------------------------------------------------------------------
# the chaos invariant, in-process (one cheap plan per seam family)
# ---------------------------------------------------------------------------

def test_chaos_smoke_warm_start_plans():
    from repro.runtime.faults import _run_one
    baselines = {}
    for workload, spec in SMOKE_PLANS:
        if workload != "warm_start":
            continue                 # mesh workloads: the test below
        verdict, detail = _run_one(workload, FaultPlan.parse(spec),
                                   baselines)
        assert verdict in ("identical", "classified"), \
            (workload, spec, verdict, detail)


MESH_SMOKE_PLANS = [(w, spec) for w, spec in SMOKE_PLANS
                    if w in ("bucketed_sync", "decode")]


@pytest.mark.parametrize("workload,spec", MESH_SMOKE_PLANS,
                         ids=[f"{w}-{spec}" for w, spec in MESH_SMOKE_PLANS])
def test_chaos_smoke_mesh_plans(workload, spec):
    """Each seam a recorded program on the host mesh can reach
    (straggler, capacity) leaves the result identical to the fault-free
    run or raises a classified error."""
    from repro.runtime.faults import _run_one
    verdict, detail = _run_one(workload, FaultPlan.parse(spec), {})
    assert verdict in ("identical", "classified"), \
        (workload, spec, verdict, detail)


def test_zero_fault_path_is_transparent(tmp_path):
    """With no plan armed, a run through every seam-bearing path equals
    a run of the seed code: same programs, same stats, no injector
    consulted."""
    assert faults.active() is None
    c1 = ProgramCache(persist_dir=str(tmp_path / "a"))
    c2 = ProgramCache(persist_dir=str(tmp_path / "b"))
    _, k1, _ = build_and_certify(c1)
    _, k2, _ = build_and_certify(c2)
    assert k1 == k2
    blob1 = (tmp_path / "a" / entry_filename(k1)).read_bytes()
    blob2 = (tmp_path / "b" / entry_filename(k2)).read_bytes()
    assert blob1 == blob2                        # byte-identical entries
    assert c1.stats.disk_errors == 0
    assert dataclasses.asdict(c1.stats) == dataclasses.asdict(c2.stats)
