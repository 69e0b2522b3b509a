"""The hardened continuous-batching serve loop.

Three layers under test:
  * the admission controller — the model-priced deadline bound is a
    theorem on the virtual model clock (admitted => predicted
    completion <= deadline, completed => completion <= prediction, so
    zero deadline misses fault-free), every refusal classified;
  * the degradation ladder — bounded queue backpressure, shrink
    routing under load, priority/deadline shedding, decode fallback
    with bucket quarantine, graceful drain;
  * the pure-LPF decode engine — requests decode bit-identical solo,
    batched, and on the per-token fallback path, and the admission
    price equals the executed ledger (model compliance end to end).

The fast tier runs the server against a deterministic fake engine (no
devices); the slow tier runs the real ``ProgramDecodeEngine`` on the
host mesh.
"""

import random

import pytest

from repro.core import LPFFatalError, ProgramCache
from repro.core.program import SuperstepProgram
from repro.runtime import faults
from repro.runtime.faults import FaultPlan
from repro.runtime.server import (REASONS, LPFServer, ServeOutcome,
                                  ServeRejected, ServeRequest,
                                  synthetic_requests)

pytestmark = pytest.mark.fast


# ==========================================================================
# fixtures: a deterministic engine with no devices behind it
# ==========================================================================

class FakeEngine:
    """Protocol-complete decode engine: tokens are a pure function of
    (seed, position), service is priced at a flat per-token cost, and
    failures are scripted via ``fail_with``."""

    def __init__(self, buckets=((2, 8), (4, 8)), token_s=1e-3):
        self._buckets = tuple(tuple(b) for b in buckets)
        self.token_s = token_s
        self.quarantined = set()
        self.decodes = 0
        self.flushed = 0
        self.fail_with = []        # exceptions raised by upcoming decodes

    def buckets(self):
        return self._buckets

    def token_seconds(self, bucket):
        return self.token_s

    def overhead_seconds(self, bucket):
        return 0.0

    def round_tokens(self, bucket, n):
        t = 1
        while t < n:
            t *= 2
        return min(t, bucket[1])

    def ledger_seconds(self, bucket, n_tokens):
        return self.token_s * n_tokens

    def quarantine(self, bucket):
        self.quarantined.add(tuple(bucket))

    def flush(self):
        self.flushed += 1
        return 0

    def decode(self, bucket, reqs, n_tokens):
        self.decodes += 1
        if self.fail_with:
            raise self.fail_with.pop(0)
        return {r.rid: tuple((r.seed * 31 + i) % 997
                             for i in range(n_tokens)) for r in reqs}


def req(rid, n=4, deadline=10.0, priority=0, seed=None):
    return ServeRequest(rid=rid, n_tokens=n, deadline_s=deadline,
                        priority=priority,
                        seed=rid * 7919 if seed is None else seed)


def expected_tokens(r, n=None):
    return tuple((r.seed * 31 + i) % 997
                 for i in range(n if n is not None else r.n_tokens))


# ==========================================================================
# admission: the deadline bound is a theorem on the model clock
# ==========================================================================

@pytest.mark.parametrize("seed", range(8))
def test_admission_deadline_property(seed):
    """Seeded property test over random arrival patterns: admitted =>
    predicted <= deadline; completed => completion <= predicted (so 0
    deadline misses); refused => classified reason."""
    rng = random.Random(seed)
    eng = FakeEngine()
    srv = LPFServer(eng, max_queue=rng.choice([4, 8, 16]))
    reqs = synthetic_requests(40, seed, eng.buckets(),
                              token_cost_s=eng.token_s,
                              tight_frac=0.35)
    admitted = set()
    for r in reqs:
        out = srv.submit(r)
        if out.status == "admitted":
            admitted.add(r.rid)
            assert out.predicted_v <= out.deadline_v
        else:
            assert out.reason in REASONS
            assert isinstance(out.error, ServeRejected)
        if rng.random() < 0.4:
            srv.step()
    srv.run_until_idle()
    outs = srv.take_outcomes()
    assert set(outs) == {r.rid for r in reqs}
    assert srv.metrics.deadline_misses == 0
    for r in reqs:
        out = outs[r.rid]
        if out.status == "completed":
            assert r.rid in admitted
            assert out.completion_v <= out.predicted_v + 1e-12
            assert out.completion_v <= out.deadline_v + 1e-12
            assert out.tokens == expected_tokens(r)
        else:
            assert out.classified, (r.rid, out.status, out.reason)
    # fault-free, every admitted request terminates: completed, or
    # shed under overload with the classified reason (never silently
    # lost, never a deadline miss)
    done = {rid for rid, o in outs.items() if o.status == "completed"}
    shed = {rid for rid, o in outs.items() if o.status == "shed"}
    assert done <= admitted
    assert done | shed >= admitted


def test_admission_accounts_backlog():
    eng = FakeEngine(buckets=((2, 8),))
    srv = LPFServer(eng, max_queue=8)
    # each request costs 8 * 1e-3; deadline fits one but not a queue
    assert srv.submit(req(0, n=8, deadline=0.009)).status == "admitted"
    out = srv.submit(req(1, n=8, deadline=0.009))
    assert out.status == "rejected" and out.reason == "deadline_unmeetable"
    # a deadline with room for the backlog is admitted
    assert srv.submit(req(2, n=8, deadline=0.025)).status == "admitted"


def test_rejection_classification():
    eng = FakeEngine(buckets=((2, 8),))
    srv = LPFServer(eng, max_queue=4)
    assert srv.submit(req(0, n=0)).reason == "no_bucket"
    assert srv.submit(req(1, n=64)).reason == "no_bucket"
    assert srv.submit(req(2, n=4, deadline=1e-9)
                      ).reason == "deadline_unmeetable"
    for out in srv.take_outcomes().values():
        assert out.classified


def test_backpressure_queue_full():
    eng = FakeEngine(buckets=((2, 8),))
    # shrink/shed disabled: the bounded queue itself must refuse
    srv = LPFServer(eng, max_queue=3, shrink_frac=1.0, shed_frac=1.0)
    for i in range(3):
        assert srv.submit(req(i)).status == "admitted"
    out = srv.submit(req(3))
    assert out.status == "rejected" and out.reason == "queue_full"
    srv.step()
    assert srv.submit(req(4)).status == "admitted"


def test_backlog_bound_rejects():
    eng = FakeEngine(buckets=((2, 8),))
    srv = LPFServer(eng, max_queue=64, reject_backlog_s=0.010)
    assert srv.submit(req(0, n=8, deadline=10.0)).status == "admitted"
    out = srv.submit(req(1, n=8, deadline=10.0))
    assert out.status == "rejected" and out.reason == "overloaded"


# ==========================================================================
# the degradation ladder
# ==========================================================================

def test_shrink_routes_to_small_bucket():
    eng = FakeEngine(buckets=((2, 8), (4, 8)))
    srv = LPFServer(eng, max_queue=8, shrink_frac=0.5)
    assert srv.submit(req(0)).bucket == (4, 8)       # level 0: throughput
    for i in range(1, 4):
        srv.submit(req(i))
    assert srv.level >= 1
    assert srv.submit(req(9)).bucket == (2, 8)       # level 1: latency
    srv.run_until_idle()


def test_shed_lowest_priority_latest_deadline():
    eng = FakeEngine(buckets=((2, 8),))
    srv = LPFServer(eng, max_queue=5, shrink_frac=0.2, shed_frac=0.4)
    # shed limit = int(0.4 * 5) = 2 queued tickets
    assert srv.submit(req(0, priority=1, deadline=5.0)
                      ).status == "admitted"
    assert srv.submit(req(1, priority=0, deadline=9.0)
                      ).status == "admitted"
    # a higher-priority arrival sheds rid 1 (lowest priority, latest
    # deadline) — classified, not silently dropped
    assert srv.submit(req(2, priority=2, deadline=5.0)
                      ).status == "admitted"
    shed = srv.outcomes[1]
    assert shed.status == "shed" and shed.reason == "shed_overload"
    assert shed.classified
    # an arrival that ranks below everything queued is itself refused
    out = srv.submit(req(3, priority=0, deadline=99.0))
    assert out.status == "rejected" and out.reason == "overloaded"
    srv.run_until_idle()
    assert srv.outcomes[0].status == "completed"
    assert srv.outcomes[2].status == "completed"


def test_continuous_batch_join_rule():
    """Members join the head-of-line leader's batch only if they do
    not extend its decode length; riders finish with the leader."""
    eng = FakeEngine(buckets=((4, 8),))
    srv = LPFServer(eng, max_queue=8)
    for r in (req(0, n=4), req(1, n=2), req(2, n=8), req(3, n=4)):
        assert srv.submit(r).status == "admitted"
    done = srv.step()      # leader rid0 (T=4) + riders rid1, rid3
    assert sorted(o.rid for o in done) == [0, 1, 3]
    assert all(o.status == "completed" for o in done)
    assert {o.rid: len(o.tokens) for o in done} == {0: 4, 1: 2, 3: 4}
    done = srv.step()      # rid2 decodes alone at T=8
    assert [o.rid for o in done] == [2]
    assert srv.metrics.batches == 2


# ==========================================================================
# decode failures: fallback, quarantine, classified batch failure
# ==========================================================================

def test_decode_fault_falls_back_and_quarantines():
    eng = FakeEngine(buckets=((2, 8),))
    eng.fail_with = [OSError("transient launch failure")]
    srv = LPFServer(eng, max_queue=4)
    srv.submit(req(0))
    done = srv.step()
    assert [o.status for o in done] == ["completed"]
    assert done[0].fallback and done[0].tokens == expected_tokens(req(0))
    assert (2, 8) in eng.quarantined
    assert srv.metrics.decode_fallbacks == 1
    assert srv.metrics.decode_failures == 0


def test_decode_fault_exhausted_fails_classified():
    eng = FakeEngine(buckets=((2, 8),))
    eng.fail_with = [OSError("boom"), OSError("boom again")]
    srv = LPFServer(eng, max_queue=4)
    srv.submit(req(0))
    srv.submit(req(1))
    done = srv.step()
    assert all(o.status == "rejected" and o.reason == "decode_failed"
               and o.classified for o in done)
    assert srv.metrics.decode_failures == 1
    # the server survives: the next batch serves normally
    srv.submit(req(2))
    assert srv.step()[0].status == "completed"


def test_fatal_lpf_error_not_degraded_around():
    eng = FakeEngine(buckets=((2, 8),))
    eng.fail_with = [LPFFatalError("contract violation")]
    srv = LPFServer(eng, max_queue=4)
    srv.submit(req(0))
    done = srv.step()
    assert done[0].reason == "decode_failed"
    # no fallback retry for a contract violation
    assert srv.metrics.decode_fallbacks == 0
    assert not eng.quarantined


def test_serve_fault_seams():
    eng = FakeEngine(buckets=((2, 8),))
    srv = LPFServer(eng, max_queue=4)
    with faults.inject(FaultPlan.parse("serve_admit@0")) as inj:
        out = srv.submit(req(0))
        assert out.status == "rejected" and out.reason == "admit_fault"
        assert out.classified
        assert inj.fired and inj.fired[0][0] == "serve_admit"
    with faults.inject(FaultPlan.parse("serve_decode@0")) as inj:
        srv.submit(req(1))
        done = srv.step()
        assert done[0].status == "completed" and done[0].fallback
    with faults.inject(FaultPlan.parse("serve_decode@0x-1")):
        srv.submit(req(2))
        done = srv.step()
        assert done[0].reason == "decode_failed" and done[0].classified


# ==========================================================================
# drain / health
# ==========================================================================

def test_graceful_drain():
    eng = FakeEngine(buckets=((2, 8),))
    srv = LPFServer(eng, max_queue=8)
    for i in range(5):
        srv.submit(req(i))
    health = srv.drain()
    assert health["draining"] and health["queue_depth"] == 0
    assert health["completed"] == 5          # in-flight work finished
    assert eng.flushed == 1                  # caches flushed
    out = srv.submit(req(9))                 # no new admissions
    assert out.status == "rejected" and out.reason == "draining"
    assert srv.drain()["queue_depth"] == 0   # idempotent


def test_health_snapshot_keys():
    eng = FakeEngine()
    srv = LPFServer(eng, max_queue=4)
    srv.submit(req(0, n=2, deadline=1e-9))
    srv.submit(req(1))
    srv.run_until_idle()
    h = srv.health()
    for key in ("vclock_s", "queue_depth", "backlog_s", "level",
                "submitted", "admitted", "completed", "rejected_total",
                "rejected_deadline_unmeetable", "deadline_misses",
                "batches", "tokens_decoded", "queue_peak",
                "stragglers_flagged"):
        assert key in h, key
    assert h["submitted"] == 2 and h["completed"] == 1


# ==========================================================================
# ProgramCache pinning (the hot-bucket protection satellite)
# ==========================================================================

def _one_step_trace(sid, size):
    import numpy as np
    from repro.core import Msg, ProgramStep, Slot
    a = Slot(sid=sid, name=f"a{sid}", size=size,
             dtype=np.dtype("float32"), kind="global",
             orig_shape=(size,))
    b = Slot(sid=sid + 1, name=f"b{sid}", size=size,
             dtype=np.dtype("float32"), kind="global",
             orig_shape=(size,))
    msgs = [Msg(s, (s + 1) % 4, a, 0, b, 0, size) for s in range(4)]
    return [ProgramStep(msgs=tuple(msgs), attrs=None, label=f"t{sid}")]


def _build_keyed(pc, plan_cache, machine, sid, size):
    from repro.core import LPF_SYNC_DEFAULT
    steps = _one_step_trace(sid, size)
    steps = [s.__class__(msgs=s.msgs, attrs=LPF_SYNC_DEFAULT,
                         label=s.label) for s in steps]
    return pc.get_or_build_keyed(steps, 4, machine,
                                 plan_cache=plan_cache)


def test_pinned_entries_survive_cold_burst():
    """Thousands of distinct one-shot signatures against a tiny
    maxsize: the pinned hot set must survive every eviction wave and
    the unpinned population must stay bounded."""
    from repro.core import LPFMachine, PlanCache
    machine = LPFMachine(p=4, g=1e-9, l=1e-6, r=1e-10)
    pc = ProgramCache(maxsize=8)
    plan_cache = PlanCache()
    hot = []
    for i in range(2):
        _prog, key = _build_keyed(pc, plan_cache, machine, 100 + 2 * i,
                                  10000 + i)
        pc.pin(key)
        hot.append(key)
    # distinct message sizes => distinct program signatures, no reuse
    for i in range(2000):
        _build_keyed(pc, plan_cache, machine, 1000 + 2 * i, 8 + i)
    for key in hot:
        assert key in pc.keys()              # never evicted
    assert len(pc) <= 8 + len(hot)           # maxsize bounds unpinned
    assert pc.stats.evictions >= 1990
    pc.unpin(hot[0])
    assert hot[0] not in pc.pinned
    with pytest.raises(LPFFatalError):
        pc.pin(("no", "such", "key"))


def test_pinning_is_observable_in_cache_metrics():
    import types
    from repro.core import CacheStats, LPFMachine, PlanCache
    from repro.runtime.monitor import cache_metrics
    machine = LPFMachine(p=4, g=1e-9, l=1e-6, r=1e-10)
    pc = ProgramCache(maxsize=4)
    _prog, key = _build_keyed(pc, PlanCache(), machine, 0, 8)
    pc.pin(key)
    ctx = types.SimpleNamespace(
        cache_stats={"plan": CacheStats(), "program": pc.stats},
        program_cache=pc)
    m = cache_metrics(ctx)
    assert m["program_pinned"] == 1
    assert m["program_entries"] == 1
    assert m["program_memory_only"] == 0
    assert "program_disk_errors" in m


# The real ProgramDecodeEngine (XLA-compiling) lives in the slow tier:
# tests/test_server_engine.py.
