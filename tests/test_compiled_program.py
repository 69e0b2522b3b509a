"""Recorded programs on the mesh, and ``compile_loop``.

A recorded ``SuperstepProgram`` runs through ``execute_schedule`` inside
the caller's trace: slot values equal the numpy differential oracle's,
the ledger records exactly the program's planned costs
(``SuperstepProgram.ledger_costs``), and a replay reuses the cached
program.  ``compile_loop`` rolls counted/conditional iterated programs
into one ``lax.scan``/``while_loop``.
"""

import numpy as np
import pytest

pytestmark = pytest.mark.fast

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro import core as lpf  # noqa: E402
from repro.core import (LPF_SYNC_DEFAULT, Msg, ProgramStep, Slot,  # noqa: E402
                        SyncAttributes, canonical_order, compat,
                        simulate_program)

P_MESH = 8


def make_slot(sid, size, dtype="int32"):
    return Slot(sid=sid, name=f"s{sid}", size=size, dtype=np.dtype(dtype),
                kind="global", orig_shape=(size,))


# ---------------------------------------------------------------------------
# canned traces (the shapes the paper's workloads record)
# ---------------------------------------------------------------------------

def fft_redistribute_trace(p=P_MESH, w=8):
    """Redistribute + reorder, the reorder reading the redistribute's
    destination (a serial dependency chain)."""
    src, buf, out = (make_slot(100, p * w), make_slot(101, p * w),
                     make_slot(102, p * w))
    redist = tuple(Msg(s, d, src, d * w, buf, s * w, w)
                   for s in range(p) for d in range(p))
    reorder = tuple(Msg(s, d, buf, d * w, out, s * w, w)
                    for s in range(p) for d in range(p))
    return [src, buf, out], [
        ProgramStep(redist, LPF_SYNC_DEFAULT, "fft.redistribute"),
        ProgramStep(reorder, LPF_SYNC_DEFAULT, "fft.reorder")]


def bucketed_sync_trace(p=P_MESH, n_buckets=3, w=8):
    """The DDP bucket shape: per bucket a reduce-scatter into a chunk,
    then an all-gather of the chunks (independent across buckets — the
    schedule search overlaps them)."""
    slots, steps, sid = [], [], 200
    for k in range(n_buckets):
        src, buf, out = (make_slot(sid, p * w), make_slot(sid + 1, w),
                         make_slot(sid + 2, p * w))
        sid += 3
        slots += [src, buf, out]
        rs = tuple(Msg(s, d, src, d * w, buf, 0, w)
                   for s in range(p) for d in range(p))
        ag = tuple(Msg(s, d, buf, 0, out, s * w, w)
                   for s in range(p) for d in range(p))
        steps += [ProgramStep(rs, SyncAttributes(reduce_op="sum"),
                              f"b{k}.rs"),
                  ProgramStep(ag, LPF_SYNC_DEFAULT, f"b{k}.ag")]
    return slots, steps


def pagerank_trace(p=P_MESH, w=8):
    """The PageRank iteration shape: an irregular halo permutation, an
    accumulating reduction of a 3-word stats vector to pid 0, and its
    broadcast back."""
    rank = make_slot(300, p * w)
    halo = make_slot(301, w)
    stats = make_slot(302, 3)
    tot = make_slot(303, 3)
    halo_msgs = tuple(Msg(s, (s * 3 + 1) % p, rank, (s % 4) * w, halo, 0, w)
                      for s in range(p))
    red = tuple(Msg(s, 0, stats, 0, tot, 0, 3) for s in range(p))
    bcast = tuple(Msg(0, d, tot, 0, tot, 0, 3) for d in range(1, p))
    return [rank, halo, stats, tot], [
        ProgramStep(halo_msgs, LPF_SYNC_DEFAULT, "pr.halo"),
        ProgramStep(red, SyncAttributes(reduce_op="sum"), "pr.red"),
        ProgramStep(bcast, LPF_SYNC_DEFAULT, "pr.bcast")]


CANNED = {
    "fft_redistribute": fft_redistribute_trace,
    "bucketed_sync": bucketed_sync_trace,
    "pagerank": pagerank_trace,
}


def _init_np(slots, p):
    """Deterministic initial values, mirrored on the numpy oracle and
    the mesh (both a pure function of (sid, pid, index))."""
    return {s.sid: np.stack([
        np.arange(s.size, dtype=np.int64) * 7 + s.sid * 1000 + pid * 37
        for pid in range(p)]).astype(np.int32) for s in slots}


def _run_trace_on_mesh(mesh8, slots, steps):
    """Issue a canned ProgramStep trace through the real ``ctx.program``
    path; returns ({sid: [p, size] np.ndarray}, ledger records, ctx)."""
    pc, pgc = lpf.PlanCache(), lpf.ProgramCache()
    box = {}

    def wrapped(_):
        ctx = lpf.LPFContext(("x",), plan_cache=pc, program_cache=pgc)
        ctx.resize_memory_register(len(slots) + 1)
        ctx.resize_message_queue(max(len(st.msgs) for st in steps))
        smap = {}
        for s in slots:
            init = (jnp.arange(s.size, dtype=jnp.int32) * 7
                    + s.sid * 1000 + ctx.pid.astype(jnp.int32) * 37)
            smap[s.sid] = ctx.register_global(s.name, init)
        with ctx.program("canned"):
            for st in steps:
                ctx.put_msgs([(m.src, m.dst, smap[m.src_slot.sid],
                               m.src_off, smap[m.dst_slot.sid],
                               m.dst_off, m.size) for m in st.msgs])
                ctx.sync(st.attrs, label=st.label)
        box["ledger"] = ctx.ledger
        box["ctx"] = ctx
        return tuple(ctx.value(smap[s.sid]) for s in slots)

    fn = jax.jit(compat.shard_map(
        wrapped, mesh=mesh8, in_specs=(P(),),
        out_specs=tuple(P("x") for _ in slots), check_vma=False))
    outs = fn(jnp.zeros(1))
    values = {s.sid: np.asarray(v).reshape(P_MESH, s.size)
              for s, v in zip(slots, outs)}
    return values, list(box["ledger"].records), box["ctx"]


# ---------------------------------------------------------------------------
# program == oracle, values AND ledger
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(CANNED))
def test_program_matches_oracle(mesh8, name):
    slots, steps = CANNED[name]()
    oracle = simulate_program([(s.msgs, s.attrs) for s in steps],
                              _init_np(slots, P_MESH))
    vals, ledger, ctx = _run_trace_on_mesh(mesh8, slots, steps)
    for s in slots:
        assert (vals[s.sid] == oracle[s.sid]).all(), (name, s.sid)
    # the ledger is the program's planned costs, field for field
    expected = ctx.last_program.ledger_costs([st.label for st in steps],
                                             canonical_order(steps))
    assert ledger == expected, name
    assert len(ledger) >= 1


def test_compiled_entry_created_and_replayed(mesh8):
    """10 replays of one recorded program in one trace: the program
    cache builds it once (1 miss) and serves the other 9 replays (9
    hits), and every replay moves the data."""
    pc, pgc = lpf.PlanCache(), lpf.ProgramCache()
    box = {}

    def wrapped(_):
        ctx = lpf.LPFContext(("x",), plan_cache=pc, program_cache=pgc)
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(2 * ctx.p)
        a = ctx.register_global("a", jnp.arange(4.0) + ctx.pid)
        b = ctx.register_global("b", jnp.zeros(8))
        outs = []
        for i in range(10):
            ctx.write(a, jnp.arange(4.0) + ctx.pid + 100 * i)
            with ctx.program():
                ctx.put(a, b, to=lambda s_: (s_ + 1) % ctx.p, size=4)
                ctx.sync(label="shift")
                ctx.put(a, b, to=lambda s_: (s_ + 2) % ctx.p, dst_off=4,
                        size=4)
                ctx.sync(label="shift2")
            outs.append(ctx.value(b))
        box["stats"] = ctx.cache_stats
        return jnp.stack(outs)

    fn = jax.jit(compat.shard_map(wrapped, mesh=mesh8, in_specs=(P(),),
                                  out_specs=P(None, "x"), check_vma=False))
    out = np.asarray(fn(jnp.zeros(1))).reshape(10, 8, 8)
    for i in range(10):
        for d in range(8):
            np.testing.assert_allclose(
                out[i, d, :4], np.arange(4.0) + (d - 1) % 8 + 100 * i)
            np.testing.assert_allclose(
                out[i, d, 4:], np.arange(4.0) + (d - 2) % 8 + 100 * i)
    assert box["stats"]["program"].misses == 1
    assert box["stats"]["program"].hits == 9
    assert len(pgc) == 1


# ---------------------------------------------------------------------------
# compile_loop
# ---------------------------------------------------------------------------

def test_compile_loop_counted_with_collect(mesh8):
    """4 counted iterations of a one-superstep ring shift in ONE scan:
    final value equals 4 composed shifts, the collected ys stack one
    entry per iteration, the body's program is ledgered exactly once,
    and the program cache sees exactly one miss."""
    pc, pgc = lpf.PlanCache(), lpf.ProgramCache()
    box = {}

    def wrapped(_):
        ctx = lpf.LPFContext(("x",), plan_cache=pc, program_cache=pgc)

        def body(c2, carry):
            c2.resize_memory_register(2)
            c2.resize_message_queue(c2.p)
            a = c2.register_global("a", carry)
            b = c2.register_global("b", jnp.zeros_like(carry))
            c2.put(a, b, to=lambda s_: (s_ + 1) % c2.p, size=4)
            c2.sync(label="shift")
            out = c2.value(b)
            c2.deregister(a)
            c2.deregister(b)
            return out

        x0 = jnp.arange(4.0) + ctx.pid
        final, ys = ctx.compile_loop(body, x0, n_iters=4,
                                     label="ring",
                                     collect=lambda c: c[:1])
        box["ledger"] = ctx.ledger
        return final, ys

    fn = jax.jit(compat.shard_map(wrapped, mesh=mesh8, in_specs=(P(),),
                                  out_specs=(P("x"), P(None, "x")),
                                  check_vma=False))
    final, ys = fn(jnp.zeros(1))
    final = np.asarray(final).reshape(8, 4)
    ys = np.asarray(ys).reshape(4, 8)
    for d in range(8):
        np.testing.assert_allclose(final[d], np.arange(4.0) + (d - 4) % 8)
        # iteration k collects element 0 of the (k+1)-shifted vector
        np.testing.assert_allclose(ys[:, d],
                                   [(d - k - 1) % 8 for k in range(4)])
    # one superstep per body, ledgered once (trace-once semantics)
    records = box["ledger"].records
    assert len(records) == 1 and records[0].label == "shift"
    assert pgc.stats.misses == 1


def test_compile_loop_while_matches_python_loop(mesh8):
    """cond-driven loop == the same body iterated by hand."""
    def run(use_loop):
        def wrapped(_):
            ctx = lpf.LPFContext(("x",))

            def body(c2, carry):
                v, it = carry
                c2.resize_memory_register(2)
                c2.resize_message_queue(c2.p)
                a = c2.register_global("a", v)
                b = c2.register_global("b", jnp.zeros_like(v))
                c2.put(a, b, to=lambda s_: (s_ + 1) % c2.p, size=4)
                c2.sync(label="shift")
                out = c2.value(b)
                c2.deregister(a)
                c2.deregister(b)
                return out + 1.0, it + 1

            v0 = (jnp.arange(4.0) + ctx.pid, jnp.zeros((), jnp.int32))
            if use_loop:
                v, it = ctx.compile_loop(
                    body, v0, cond=lambda c: c[1] < 3, label="w")
            else:
                v, it = v0
                for _ in range(3):
                    v, it = body(ctx, (v, it))
            return v

        fn = jax.jit(compat.shard_map(wrapped, mesh=mesh8,
                                      in_specs=(P(),), out_specs=P("x"),
                                      check_vma=False))
        return np.asarray(fn(jnp.zeros(1))).reshape(8, 4)

    np.testing.assert_array_equal(run(True), run(False))


def test_compile_loop_argument_validation(mesh8):
    def wrapped_both(_):
        ctx = lpf.LPFContext(("x",))
        ctx.compile_loop(lambda c2, c: c, jnp.zeros(1), n_iters=2,
                         cond=lambda c: True)
        return jnp.zeros(1)

    def wrapped_collect_while(_):
        ctx = lpf.LPFContext(("x",))
        ctx.compile_loop(lambda c2, c: c, jnp.zeros(1),
                         cond=lambda c: True, collect=lambda c: c)
        return jnp.zeros(1)

    for bad in (wrapped_both, wrapped_collect_while):
        fn = jax.jit(compat.shard_map(bad, mesh=mesh8, in_specs=(P(),),
                                      out_specs=P(), check_vma=False))
        with pytest.raises(Exception, match="compile_loop|collect"):
            fn(jnp.zeros(1))
