"""LPF core semantics: the twelve primitives against explicit oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import core as lpf
from repro.core import (CompressSpec, LPFCapacityError, LPFFatalError,
                        SyncAttributes)

pytestmark = [pytest.mark.filterwarnings("ignore::DeprecationWarning"),
              pytest.mark.slow]


def run8(mesh8, spmd, args=None, out_specs=P("x"), **kw):
    return lpf.exec_(mesh8, spmd, args, out_specs=out_specs, **kw)


# ---------------------------------------------------------------------------
# put / get / sync
# ---------------------------------------------------------------------------

def test_put_shift(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_global("src", jnp.arange(4.0) + 10.0 * ctx.pid)
        dst = ctx.register_global("dst", jnp.zeros(4))
        ctx.put(src, dst, to=lambda s: (s + 1) % p, size=4)
        ctx.sync()
        return ctx.tensor(dst)

    out = np.asarray(run8(mesh8, spmd)).reshape(8, 4)
    want = np.stack([np.arange(4.0) + 10.0 * ((i - 1) % 8)
                     for i in range(8)])
    np.testing.assert_allclose(out, want)


def test_get_neighbour(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_global("src", jnp.full(3, 1.0) * ctx.pid)
        dst = ctx.register_global("dst", jnp.zeros(3))
        ctx.get(src, dst, frm=lambda s: (s + 2) % p, size=3)
        ctx.sync()
        return ctx.tensor(dst)

    out = np.asarray(run8(mesh8, spmd)).reshape(8, 3)
    np.testing.assert_allclose(
        out, np.stack([np.full(3, (i + 2) % 8.0) for i in range(8)]))


def test_offsets_and_partial_sizes(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_global("src", jnp.arange(8.0) + 100.0 * ctx.pid)
        dst = ctx.register_global("dst", jnp.full(8, -1.0))
        # send elements [2:5) to the right neighbour's offset 1
        ctx.put(src, dst, to=lambda s: (s + 1) % p, src_off=2, dst_off=1,
                size=3)
        ctx.sync()
        return ctx.tensor(dst)

    out = np.asarray(run8(mesh8, spmd)).reshape(8, 8)
    for i in range(8):
        left = (i - 1) % 8
        want = np.full(8, -1.0)
        want[1:4] = np.arange(2.0, 5.0) + 100.0 * left
        np.testing.assert_allclose(out[i], want)


def test_crcw_highest_pid_wins(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        mine = ctx.register_global("m", jnp.full(2, 1.0) * ctx.pid)
        tgt = ctx.register_global("t", jnp.full(2, -1.0))
        ctx.put(mine, tgt, to=0, size=2)
        ctx.sync()
        return ctx.tensor(tgt)

    out = np.asarray(run8(mesh8, spmd)).reshape(8, 2)
    assert out[0, 0] == 7.0               # arbitrary-CRCW: last writer wins
    assert (out[1:] == -1.0).all()        # non-targets untouched


def test_reads_observe_pre_sync_values(mesh8):
    """All payloads must be read from the pre-superstep state, even when
    the same slot is both source and destination."""
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(1)
        ctx.resize_message_queue(p)
        buf = ctx.register_global("b", jnp.full(2, 1.0) * ctx.pid)
        ctx.put(buf, buf, to=lambda s: (s + 1) % p, size=2)
        ctx.sync()
        return ctx.tensor(buf)

    out = np.asarray(run8(mesh8, spmd)).reshape(8, 2)
    np.testing.assert_allclose(out[:, 0], [(i - 1) % 8 for i in range(8)])


# ---------------------------------------------------------------------------
# methods: bruck / valiant / fused equivalence
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", ["direct", "bruck"])
def test_methods_agree_on_permutation(mesh8, method):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_global("src", jnp.arange(4.0) + 10.0 * ctx.pid)
        dst = ctx.register_global("dst", jnp.zeros(4))
        ctx.put(src, dst, to=lambda s: (s * 3 + 1) % p, size=4)
        ctx.sync(SyncAttributes(method=method))
        return ctx.tensor(dst)

    out = np.asarray(run8(mesh8, spmd)).reshape(8, 4)
    # invert the permutation d = (3s + 1) mod 8
    inv = {(3 * s + 1) % 8: s for s in range(8)}
    want = np.stack([np.arange(4.0) + 10.0 * inv[i] for i in range(8)])
    np.testing.assert_allclose(out, want)


def test_valiant_routing(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(3)
        ctx.resize_message_queue(4 * p, valiant_payload=64)
        src = ctx.register_global("src", jnp.arange(4.0) + 10.0 * ctx.pid)
        dst = ctx.register_global("dst", jnp.zeros(4))
        ctx.put(src, dst, to=lambda s: (s + 5) % p, size=4)
        ctx.sync(SyncAttributes(method="valiant"))
        return ctx.tensor(dst)

    out = np.asarray(run8(mesh8, spmd)).reshape(8, 4)
    want = np.stack([np.arange(4.0) + 10.0 * ((i - 5) % 8)
                     for i in range(8)])
    np.testing.assert_allclose(out, want)


def test_fused_total_exchange_detection(mesh8):
    def spmd(ctx, s, p, _):
        w = 2
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p * p)
        src = ctx.register_global(
            "src", jnp.arange(p * w, dtype=jnp.float32) + 100.0 * ctx.pid)
        dst = ctx.register_global("dst", jnp.zeros(p * w))
        ctx.put_msgs([(s_, d, src, d * w, dst, s_ * w, w)
                      for s_ in range(p) for d in range(p)])
        ctx.sync(label="a2a")
        return ctx.tensor(dst)

    out, ledger = run8(mesh8, spmd, return_ledger=True)
    assert ledger.records[0].method == "fused"
    assert ledger.records[0].rounds == 1
    out = np.asarray(out).reshape(8, 16)
    want = np.stack([np.concatenate(
        [np.arange(d * 2, d * 2 + 2) + 100.0 * s for s in range(8)])
        for d in range(8)])
    np.testing.assert_allclose(out, want)


# ---------------------------------------------------------------------------
# capacity / errors (mitigable before side effects)
# ---------------------------------------------------------------------------

def test_queue_capacity_mitigable(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(2)          # deliberately too small
        src = ctx.register_global("src", jnp.zeros(4))
        dst = ctx.register_global("dst", jnp.zeros(4))
        try:
            ctx.put(src, dst, to=lambda s: (s + 1) % p, size=4)  # p msgs
            code = 0
        except LPFCapacityError:
            # mitigate: grow the queue and retry — no side effects happened
            ctx.resize_message_queue(p)
            ctx.put(src, dst, to=lambda s: (s + 1) % p, size=4)
            code = 1
        ctx.sync()
        return jnp.full((1,), code, jnp.int32)

    out = np.asarray(run8(mesh8, spmd)).reshape(-1)
    assert (out == 1).all()


def test_register_capacity(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(1)
        ctx.register_global("a", jnp.zeros(2))
        try:
            ctx.register_global("b", jnp.zeros(2))
            return jnp.zeros((1,), jnp.int32)
        except LPFCapacityError:
            return jnp.ones((1,), jnp.int32)

    assert (np.asarray(run8(mesh8, spmd)) == 1).all()


def test_oob_message_fatal(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_global("src", jnp.zeros(4))
        dst = ctx.register_global("dst", jnp.zeros(2))
        ctx.put(src, dst, to=0, size=4)   # dst too small
        ctx.sync()
        return jnp.zeros((1,))

    with pytest.raises(LPFFatalError):
        run8(mesh8, spmd)


def test_local_slot_semantics(mesh8):
    """put FROM a local slot is legal (Algorithm 2's error broadcast);
    put INTO a local slot (remotely referred) is fatal."""
    def spmd_ok(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_local("src", jnp.full(4, 1.0) * ctx.pid)
        dst = ctx.register_global("dst", jnp.zeros(4))
        ctx.put(src, dst, to=lambda s: (s + 1) % p, size=4)
        ctx.sync()
        return ctx.tensor(dst)

    out = np.asarray(run8(mesh8, spmd_ok)).reshape(8, 4)
    np.testing.assert_allclose(out[:, 0], [(i - 1) % 8 for i in range(8)])

    def spmd_bad(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_global("src", jnp.zeros(4))
        dst = ctx.register_local("dst", jnp.zeros(4))
        ctx.put(src, dst, to=lambda s: (s + 1) % p, size=4)
        ctx.sync()
        return jnp.zeros((1,))

    with pytest.raises(LPFFatalError):
        run8(mesh8, spmd_bad)


# ---------------------------------------------------------------------------
# probe / ledger / compliance accounting
# ---------------------------------------------------------------------------

def test_probe_table():
    m = lpf.probe({"data": 16, "model": 16}, lpf.TPU_V5E)
    assert m.p == 256
    assert m.g > 0 and m.l > 0
    assert m.t_comm(1e6) > m.t_comm(0)
    m2 = lpf.probe({"pod": 2, "data": 16, "model": 16}, lpf.TPU_V5E)
    assert m2.g > m.g * 0.9   # DCN-dominated g is never better than ICI


@pytest.mark.parametrize("kind,hw", [("TPU v5 lite", "TPU_V5E"),
                                     ("TPU v5e", "TPU_V5E"),
                                     ("cpu", "CPU_HOST")])
def test_hardware_for_known_device_kinds(kind, hw):
    assert lpf.hardware_for(kind) is getattr(lpf, hw)


def test_hardware_for_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v4"):
        lpf.hardware_for("TPU v4")
    # the device this test runs on is one the table knows
    lpf.hardware_for(jax.devices()[0].device_kind)


def test_ledger_h_relation(mesh8):
    """The ledger must record exactly the BSP h-relation of the pattern."""
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_global("src", jnp.zeros(10))
        dst = ctx.register_global("dst", jnp.zeros(10))
        ctx.put(src, dst, to=lambda s: (s + 1) % p, size=10)
        ctx.sync(label="shift10")
        return ctx.tensor(dst)

    _, ledger = run8(mesh8, spmd, return_ledger=True)
    rec = ledger.records[0]
    assert rec.h_bytes == 10 * 4          # 10 f32 sent == received per pid
    assert rec.n_msgs == 8
    assert rec.rounds == 1


def test_compressed_sync_wire_bytes(mesh8):
    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(2)
        ctx.resize_message_queue(p)
        src = ctx.register_global("src", jnp.linspace(-1, 1, 16))
        dst = ctx.register_global("dst", jnp.zeros(16))
        ctx.put(src, dst, to=lambda s: (s + 1) % p, size=16)
        ctx.sync(SyncAttributes(compress=CompressSpec(bits=8)))
        return ctx.tensor(dst)

    out, ledger = run8(mesh8, spmd, return_ledger=True)
    out = np.asarray(out).reshape(8, 16)
    np.testing.assert_allclose(out[0], np.linspace(-1, 1, 16), atol=0.02)
    # int8 wire: ~4x fewer bytes than the h-relation's f32 accounting
    assert ledger.records[0].wire_bytes < ledger.records[0].h_bytes / 2


def test_rehook_pristine_context(mesh8):
    def sub(ctx, s, p, args):
        ctx.resize_memory_register(1)
        ctx.resize_message_queue(p)
        src = ctx.register_global("v", jnp.full(1, 1.0) * ctx.pid)
        dst = src
        ctx.put(src, dst, to=lambda s: (s + 1) % p, size=1)
        ctx.sync()
        return ctx.tensor(dst)

    def spmd(ctx, s, p, _):
        ctx.resize_memory_register(1)
        ctx.register_global("outer", jnp.zeros(1))
        inner = lpf.rehook(ctx, sub)       # fresh registry, same procs
        assert ctx.registry.n_active == 1  # outer context untouched
        return inner

    out = np.asarray(run8(mesh8, spmd)).reshape(-1)
    np.testing.assert_allclose(out, [(i - 1) % 8 for i in range(8)])


def test_on_hold_context_rejects_staging_and_sync():
    """Active contexts are disjoint (paper S2.2): while a rehook
    sub-program runs, the parent context must refuse staging and sync."""
    from repro.core import LPFContext

    ctx = LPFContext(())
    ctx.resize_memory_register(2)
    ctx.resize_message_queue(4)
    a = ctx.register_global("a", jnp.arange(4.0))
    b = ctx.register_global("b", jnp.zeros(4))
    seen = []

    def sub(sub_ctx, s, p, _):
        for stage in (lambda: ctx.put(a, b, to=0, size=4),
                      lambda: ctx.get(a, b, frm=0, size=4),
                      lambda: ctx.put_msgs([(0, 0, a, 0, b, 0, 4)]),
                      lambda: ctx.sync()):
            with pytest.raises(LPFFatalError):
                stage()
            seen.append(1)
        return jnp.zeros(1)

    lpf.rehook(ctx, sub)
    assert len(seen) == 4
    # released after the sub-program: the parent context works again
    ctx.put(a, b, to=0, size=4)
    ctx.sync()
    np.testing.assert_allclose(np.asarray(ctx.tensor(b)), np.arange(4.0))


def test_valiant_scratch_resize_does_not_leak_slots():
    """Re-provisioning the Valiant scratch must replace the old slot, not
    leak a registration per resize_message_queue call."""
    from repro.core import LPFContext

    ctx = LPFContext(())
    ctx.resize_message_queue(4, valiant_payload=32)
    baseline = ctx.registry.n_active
    for _ in range(5):
        ctx.resize_message_queue(4, valiant_payload=64)
    assert ctx.registry.n_active == baseline
    assert ctx._scratch is not None and ctx._scratch.size == 64
    # user slots registered alongside survive the re-provisioning
    ctx.resize_memory_register(1)
    slot = ctx.register_global("user", jnp.zeros(4))
    ctx.resize_message_queue(4, valiant_payload=16)
    assert ctx.registry.value(slot).shape == (4,)


def test_pad_to_validation():
    from repro.bsp import pad_to

    x = jnp.arange(4.0)
    np.testing.assert_allclose(np.asarray(pad_to(x, 6)),
                               [0, 1, 2, 3, 0, 0])
    assert pad_to(x, 4) is x
    with pytest.raises(LPFFatalError):       # cannot shrink
        pad_to(x, 3)
    with pytest.raises(LPFFatalError):       # 1-D only
        pad_to(jnp.zeros((2, 2)), 8)


def test_sequential_root_context():
    """LPF_ROOT: p=1 context outside any mesh — puts are memcpys."""
    from repro.core import LPFContext
    ctx = LPFContext(())
    ctx.resize_memory_register(2)
    ctx.resize_message_queue(4)
    a = ctx.register_global("a", jnp.arange(4.0))
    b = ctx.register_global("b", jnp.zeros(4))
    ctx.put(a, b, to=0, size=4)
    ctx.sync()
    np.testing.assert_allclose(np.asarray(ctx.tensor(b)), np.arange(4.0))


def test_sequential_reads_observe_pre_sync_values():
    """Chained p=1 puts (a->b, b->c) in one superstep must deliver b's
    PRE-superstep contents to c, matching the p>1 direct semantics."""
    from repro.core import LPFContext
    ctx = LPFContext(())
    ctx.resize_memory_register(3)
    ctx.resize_message_queue(4)
    a = ctx.register_global("a", jnp.arange(1.0, 5.0))
    b = ctx.register_global("b", jnp.full(4, 7.0))
    c = ctx.register_global("c", jnp.zeros(4))
    ctx.put(a, b, to=0, size=4)
    ctx.put(b, c, to=0, size=4)
    ctx.sync()
    np.testing.assert_allclose(np.asarray(ctx.tensor(b)),
                               np.arange(1.0, 5.0))
    np.testing.assert_allclose(np.asarray(ctx.tensor(c)), 7.0)
