"""exec_'s kept executables: what hits, what misses, and what is kept.

The cache is process-wide, so a test that needs a cold call builds a
fresh closure (a new function is a new key)."""

import collections
import gc
import os
import sys
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import core as lpf
from repro.algorithms import (bsp_fft, lpf_pagerank, partition_graph,
                              reference_pagerank, rmat_graph)
from repro.algorithms import fft as fft_mod
from repro.algorithms import pagerank as pagerank_mod
from repro.core import LPF_SYNC_DEFAULT, CostLedger, compat
from repro.core import context as context_mod
from repro.core.machine import TPU_V5P

XY = P(("x", "y"))


@pytest.fixture()
def events():
    """The names of the ``lpf.*`` spans recorded while the test runs."""
    seen = []

    def listen(name, seconds, **kw):
        if name.startswith("lpf."):
            seen.append(name)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _fresh_shift():
    """A new SPMD function (a key no call has used): a ring shift."""

    def shift(ctx, s, p, x):
        ctx.resize_memory_register(1)
        ctx.resize_message_queue(p)
        buf = ctx.register_global("buf", x)
        ctx.put(buf, buf, to=lambda q: (q + 1) % p)
        ctx.sync(label="shift")
        return ctx.value(buf)

    return shift


def _mesh(shape, names=("x",), first=0):
    n = int(np.prod(shape))
    return compat.make_mesh(shape, names,
                            devices=jax.devices()[first:first + n])


@pytest.mark.parametrize("p", [1, 4])
def test_second_call_is_a_hit(events, p):
    mesh, spmd = _mesh((p,)), _fresh_shift()
    x = jnp.arange(4.0 * p)
    first = lpf.exec_(mesh, spmd, x, in_specs=P("x"), out_specs=P("x"))
    assert collections.Counter(events)["lpf.exec.trace"] == 1
    del events[:]
    second = lpf.exec_(mesh, spmd, x, in_specs=P("x"), out_specs=P("x"))
    assert collections.Counter(events) == {"lpf.exec.hit": 1,
                                           "lpf.exec.run": 1}
    np.testing.assert_array_equal(np.asarray(second), np.asarray(first))
    np.testing.assert_array_equal(np.asarray(first),
                                  np.roll(np.arange(4.0 * p), 4))


def _change(case):
    """(mesh, args, kwargs) of a base call and of the call that differs
    from it in ``case`` alone."""
    mesh = _mesh((2, 2), ("x", "y"))
    x = jnp.arange(8.0)
    base = (mesh, x, dict(in_specs=XY, out_specs=XY))
    changed = {
        "shape": (mesh, jnp.arange(16.0), base[2]),
        "dtype": (mesh, jnp.arange(8, dtype=jnp.int32), base[2]),
        "sharding": (mesh, jax.device_put(x, NamedSharding(mesh, XY)),
                     base[2]),
        "in_specs": (mesh, x, dict(base[2], in_specs=P())),
        "out_specs": (mesh, x, dict(base[2], out_specs=P())),
        "mesh": (_mesh((2, 2), ("x", "y"), first=4), x, base[2]),
        "axes": (mesh, x, dict(base[2], axes=("x",))),
        "hardware": (mesh, x, dict(base[2], hardware=TPU_V5P)),
    }
    if case == "committed":
        # a one-device mesh: an argument committed to another device
        # than the computation's is an error, whatever the cache
        one = _mesh((1,))
        kw = dict(in_specs=P("x"), out_specs=P("x"))
        return (one, x, kw), (one, jax.device_put(x, jax.devices()[0]), kw)
    return base, changed[case]


@pytest.mark.parametrize("case", [
    "shape", "dtype", "committed", "sharding", "in_specs", "out_specs",
    "mesh", "axes", "hardware"])
def test_each_change_misses(events, case):
    spmd = _fresh_shift()
    (mesh, x, kw), (mesh2, x2, kw2) = _change(case)
    lpf.exec_(mesh, spmd, x, **kw)
    lpf.exec_(mesh, spmd, x, **kw)
    assert collections.Counter(events)["lpf.exec.hit"] == 1
    del events[:]
    out = lpf.exec_(mesh2, spmd, x2, **kw2)
    got = collections.Counter(events)
    assert got["lpf.exec.trace"] == 1 and got["lpf.exec.hit"] == 0, got
    eager = lpf.exec_(mesh2, spmd, x2, jit=False, **kw2)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(eager))


def test_hit_returns_the_misses_ledger(events):
    mesh, spmd = _mesh((4,)), _fresh_shift()
    x = jnp.arange(8.0)
    kw = dict(in_specs=P("x"), out_specs=P("x"), return_ledger=True)
    out1, led1 = lpf.exec_(mesh, spmd, x, **kw)
    out2, led2 = lpf.exec_(mesh, spmd, x, **kw)
    assert events.count("lpf.exec.hit") == 1
    assert led1.records and led2.records == led1.records
    assert led2.h_bytes == led1.h_bytes == 2 * 4
    # each caller's ledger is its own: editing one leaves the kept one
    led2.add(led2.records[0])
    _, led3 = lpf.exec_(mesh, spmd, x, **kw)
    assert led3.records == led1.records
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(out1))


def _bad(ctx, s, p, x):
    ctx.resize_memory_register(1)
    ctx.resize_message_queue(p)
    buf = ctx.register_global("buf", x)
    ctx.put(buf, buf, to=lambda q: q + p)    # no such process
    ctx.sync()
    return ctx.value(buf)


def test_a_miss_that_raises_stores_nothing(events):
    mesh = _mesh((4,))
    for _ in range(2):
        with pytest.raises(lpf.LPFFatalError):
            lpf.exec_(mesh, _bad, jnp.arange(4.0), in_specs=P("x"),
                      out_specs=P("x"))
        assert events == ["lpf.exec.trace"]
        del events[:]


def test_lru_evicts_past_its_capacity(events, monkeypatch):
    monkeypatch.setattr(context_mod, "_EXEC_CACHE_SIZE", 2)
    mesh, x = _mesh((4,)), jnp.arange(4.0)
    a, b, c = _fresh_shift(), _fresh_shift(), _fresh_shift()

    def traced(spmd):
        del events[:]
        lpf.exec_(mesh, spmd, x, in_specs=P("x"), out_specs=P("x"))
        return "lpf.exec.trace" in events

    assert [traced(f) for f in (a, b, c)] == [True, True, True]
    assert len(context_mod._EXEC_CACHE) == 2
    assert traced(c) is False          # the newest two are kept
    assert traced(b) is False
    assert traced(a) is True           # the oldest was evicted
    assert traced(c) is True           # ... and a's return pushed c out


@pytest.mark.parametrize("path", ["eager", "nested"])
def test_eager_and_nested_paths_keep_nothing(events, path):
    mesh, spmd = _mesh((4,)), _fresh_shift()
    x = jnp.arange(4.0)
    before = dict(context_mod._EXEC_CACHE)
    if path == "eager":
        def call():
            return lpf.exec_(mesh, spmd, x, in_specs=P("x"),
                             out_specs=P("x"), jit=False)
        per_call = ["lpf.sync", "lpf.exec.run"]
    else:
        def call():
            return jax.jit(lambda v: lpf.exec_(
                mesh, spmd, v, in_specs=P("x"), out_specs=P("x")))(x)
        per_call = ["lpf.sync"]
    outs = [np.asarray(call()) for _ in range(2)]
    assert events == per_call * 2
    np.testing.assert_array_equal(outs[0], [3.0, 0.0, 1.0, 2.0])
    np.testing.assert_array_equal(outs[1], outs[0])
    assert dict(context_mod._EXEC_CACHE) == before


@pytest.mark.parametrize("n,ordered,inverse", [
    (256, True, False), (512, True, False), (256, False, False),
    (256, True, True)])
def test_bsp_fft_parameters_use_distinct_entries(events, n, ordered,
                                                 inverse):
    mesh = _mesh((4,))
    x = jnp.asarray(np.random.default_rng(n).standard_normal(n)
                    + 1j * np.random.default_rng(n + 1).standard_normal(n),
                    jnp.complex64)
    ys = [bsp_fft(mesh, x, ordered=ordered, inverse=inverse)
          for _ in range(2)]
    assert events.count("lpf.exec.hit") >= 1
    np.testing.assert_array_equal(np.asarray(ys[1]), np.asarray(ys[0]))
    ref = np.fft.ifft(np.asarray(x)) if inverse else np.fft.fft(np.asarray(x))
    assert np.abs(np.asarray(ys[0]) - ref).max() / np.abs(ref).max() < 2e-4
    # the four parameter sets, four functions, each with its own entry
    variants = {(256, True, False), (512, True, False), (256, False, False),
                (256, True, True), (n, ordered, inverse)}
    fns = {v: fft_mod._fft_exec_spmd(v[0], 4, v[1], False,
                                     LPF_SYNC_DEFAULT, v[2])
           for v in variants}
    assert len({id(f) for f in fns.values()}) == len(variants)
    keyed = [k[0] for k in context_mod._EXEC_CACHE]
    assert keyed.count(fns[(n, ordered, inverse)]) == 1


def _rotated(edges, n, by):
    """The graph with every vertex moved ``by`` places along, mod n."""
    return (edges + by) % n


def test_pagerank_graphs_with_other_msgs_share_no_executable(events):
    n, p = 128, 4
    mesh = _mesh((p,))
    e1 = rmat_graph(n, 600, seed=11)
    e2 = _rotated(e1, n, n // p)
    g1, g2 = partition_graph(e1, n, p), partition_graph(e2, n, p)
    for f in ("row_ids", "pack_idx", "dangling"):
        assert getattr(g1, f).shape == getattr(g2, f).shape
    assert g1.plan.msgs != g2.plan.msgs
    assert g1.halo_max == g2.halo_max
    for g, edges in ((g1, e1), (g2, e2)):
        del events[:]
        r, _, _ = lpf_pagerank(mesh, g)
        assert "lpf.exec.trace" in events and "lpf.exec.hit" not in events
        ref, _ = reference_pagerank(edges, n)
        assert np.abs(np.asarray(r) - ref).max() < 1e-5
    del events[:]
    r2, _, _ = lpf_pagerank(mesh, g2)
    assert "lpf.exec.hit" in events
    ref2, _ = reference_pagerank(e2, n)
    assert np.abs(np.asarray(r2) - ref2).max() < 1e-5


def test_a_kept_entry_holds_no_input():
    mesh, spmd = _mesh((4,)), _fresh_shift()
    x = jnp.arange(4.0)
    ref = weakref.ref(x)
    lpf.exec_(mesh, spmd, x, in_specs=P("x"), out_specs=P("x"))
    del x
    gc.collect()
    assert ref() is None

    # lpf_pagerank keys on the graph's plan, not on the graph
    g = partition_graph(rmat_graph(64, 200, seed=5), 64, 4)
    graph = weakref.ref(g)
    lpf_pagerank(mesh, g)
    assert any(k[0] is pagerank_mod._pagerank_exec_spmd(
        g.plan, 0.85, 1e-7, 200, LPF_SYNC_DEFAULT)
        for k in context_mod._EXEC_CACHE)
    del g
    gc.collect()
    assert graph() is None


def test_lookups_and_stores_from_many_threads(monkeypatch):
    """Threads that look up and store at once keep the LRU within its
    capacity, and each finds only what was stored under its key."""
    size, workers = 8, min(64, (os.cpu_count() or 1) + 4)
    monkeypatch.setattr(context_mod, "_EXEC_CACHE_SIZE", size)
    errors = []

    def work(t):
        try:
            for i in range(500):
                key = ("stress", t, i % 12)
                entry = context_mod._exec_lookup(key)
                if entry is None:
                    context_mod._exec_store(key, (key, CostLedger()))
                elif entry[0] != key:
                    raise AssertionError((key, entry))
                with context_mod._EXEC_LOCK:
                    if len(context_mod._EXEC_CACHE) > size:
                        raise AssertionError(len(context_mod._EXEC_CACHE))
        except AssertionError as e:
            errors.append(e)

    threads = [threading.Thread(target=work, args=(t,))
               for t in range(workers)]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=120)
    finally:
        sys.setswitchinterval(interval)
        with context_mod._EXEC_LOCK:
            for key in [k for k in context_mod._EXEC_CACHE
                        if k[0] == "stress"]:
                del context_mod._EXEC_CACHE[key]
    assert not any(th.is_alive() for th in threads)
    assert not errors, errors[:3]
