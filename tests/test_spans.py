"""Spans: the facility, and the spans LPF's drivers cross per call."""

import collections
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import core as lpf
from repro.algorithms import bsp_fft, lpf_pagerank, partition_graph, rmat_graph
from repro.algorithms import fft as fft_mod
from repro.algorithms import pagerank as pagerank_mod
from repro.core import compat, span
from repro.core import spans as spans_mod


@pytest.fixture()
def events():
    """Every duration event recorded while the test runs, as
    (name, seconds, parent)."""
    seen = []

    def listen(name, seconds, **kw):
        seen.append((name, seconds, kw.get("parent")))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        yield seen
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)


def _spans(events):
    return [e for e in events if e[0].startswith("lpf.")]


def _open_spans():
    return getattr(spans_mod._open, "names", [])


def test_nesting_names_the_parent(events):
    with span("a"):
        with span("b"):
            with span("c"):
                pass
        with span("d"):
            pass
    assert [(n, p) for n, _, p in events] == [
        ("c", "b"), ("b", "a"), ("d", "a"), ("a", "")]
    assert all(s >= 0.0 for _, s, _ in events)
    outer = events[-1][1]
    assert all(s <= outer for _, s, _ in events)
    assert _open_spans() == []


def test_one_event_per_exit_also_when_the_body_raises(events):
    with pytest.raises(ZeroDivisionError):
        with span("outer"):
            with span("inner"):
                1 / 0
    assert [(n, p) for n, _, p in events] == [("inner", "outer"),
                                              ("outer", "")]
    assert _open_spans() == []
    with span("after"):
        pass
    assert events[-1][0] == "after" and events[-1][2] == ""


def test_span_is_written_into_the_profilers_trace(tmp_path, events):
    jax.profiler.start_trace(str(tmp_path))
    try:
        with span("lpf.test.outer"):
            with span("lpf.test.inner"):
                jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    pd = jax.profiler.ProfileData.from_file(path)
    found = {e.name: e for plane in pd.planes for line in plane.lines
             for e in line.events if e.name.startswith("lpf.test.")}
    assert set(found) == {"lpf.test.outer", "lpf.test.inner"}
    outer, inner = found["lpf.test.outer"], found["lpf.test.inner"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= (outer.start_ns
                                                  + outer.duration_ns)
    assert [n for n, _, _ in _spans(events)] == ["lpf.test.inner",
                                                 "lpf.test.outer"]


EXEC = {"lpf.exec.trace": 1, "lpf.exec.lower": 1, "lpf.exec.compile": 1,
        "lpf.exec.run": 1}

#: spans of a driver's first call, by driver and number of processes
PER_CALL = {
    ("fft", 1): {**EXEC, "lpf.fft.layout": 1},
    ("fft", 4): {**EXEC, "lpf.fft.layout": 1, "lpf.fft.scatter": 1,
                 "lpf.sync": 2, "lpf.flush": 2},
    ("pagerank", 1): {**EXEC, "lpf.pagerank.upload": 1, "lpf.sync": 1,
                      "lpf.flush": 1},
    ("pagerank", 4): {**EXEC, "lpf.pagerank.upload": 1, "lpf.sync": 5,
                      "lpf.flush": 3},
}

#: spans of every later call, which runs the executable exec_ kept;
#: bsp_fft scatters its layout over the processes when there are several
WARM = {
    ("fft", 1): {"lpf.exec.hit": 1, "lpf.exec.run": 1, "lpf.fft.layout": 1},
    ("fft", 4): {"lpf.exec.hit": 1, "lpf.exec.run": 1, "lpf.fft.layout": 1,
                 "lpf.fft.scatter": 1},
    ("pagerank", 1): {"lpf.exec.hit": 1, "lpf.exec.run": 1,
                      "lpf.pagerank.upload": 1},
    ("pagerank", 4): {"lpf.exec.hit": 1, "lpf.exec.run": 1,
                      "lpf.pagerank.upload": 1},
}

#: each driver's factory of the SPMD function exec_ keys on
FACTORY = {"fft": fft_mod._fft_exec_spmd,
           "pagerank": pagerank_mod._pagerank_exec_spmd}


def _call(driver, p):
    mesh = compat.make_mesh((p,), ("x",), devices=jax.devices()[:p])
    if driver == "fft":
        x = jnp.asarray(np.random.default_rng(0).standard_normal(256),
                        jnp.complex64)
        return lambda: bsp_fft(mesh, x).block_until_ready()
    edges = rmat_graph(128, 400, seed=3)
    g = partition_graph(edges, 128, p)
    return lambda: lpf_pagerank(mesh, g)


@pytest.mark.parametrize("warm", [False, True], ids=["cold", "warm"])
@pytest.mark.parametrize("driver,p", sorted(PER_CALL))
def test_driver_spans_per_call(events, driver, p, warm):
    call = _call(driver, p)
    if warm:
        call()                             # plans and compiles once
        del events[:]
        calls, expect = 2, WARM[(driver, p)]
    else:
        # a new SPMD function, for which exec_ has kept nothing
        FACTORY[driver].cache_clear()
        calls, expect = 1, PER_CALL[(driver, p)]
    for _ in range(calls):
        call()
    got = collections.Counter(n for n, _, _ in _spans(events))
    assert got == {k: calls * v for k, v in expect.items()}
    parents = {(n, par) for n, _, par in _spans(events)}
    # LPF's planning happens while exec_ traces; the rest is top level
    assert {par for n, par in parents if n in ("lpf.sync", "lpf.flush")} \
        <= {"lpf.exec.trace"}
    assert {par for n, par in parents
            if n not in ("lpf.sync", "lpf.flush")} == {""}
    assert _open_spans() == []


def _spmd(ctx, s, p, x):
    ctx.resize_memory_register(1)
    ctx.resize_message_queue(p)
    buf = ctx.register_global("buf", x)
    ctx.put(buf, buf, to=lambda q: (q + 1) % p)
    ctx.sync(label="shift")
    return ctx.value(buf)


def test_exec_stages_and_the_eager_call_agree(events):
    mesh = compat.make_mesh((4,), ("x",), devices=jax.devices()[:4])
    x = jnp.arange(4.0)
    staged = lpf.exec_(mesh, _spmd, x, in_specs=P("x"), out_specs=P("x"))
    eager = lpf.exec_(mesh, _spmd, x, in_specs=P("x"), out_specs=P("x"),
                      jit=False)
    np.testing.assert_array_equal(np.asarray(staged), [3.0, 0.0, 1.0, 2.0])
    np.testing.assert_array_equal(np.asarray(eager), np.asarray(staged))
    names = [(n, par) for n, _, par in _spans(events)]
    assert names == [("lpf.sync", "lpf.exec.trace"), ("lpf.exec.trace", ""),
                     ("lpf.exec.lower", ""), ("lpf.exec.compile", ""),
                     ("lpf.exec.run", ""),
                     ("lpf.sync", "lpf.exec.run"), ("lpf.exec.run", "")]


def test_exec_inside_a_callers_jit_has_no_stages(events):
    mesh = compat.make_mesh((4,), ("x",), devices=jax.devices()[:4])
    x = jnp.arange(4.0)
    out = jax.jit(lambda v: lpf.exec_(mesh, _spmd, v, in_specs=P("x"),
                                      out_specs=P("x")))(x)
    np.testing.assert_array_equal(np.asarray(out), [3.0, 0.0, 1.0, 2.0])
    assert [(n, par) for n, _, par in _spans(events)] == [("lpf.sync", "")]


def test_exec_error_at_trace_time_closes_its_spans(events):
    mesh = compat.make_mesh((4,), ("x",), devices=jax.devices()[:4])

    def bad(ctx, s, p, x):
        ctx.resize_memory_register(1)
        ctx.resize_message_queue(p)
        buf = ctx.register_global("buf", x)
        ctx.put(buf, buf, to=lambda q: q + p)    # no such process
        ctx.sync()
        return ctx.value(buf)

    with pytest.raises(lpf.LPFFatalError):
        lpf.exec_(mesh, bad, jnp.arange(4.0), in_specs=P("x"),
                  out_specs=P("x"))
    assert [n for n, _, _ in _spans(events)] == ["lpf.exec.trace"]
    assert _open_spans() == []
