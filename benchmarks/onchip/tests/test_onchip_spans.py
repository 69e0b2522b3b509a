"""The metrics that read the program's own spans: each reader on known
events, and each in a rehearsed traced run of every cell it lists."""

import argparse
import time

import jax
import pytest

import cells
import run

NEW = {
    "fft.exec_prepare_ms": ("lpf.exec.trace", "lpf.exec.lower",
                            "lpf.exec.compile"),
    "fft.exec_prepare_ms.p4": ("lpf.exec.trace", "lpf.exec.lower",
                               "lpf.exec.compile"),
    "fft.layout_ms": ("lpf.fft.layout",),
    "fft.lpf_plan_ms.p4": ("lpf.sync", "lpf.flush"),
    "pagerank.exec_prepare_ms": ("lpf.exec.trace", "lpf.exec.lower",
                                 "lpf.exec.compile"),
    "pagerank.lpf_plan_ms": ("lpf.sync", "lpf.flush"),
    "pagerank.upload_ms": ("lpf.pagerank.upload",),
}

#: what a window of two calls could hold: every span of the program, and
#: JAX's own events beside them
EVENTS = [
    ("lpf.fft.layout", 0.0, 0.001), ("lpf.pagerank.upload", 0.0, 0.004),
    ("lpf.sync", 0.0, 0.0005), ("lpf.sync", 0.0, 0.0005),
    ("lpf.flush", 0.0, 0.002),
    ("lpf.exec.trace", 0.0, 0.003), ("lpf.exec.lower", 0.0, 0.0065),
    ("lpf.exec.compile", 0.0, 0.020), ("lpf.exec.run", 0.0, 0.100),
    ("/jax/core/compile/jaxpr_trace_duration", 0.0, 0.5),
    ("lpf.exec.trace", 0.0, 0.003), ("lpf.exec.lower", 0.0, 0.0065),
    ("lpf.exec.compile", 0.0, 0.020), ("lpf.exec.run", 0.0, 0.100),
]


def _run(events):
    return run.Run("cell", {}, {}, 0.0, [(0.0, 0.05, 0.1), (0.1, 0.15, 0.2)],
                   events, {}, None, None)


@pytest.mark.parametrize("metric,expect_ms", [
    ("fft.exec_prepare_ms", 29.5), ("fft.exec_prepare_ms.p4", 29.5),
    ("fft.layout_ms", 0.5), ("fft.lpf_plan_ms.p4", 1.5),
    ("pagerank.exec_prepare_ms", 29.5), ("pagerank.lpf_plan_ms", 1.5),
    ("pagerank.upload_ms", 2.0)])
def test_reader_sums_its_spans_per_call(metric, expect_ms):
    read = cells.load_module(cells.HERE / "metrics" / f"{metric}.py").read
    assert read(_run(EVENTS)) == pytest.approx(expect_ms)
    # a program without the spans: nothing to read
    jax_only = [e for e in EVENTS if not e[0].startswith("lpf.")]
    assert read(_run(jax_only)) is None
    # only its own spans count
    mine = [e for e in EVENTS if e[0] in NEW[metric]]
    assert read(_run(mine)) == pytest.approx(expect_ms)


def test_new_metrics_are_listed():
    bench = cells.benchmark()
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert listed[name]["source"] == "program_counter"
        assert listed[name]["unit"] == "ms"
        assert listed[name]["workloads"]


def _traced(workload):
    """A rehearsed traced run of ``workload`` with the benchmark's own
    listener: every duration event in ``run.events``."""
    events = []

    def listen(name, seconds, **_):
        events.append((name, time.perf_counter(), seconds))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        args = argparse.Namespace(
            workload=workload, seed=2**31 + 77, seconds=0.3, trace=1,
            control=0, rehearse=True, seeds=1)
        cell = cells.Cell(cells.benchmark(), workload)
        res = run.run_once(cell, args, args.seed, jax.devices(), None,
                           events, time.perf_counter())
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    return cell, res


@pytest.mark.parametrize(
    "workload", [w["name"] for w in cells.benchmark()["workloads"]])
def test_traced_run_reads_every_new_metric(workload):
    cell, res = _traced(workload)
    assert res["correct"], res["checks"]
    wanted = {m["name"] for m in cell.per_layer} & set(NEW)
    assert wanted
    got = {k: v["value"] for k, v in res["metrics"].items()}
    for name in wanted:
        assert got.get(name, 0.0) > 0.0, (name, got)
    # nested spans read no more than what holds them
    if "fft.host_ms" in got:
        assert got["fft.exec_prepare_ms"] + got["fft.layout_ms"] <= \
            got["fft.host_ms"]
    if "fft.lpf_plan_ms.p4" in got:
        assert got["fft.lpf_plan_ms.p4"] <= got["fft.exec_prepare_ms.p4"]
    if "pagerank.lpf_plan_ms" in got:
        assert got["pagerank.lpf_plan_ms"] <= got["pagerank.exec_prepare_ms"]
