"""The share of exec_ calls that ran a kept executable: the reader on
known events, and each cell that lists it in a rehearsed traced run."""

import argparse
import time

import jax
import pytest

import cells
import run

HIT_SHARE = {"fft.exec_hit_share": "fft-c64-2e28.p1",
             "fft.exec_hit_share.p4": "fft-c64-2e28.p4",
             "pagerank.exec_hit_share": "pagerank-rmat-s20.p1"}


def _run(names):
    events = [(name, 0.0, 0.001) for name in names]
    return run.Run("cell", {}, {}, 0.0, [(0.0, 0.05, 0.1), (0.1, 0.15, 0.2)],
                   events, {}, None, None)


@pytest.mark.parametrize("metric", sorted(HIT_SHARE))
@pytest.mark.parametrize("names,share", [
    (["lpf.exec.hit", "lpf.exec.run"] * 2, 100.0),
    (["lpf.exec.trace", "lpf.exec.lower", "lpf.exec.compile",
      "lpf.exec.run"] * 2, 0.0),
    (["lpf.exec.trace", "lpf.exec.run", "lpf.exec.hit", "lpf.exec.run",
      "lpf.exec.hit", "lpf.exec.hit"], 75.0),
    (["lpf.exec.run", "/jax/core/compile/jaxpr_trace_duration"], None),
])
def test_reader_counts_hits_against_traces(metric, names, share):
    read = cells.load_module(cells.HERE / "metrics" / f"{metric}.py").read
    assert read(_run(names)) == share


def test_hit_shares_are_listed():
    listed = {m["name"]: m for m in cells.benchmark()["per_layer"]}
    for name, cell in HIT_SHARE.items():
        m = listed[name]
        assert (m["source"], m["unit"], m["better"], m["layer"]) == (
            "program_counter", "%", "higher", "host path")
        assert m["workloads"] == [cell]


@pytest.mark.parametrize("metric,workload", sorted(HIT_SHARE.items()))
def test_a_warm_window_runs_kept_executables(metric, workload):
    events = []

    def listen(name, seconds, **_):
        events.append((name, time.perf_counter(), seconds))

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        args = argparse.Namespace(
            workload=workload, seed=2**31 + 91, seconds=0.3, trace=1,
            control=0, rehearse=True, seeds=1)
        cell = cells.Cell(cells.benchmark(), workload)
        res = run.run_once(cell, args, args.seed, jax.devices(), None,
                           events, time.perf_counter())
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert res["correct"], res["checks"]
    assert res["metrics"][metric]["value"] == 100.0
