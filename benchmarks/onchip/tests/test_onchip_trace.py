"""The reduction from a trace to busy time, idle gaps and op classes."""

import json
import pathlib

import pytest

import devtrace

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _synthetic():
    device = {"/device:TPU:0": [(0, 10, "fft.1"), (10, 12, "copy.2"),
                                (30, 10, "all-to-all.3"),
                                (60, 5, "fusion.4")]}
    host = [(0, 50, "bench.window", "main"),
            (20, 10, "bench.call", "main"),
            (22, 6, "PjitFunction(wrapped)", "main"),
            (23, 2, "compile", "main"),
            (21, 8, "other thread", "worker")]
    return devtrace.Trace(device, host)


def test_synthetic_trace():
    s = devtrace.reduce(_synthetic())
    ns = 1e-9
    assert s.window_s == pytest.approx(50 * ns)
    assert s.busy_s == pytest.approx(32 * ns)        # [0, 22] and [30, 40]
    assert s.idle_share == pytest.approx(18 / 50)
    assert s.class_s == pytest.approx({"fft": 10 * ns, "copy": 12 * ns,
                                       "collective": 10 * ns})
    # [22, 30] idle: midpoint 26 lies in PjitFunction, not in compile
    assert s.gap_s == pytest.approx({
        "bench.call/PjitFunction(wrapped)": 8 * ns,
        "bench.window": 10 * ns})
    b = s.breakdown()
    assert b["device_ops"][0] == ["copy.2", pytest.approx(12 * ns)]
    assert {g[0] for g in b["idle_gaps"]} == set(s.gap_s)


def test_chips_are_averaged():
    t = _synthetic()
    t.device["/device:TPU:1"] = [(0, 50, "fft.1")]
    s = devtrace.reduce(t)
    assert s.chips == 2
    assert s.busy_s == pytest.approx((32 + 50) / 2 * 1e-9)


@pytest.mark.parametrize("text,name,cls", [
    ('%all-to-all.1 = c64[4,16]{1,0} all-to-all(c64[4,16]{1,0} %p), '
     'dimensions={0}', "all-to-all.1", "collective"),
    ("%fft.3 = c64[8]{0} fft(c64[8]{0} %p), fft_type=FFT", "fft.3", "fft"),
    ('%custom-call.2 = c64[268435456]{0:T(1024)} custom-call(f32[268435456]'
     '{0:T(1024)} %bitcast.18), custom_call_target="X64Combine"',
     "custom-call.2[X64Combine]", "copy"),
    ("%convolution_subtract_fusion.3 = f32[128,128]{3,0:T(8,128)} fusion("
     "f32[128,128]{3,0:T(8,128)} %fusion.12), kind=kOutput, "
     "calls=%fused_computation.21",
     "convolution_subtract_fusion.3[kOutput]", "matmul"),
    ("%fusion.14 = f32[16777216]{0:T(1024)} fusion(f32[1048577]{0:T(1024)S(1)}"
     " %pad_maximum_fusion.2, s32[16777216]{0:T(1024)S(1)} %b), "
     "kind=kCustom, calls=%fused_computation.clone.clone",
     "fusion.14[kCustom]", "gather_scatter"),
    ("%copy-done = f32[1048576]{0:T(1024)S(1)} copy-done((f32[1048576]"
     "{0:T(1024)S(1)}, u32[]{:S(2)}) %copy-start)", "copy-done", "copy"),
    ("%fusion.34 = (f32[128,128]{0,2,1:T(8,128)}, f32[128]{0}) fusion("
     "u32[128,128]{1,0:T(8,128)S(1)} %fusion.49), kind=kLoop, "
     "calls=%fused_computation.50", "fusion.34[kLoop]", "other"),
    ("%mul.9 = f32[16]{0:T(1024)S(1)} multiply(f32[16]{0:T(1024)} %a, "
     "f32[16]{0:T(1024)} %b)", "mul.9", "other"),
])
def test_op_names_and_classes(text, name, cls):
    assert devtrace.op_name(text) == name
    assert devtrace.op_class(text) == cls


def test_container_ops_count_in_busy_time_only():
    device = {"tpu": [(0, 100, "%while = (f32[4]) while(f32[4] %a)"),
                      (10, 30, "%fusion.1 = f32[4] fusion(), kind=kCustom"),
                      (50, 40, "%fusion.2 = f32[4] fusion(), kind=kLoop")]}
    s = devtrace.reduce(devtrace.Trace(device, []))
    assert s.busy_s == pytest.approx(100e-9)
    assert s.op_s == pytest.approx({"fusion.1[kCustom]": 30e-9,
                                    "fusion.2[kLoop]": 40e-9})


def _brute_busy(trace, w0, w1):
    """Busy time by marking every nanosecond: the slow, obvious way."""
    total = 0
    for events in trace.device.values():
        on = bytearray(w1 - w0)
        for s, d, _ in events:
            for t in range(max(s, w0), min(s + d, w1)):
                on[t - w0] = 1
        total += sum(on)
    return total / len(trace.device) / 1e9


def test_recorded_trace():
    """Three transforms of a TPU v5e trace of the FFT cell at 2^28."""
    trace = devtrace.load(DATA / "fft_p1_recorded.json")
    s = devtrace.reduce(trace)
    bench = [(h[0], h[0] + h[1]) for h in trace.host
             if h[2].startswith(devtrace.BENCH_PREFIX)]
    w0, w1 = min(b[0] for b in bench), max(b[1] for b in bench)
    assert s.window_s == pytest.approx((w1 - w0) / 1e9)
    step = 1000                  # compare on a 1 us grid, to keep it fast
    coarse = devtrace.Trace(
        {k: [(int(s_ // step), max(1, int(d // step)), n) for s_, d, n in v]
         for k, v in trace.device.items()}, [])
    assert s.busy_s == pytest.approx(
        _brute_busy(coarse, int(w0 // step), int(w1 // step)) * step,
        rel=0.02)
    assert 0.0 < s.idle_share < 1.0
    assert sum(s.gap_s.values()) == pytest.approx(s.window_s - s.busy_s)
    # XLA's TPU FFT: complex split and joined, transformed by convolutions
    assert s.class_s["matmul"] > 0.0 and s.class_s["copy"] > 0.0
