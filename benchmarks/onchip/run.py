"""Run one cell of the on-chip benchmark and print its result.

    python3 benchmarks/onchip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout that holds ``BENCHMARK.json`` and the
program under ``src/``.  The cell's configuration, traffic mix, system
module and metric readers are found by name (see ``cells.py``).  A run
sets up the cell from the seed and warms up every shape (``setup_s``),
drives the timed call in a closed loop for ``--seconds``
(``traffic.py``), reads the memory peak, frees the program's state, and
compares what the window produced with the plain reference.  The last
line on stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window), ``device``, ``breakdown`` with ``--trace 1``, and last
``checks``: each number compared, with its limit.  The same comparisons are the last lines on
stderr.

Without a TPU, or with fewer chips than the cell asks for, it exits 2
and prints no result.  Options that checks never pass:

* ``--rehearse``: run on the CPU (4 virtual devices) at the tiny sizes
  of the configuration's ``rehearse`` entry;
* ``--control 1``: put the reference, one precision step below the
  configuration's, in the program's place (it must come out not
  correct);
* ``--seeds N``: run seeds ``seed .. seed+N-1`` one after another in
  this process, printing one result line each (readings for limits);
  only the first warms up.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

import cells  # noqa: E402

COMPILE_CACHE = cells.ROOT / ".jax_cache"


@dataclasses.dataclass
class Run:
    """What metric readers read."""
    cell: str
    config: dict
    mix: dict
    setup_s: float
    calls: list          # [(called, returned, done)] host seconds
    events: list         # [(jax monitoring event, host seconds, duration)]
    info: dict           # the system module's own counts
    trace: object        # devtrace.Summary, or None without --trace 1
    peak: dict           # peaks.lookup(device_kind), or None (rehearsal)

    @property
    def window_s(self) -> float:
        return self.calls[-1][2] - self.calls[0][0]

    def event_total(self, name: str) -> tuple:
        """(count, total seconds) of a JAX event inside the window."""
        durs = [d for n, _, d in self.events if n == name]
        return len(durs), sum(durs)


def _fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr)
    return 2


def _parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--seeds", type=int, default=1)
    return ap.parse_args(argv)


def _window_trace(jax, directory):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(directory, profiler_options=opts)


def _report(run: Run) -> None:
    """What the window did on the host and on the chips, for the log."""
    per = max(1, len(run.calls))
    for name in sorted({n for n, _, _ in run.events}):
        count, total = run.event_total(name)
        print(f"window: {name} {count / per:.2f}/call "
              f"{1e3 * total / per:.3f} ms/call", file=sys.stderr)
    if run.trace is not None:
        classes = {k: round(v / per * 1e3, 3)
                   for k, v in sorted(run.trace.class_s.items())}
        print(f"window: device ms/call by class {classes}", file=sys.stderr)


def run_once(cell, args, seed, devices, peak, events, t_start,
             warm: bool = True) -> dict:
    import jax
    import devtrace
    from traffic import closed_loop

    t_system = time.perf_counter()
    system = cell.system().System(cell.config, cell.mix, devices, seed,
                                  args.rehearse)
    t_warm = time.perf_counter()
    call = system.control if args.control else system.call
    if warm:
        system.wait(call(-1))             # compiles every shape it uses
    setup_s = time.perf_counter() - t_start
    print(f"setup: {t_system - t_start:.3f} s to the system, "
          f"{t_warm - t_system:.3f} s inputs, "
          f"{t_start + setup_s - t_warm:.3f} s warm-up", file=sys.stderr)

    tdir = tempfile.mkdtemp(prefix="onchip-trace-") if args.trace else None
    if tdir:
        _window_trace(jax, tdir)
    mark = len(events)
    failed, calls = 0, []
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            calls = closed_loop(call, system.wait, system.keep,
                                args.seconds)
    except Exception:                     # the program failed: report it
        traceback.print_exc()
        failed = 1
    window_events = events[mark:]
    if tdir:
        jax.profiler.stop_trace()
    used = devices[:int(cell.mix["devices"])]
    mem = [d.memory_stats() or {} for d in used]
    peak_bytes = max(m.get("peak_bytes_in_use", 0) for m in mem)

    system.release()
    checks = system.check() if calls and not failed else []
    correct = bool(checks) and all(v <= lim for _, v, lim in checks)

    summary = None
    if tdir:
        paths = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        summary = devtrace.reduce(devtrace.load_xplane(paths[0]))
        shutil.rmtree(tdir, ignore_errors=True)

    run = Run(cell.name, cell.config, cell.mix, setup_s, calls,
              window_events, system.info(), summary, peak)
    _report(run)
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = cell.reader(m["name"]).read(run) if calls else None
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": int(peak_bytes)}
    result = {"correct": correct, "attempted": len(calls) + failed,
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    result["checks"] = {name: {"value": v, "limit": lim}
                        for name, v, lim in checks}
    return result


def main(argv=None) -> int:
    args = _parse(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ.setdefault(
            "XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    try:
        cell = cells.Cell(cells.benchmark(), args.workload)
    except (OSError, KeyError, ValueError) as e:
        return _fail(f"cannot load workload {args.workload!r}: {e}")
    src = cells.ROOT / "src"
    if not (src / "repro").is_dir():
        return _fail(f"no program at {src}")
    sys.path.insert(0, str(src))

    import jax
    import peaks

    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    devices = jax.devices()
    # set-up is counted from here: the TPU runtime's start before it swings
    # by seconds from run to run, and neither the program nor the cell
    # shapes it; the program is imported, and everything made, after it
    t_start = time.perf_counter()
    print(f"setup: {t_start - T_START:.3f} s to the devices (not counted)",
          file=sys.stderr)
    if devices[0].platform != "tpu" and not args.rehearse:
        return _fail(f"no TPU found (JAX platform {devices[0].platform!r})")
    need = max(cell.chips, int(cell.mix["devices"]))
    if len(devices) < need:
        return _fail(f"{args.workload} needs {need} chips, JAX sees "
                     f"{len(devices)}")
    try:
        peak = None if args.rehearse else peaks.lookup(devices[0].device_kind)
    except KeyError as e:
        return _fail(str(e))

    events = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, dur, **_: events.append((name, time.perf_counter(),
                                              dur)))
    for k in range(args.seeds):
        result = run_once(cell, args, args.seed + k, devices, peak, events,
                          t_start, warm=k == 0)
        for name, c in result["checks"].items():
            print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
