"""Machine-readable benchmark runner — the perf trajectory across PRs.

``python -m benchmarks.run_all --json [DIR]`` runs every benchmark and
writes one ``BENCH_<name>.json`` per benchmark plus a
``BENCH_summary.json`` roll-up into DIR (default ``bench-results/``).
Each file carries the benchmark's structured rows (when its ``main``
returns them), its captured CSV stdout, wall-clock, and enough platform
metadata (jax version, device/core counts) to compare runs across
machines.  The nightly workflow uploads DIR as an artifact, so every
PR's perf numbers are recorded instead of scrolling away in logs.

``--fast`` mirrors ``benchmarks.run --fast`` (CI-friendly sizes);
``--only NAME`` runs a single benchmark.

Everything runs in this one process, which holds the device.  The
cross-process warm-start benchmark starts child processes that need the
device themselves, so it is not run from here: run it on its own with
``python benchmarks/warm_start.py``.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import platform
import sys
import time
import traceback

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")


def _jobs(fast: bool):
    from . import (allreduce, fft, hrelation, messages, pagerank,
                   program_replay, roofline, schedule_search,
                   serve_latency)
    return {
        "scheduler": lambda: schedule_search.main(),
        "hrelation": lambda: hrelation.main(),
        "messages": lambda: messages.main(),
        "allreduce": lambda: allreduce.main(
            log_ns=(16, 18) if fast else (18, 20, 22)),
        "fft": lambda: fft.main(max_log2=14 if fast else 18),
        "pagerank": lambda: pagerank.main(
            sizes=((1 << 10, 6),) if fast
            else ((1 << 12, 6), (1 << 14, 6))),
        "roofline": lambda: roofline.main(),
        "overlap": lambda: program_replay.main(),
        "serve": lambda: serve_latency.main(
            n_requests=40 if fast else 120),
    }


def _meta():
    import jax
    return {
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "devices": jax.device_count(),
        "cpu_count": os.cpu_count(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "unix_time": time.time(),
    }


def _jsonable(obj):
    try:
        json.dumps(obj)
        return obj
    except (TypeError, ValueError):
        return None


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true",
                    help="smaller sizes (CI-friendly)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--json", nargs="?", const="bench-results",
                    default=None, metavar="DIR",
                    help="write BENCH_<name>.json files into DIR")
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    meta = _meta()
    out_dir = args.json
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)

    summary = {"meta": meta, "benchmarks": {}}
    failed = []
    for name, job in _jobs(args.fast).items():
        if args.only and name != args.only:
            continue
        print(f"\n===== {name} =====")
        buf = io.StringIO()
        t0 = time.perf_counter()
        ok, rows, err = True, None, None
        try:
            with contextlib.redirect_stdout(buf):
                rows = _jsonable(job())
        except Exception:                      # report, keep going
            ok = False
            err = traceback.format_exc()
            failed.append(name)
        dt = time.perf_counter() - t0
        stdout = buf.getvalue()
        sys.stdout.write(stdout)
        if err:
            sys.stderr.write(err)
        record = {"name": name, "ok": ok, "seconds": dt, "rows": rows,
                  "stdout": stdout, "error": err, "meta": meta}
        summary["benchmarks"][name] = {"ok": ok, "seconds": dt}
        if out_dir:
            path = os.path.join(out_dir, f"BENCH_{name}.json")
            with open(path, "w") as f:
                json.dump(record, f, indent=2)
            print(f"# wrote {path}")
    if out_dir:
        with open(os.path.join(out_dir, "BENCH_summary.json"), "w") as f:
            json.dump(summary, f, indent=2)
    if failed:
        print(f"\nFAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
