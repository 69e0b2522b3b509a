"""Drive a whole run of a cell in this process, on the CPU, at the
configuration's rehearsal size, skipping the look for a chip."""

import argparse
import time

import jax

import cells
import run


def drive(workload: str, *, seed: int = 1234567891011, seconds: float = 0.3,
          control: int = 0, trace: int = 0, here=cells.HERE) -> dict:
    args = argparse.Namespace(
        workload=workload, seed=seed, seconds=seconds, trace=trace,
        control=control, rehearse=True, seeds=1)
    cell = cells.Cell(cells.benchmark(), workload, here=here)
    return run.run_once(cell, args, seed, jax.devices(), None, [],
                        time.perf_counter())
