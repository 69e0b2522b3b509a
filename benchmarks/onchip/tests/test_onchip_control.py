"""The comparison that decides ``correct`` passes the program and fails
its control (the reference one precision step below, in the program's
place), on every cell, at the rehearsal size."""

import pytest

import cells
from drive import drive

CELLS = [w["name"] for w in cells.benchmark()["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_program_is_correct(workload):
    res = drive(workload)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert list(res)[-1] == "checks"


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("seed", [3, 2**31 + 5, 987654321987])
def test_control_is_not_correct(workload, seed):
    res = drive(workload, seed=seed, control=1)
    assert res["checks"] and not res["correct"], res["checks"]
