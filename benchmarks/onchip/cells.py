"""Find what a cell is made of, by the names in ``BENCHMARK.json``.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Each lives in a file of its own, found by name:

* ``configs/<config>.json``: the sizes, and ``system``, the name of the
  module ``systems/<system>.py`` that makes the inputs, makes the timed
  call and checks what it returned against ``references/<system>.py``;
* ``mixes/<traffic>.json``: the parameters the one traffic generator
  (``traffic.py``) reads;
* ``metrics/<metric>.py``: one reader per metric, with ``read(run)``
  returning a number, or None where it finds nothing to read.

Adding a cell, configuration, mix or metric is adding files and entries;
no existing file changes.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_module(path: pathlib.Path):
    """Import the Python file at ``path`` under a name of its own."""
    name = f"onchip_{path.parent.name}_{path.stem}"
    name = name.replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One workload with its configuration, mix, system and metrics."""

    def __init__(self, bench: dict, name: str, here: pathlib.Path = HERE):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.here = here
        self.chips = int(self.entry["chips"])
        with open(here / "configs" / f"{self.entry['config']}.json") as f:
            self.config = json.load(f)
        with open(here / "mixes" / f"{self.entry['traffic']}.json") as f:
            self.mix = json.load(f)
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _applies(m, name)]

    def system(self):
        return load_module(self.here / "systems"
                           / f"{self.config['system']}.py")

    def reader(self, metric: str):
        return load_module(self.here / "metrics" / f"{metric}.py")
