"""A configuration, a traffic mix and a metric added as new files are
found by name, with no existing file of the harness edited."""

import hashlib
import json
import shutil

import cells
from drive import drive


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts}


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    here = tmp_path / "onchip"
    shutil.copytree(cells.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _digests(here)

    cfg = json.loads((here / "configs" / "fft-c64-2e28.json").read_text())
    cfg["rehearse"] = {"log2n": 10}
    (here / "configs" / "fft-small.json").write_text(json.dumps(cfg))
    (here / "mixes" / "two.p2.json").write_text(
        json.dumps({"devices": 2}))
    (here / "metrics" / "calls_made.py").write_text(
        "def read(run):\n    return float(len(run.calls))\n")

    bench = cells.benchmark()
    bench["configs"].append({"name": "fft-small"})
    bench["workloads"].append({"name": "fft-small.two", "config": "fft-small",
                               "traffic": "two.p2", "chips": 4})
    bench["end_to_end"].append({"name": "calls_made", "unit": "calls",
                                "workloads": ["fft-small.two"]})
    monkeypatch.setattr(cells, "benchmark", lambda root=None: bench)

    cell = cells.Cell(bench, "fft-small.two", here=here)
    assert cell.mix == {"devices": 2}
    assert cell.config["rehearse"] == {"log2n": 10}
    assert [m["name"] for m in cell.end_to_end] == ["setup_s", "calls_made"]

    res = drive("fft-small.two", here=here)
    assert res["correct"], res["checks"]
    assert res["metrics"]["calls_made"]["value"] == res["attempted"]
    assert set(res["metrics"]) == {"setup_s", "calls_made"}
    # nothing that was there before changed
    after = _digests(here)
    assert {k: after[k] for k in before} == before
