"""Cells, configurations, traffic mixes and metrics are found by name."""

import json
import shutil

import pytest

from harness import cells, compare


def test_every_cell_has_its_parts():
    bm = cells.benchmark()
    for w in bm["workloads"]:
        cell = cells.find(w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"]
        assert cell.reference().Reference
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(cell.metric_reader(m["name"]).read)
        assert cell.limits and set(cell.limits) <= set(compare.NUMBERS)


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        cells.find("no-such-model.train")


def test_new_cell_mix_and_metric_need_no_edit(tmp_path):
    """A later cell is new files and new entries: found without touching
    any file that is already there."""
    root = tmp_path / "checkout"
    shutil.copytree(cells.BENCH, root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = cells.benchmark()
    bm["workloads"].append({"name": "mamba2-780m.long", "config":
                            "mamba2-780m", "traffic": "long", "chips": 1,
                            "why": "x"})
    bm["per_layer"].append({"name": "steps_seen", "unit": "steps",
                            "better": "higher", "source": "host_clock",
                            "layer": "launcher loop",
                            "moves": "train_tokens_per_s",
                            "workloads": ["mamba2-780m.long"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    mix = json.loads((cells.BENCH / "traffic" / "train.json").read_text())
    (root / "bench" / "traffic" / "long.json").write_text(
        json.dumps(dict(mix, seq_len=8192)))
    (root / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(r):\n    return r['window']['steps']\n")
    cell = cells.find("mamba2-780m.long", root)
    assert cell.traffic["seq_len"] == 8192
    assert [m["name"] for m in cell.per_layer][-1] == "steps_seen"
    assert "steps_seen" not in [m["name"] for m in
                                cells.find("mamba2-780m.train",
                                           root).per_layer]
