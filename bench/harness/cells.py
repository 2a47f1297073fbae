"""Finding a cell's parts by name.

``BENCHMARK.json`` at the checkout's root names each cell's configuration,
traffic mix and metrics. Each part is a file of its own under ``bench/``:

* ``configs/<config>.json``: the sizes as run, with the module of its
  parameter shapes and model FLOPs (``shapes``) and the plain reference
  module (``reference``) that it names beside it;
* ``traffic/<mix>.json``: the parameters the general generator reads;
* ``metrics/<metric>.py``: a reader with ``read(r) -> float | None``;
* ``limits/<workload>.json``: the limit of each number compared.

A later cell, mix or metric is a new file and a new entry; no file here
changes.
"""

from __future__ import annotations

import functools
import importlib.util
import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def _load_module(path: Path, name: str):
    if not path.is_file():
        raise FileNotFoundError(f"no module {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def _shapes_at(path: Path):
    return _load_module(path, f"shapes_{path.stem}")


def shapes(config: dict, bench: Path = BENCH):
    """The module of the configuration's parameter shapes and model FLOPs,
    ``configs/<shapes>``: ``leaves(c)``, ``forward_flops_per_sequence(c,
    seq_len)``, ``matmul_param_count(c)``. Loaded once per file."""
    return _shapes_at(bench / "configs" / config["shapes"])


@dataclass
class Cell:
    workload: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    limits: Dict[str, float] = field(default_factory=dict)
    bench: Path = BENCH

    @property
    def name(self) -> str:
        return self.workload["name"]

    def shapes(self):
        return shapes(self.config, self.bench)

    def reference(self):
        return _load_module(self.bench / "configs" / self.config["reference"],
                            f"ref_{self.config['name']}")

    def metric_reader(self, name: str):
        return _load_module(self.bench / "metrics" / f"{name}.py",
                            f"m_{name}")


def benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _reports(metric: dict, workload: str, e2e_names) -> bool:
    if "workloads" in metric:
        return workload in metric["workloads"]
    return metric.get("moves", metric["name"]) in e2e_names


def find(workload: str, root: Path = ROOT) -> Cell:
    """The cell named ``workload`` with every part it needs; KeyError or
    FileNotFoundError when one is missing."""
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    entry = configs[w["config"]]
    config = json.loads((root / entry["file"]).read_text())
    bench = root / "bench"
    shapes(config, bench)
    traffic = json.loads(
        (bench / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bm["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"] if _reports(m, workload, names)]
    limits_file = bench / "limits" / f"{workload}.json"
    limits = (json.loads(limits_file.read_text())["limits"]
              if limits_file.is_file() else {})
    return Cell(w, config, traffic, e2e, per_layer, limits, bench)
