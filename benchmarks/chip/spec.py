"""What the benchmark runs, found by name.

``BENCHMARK.json`` at the root of the checkout names the cells.  Each
cell's configuration is the file its ``configs`` entry names, its traffic
mix is ``traffic/<traffic>.json``, and each metric, end-to-end or
per-layer, is read by ``metrics/<name>.py``, whose ``read(record)``
returns a number, or None where the run gave it nothing to read.  A later change adds a cell, a mix
or a metric by adding files and entries, and edits no file here.
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path

__all__ = ["ROOT", "HERE", "load_benchmark", "Cell", "metric_reader"]

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


class Cell:
    """One entry of ``workloads`` with everything it names."""

    def __init__(self, bench: dict, name: str, root: Path = ROOT,
                 here: Path = HERE) -> None:
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
        self.entry = cells[name]
        self.name = name
        configs = {c["name"]: c for c in bench["configs"]}
        cfg_entry = configs[self.entry["config"]]
        self.config = json.loads((Path(root) / cfg_entry["file"]).read_text())
        self.traffic = json.loads(
            (Path(here) / "traffic" / f"{self.entry['traffic']}.json")
            .read_text())
        self.chips = int(self.entry["chips"])

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]


def metric_reader(name: str, directory: Path = HERE / "metrics"):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = Path(directory) / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    if spec is None or not path.is_file():
        raise FileNotFoundError(f"no reader for metric {name!r} at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
