"""What the benchmark runs, found by name.

``BENCHMARK.json`` at the root of the checkout names the cells.  Each
cell's configuration is the file its ``configs`` entry names, its traffic
mix is ``traffic/<traffic>.json``, and each metric, end-to-end or
per-layer, is read by ``metrics/<name>.py``, whose ``read(record)``
returns a number, or None where the run gave it nothing to read.

A configuration's file may name ``"reference"``, the module that judges
its served tokens (``Arch`` and ``logits_at(params, tokens, rows, arch,
quant=False)``, as ``reference.py`` has them), and ``"counts"``, the module
that counts its work (``prefill_flops(model, n)``, ``decode_flops(model,
keys)``, ``weight_bytes(model)`` and ``kv_bytes(model, keys)``, as
``flops.py`` has them): each a module file beside this one, found by its
name as a metric's reader is.  Without them it is ``reference.py`` and
``flops.py``.

A later change adds a cell, a mix or a metric, and a configuration of
another architecture with its own reference and counts, by adding files
and entries, and edits no file here.
"""

from __future__ import annotations

import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType

__all__ = ["ROOT", "HERE", "load_benchmark", "Cell", "metric_reader",
           "module"]

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

        def named(key: str, default: str) -> ModuleType:
            if key in self.config:
                return module(self.config[key], here)
            return module(default)

        #: the modules that judge and count this configuration
        self.reference = named("reference", "reference")
        self.counts = named("counts", "flops")

        def mine(m):
            return "workloads" not in m or name in m["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if mine(m)]
        self.per_layer = [m for m in bench["per_layer"] if mine(m)]


def metric_reader(name: str, directory: Path = HERE / "metrics"):
    """The ``read`` function of ``metrics/<name>.py``."""
    return module(name, directory).read


def module(name: str, directory: Path = HERE) -> ModuleType:
    """The module ``<directory>/<name>.py``, loaded once a process.  One
    beside this file is imported as a module of this package, so that it
    may import from its neighbours (``from .reference import ...``)."""
    path = Path(directory).resolve() / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no module {name!r} at {path}")
    if path.parent == HERE and name.isidentifier():
        return importlib.import_module(f"{__package__}.{name}")
    key = "chipbench_" + re.sub(r"\W", "_", str(path.with_suffix("")))
    if key not in sys.modules:
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        try:
            spec.loader.exec_module(mod)
        except BaseException:
            del sys.modules[key]
            raise
    return sys.modules[key]
