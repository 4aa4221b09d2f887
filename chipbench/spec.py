"""Finds a cell's parts by name.

``BENCHMARK.json`` at the root names the cells, configurations and metrics.
A cell's configuration is the file its ``configs`` entry gives; its traffic
mix is ``chipbench/workloads/<traffic>.json``; the mix's operations are the
module ``chipbench/ops/<ops>.py``; a metric's reader is
``chipbench/metrics/<name>.py``, or, for a name with a dot such as
``iter_us.bulk``, the reader of its stem (``iter_us.py``) where the full
name has none. A reader is a module with ``read(ctx) -> float | None``.

The program is configured by data too: a configuration's ``serve`` object
and its mix's ``serve`` object hold flags of the program's serving entry
point (``launch/serve.py``), passed through as they are written.
Adding a configuration, a mix, an operation kind or a metric is adding
files and entries.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, List

BENCH_FILE = "BENCHMARK.json"
WORKLOADS_DIR = Path("chipbench") / "workloads"
OPS_DIR = Path("chipbench") / "ops"
METRICS_DIR = Path("chipbench") / "metrics"


@dataclasses.dataclass
class Cell:
    root: Path
    name: str
    chips: int
    config: dict
    traffic: dict
    ops: ModuleType  # the mix's operations: requests, answers, comparison
    end_to_end: List[dict]  # the end-to-end metrics this cell reports
    per_layer: List[dict]  # the per-layer metrics this cell reports

    def serve_flags(self) -> List[str]:
        """The program's serve flags: the corpus the benchmark hands it,
        then the configuration's flags, then the mix's."""
        cfg = self.config
        corpus = {"n": cfg["n"], "d": cfg["d"], "labels": cfg["labels"]}
        return flags(corpus) + flags(cfg.get("serve", {})) + flags(self.traffic.get("serve", {}))


def flags(options: dict) -> List[str]:
    """``{"base-ef": 1024, "hybrid": true, "ladder": [8, 32]}`` ->
    ``["--base-ef", "1024", "--hybrid", "--ladder", "8,32"]``; a flag set to
    false or null is left out."""
    out: List[str] = []
    for key, value in options.items():
        if value is False or value is None:
            continue
        out.append(f"--{key}")
        if isinstance(value, list):
            out.append(",".join(str(v) for v in value))
        elif value is not True:
            out.append(str(value))
    return out


def reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def _module(path: Path, prefix: str) -> ModuleType:
    name = prefix + path.stem.replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[name] = module  # a dataclass in it looks its module up
    spec.loader.exec_module(module)
    return module


def load_ops(root: Path, name: str) -> ModuleType:
    """The operations module ``chipbench/ops/<name>.py`` under ``root``."""
    path = Path(root) / OPS_DIR / f"{name}.py"
    if not path.exists():
        raise FileNotFoundError(f"no operations module {name!r} under {path.parent}")
    return _module(path, "chipbench_ops_")


def load_cell(root: Path, name: str) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files read."""
    root = Path(root)
    bench = _json(root / BENCH_FILE)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no cell {name!r} in {BENCH_FILE}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = _json(root / configs[cell["config"]]["file"])
    traffic = _json(root / WORKLOADS_DIR / f"{cell['traffic']}.json")
    return Cell(
        root=root, name=name, chips=int(cell["chips"]), config=config, traffic=traffic,
        ops=load_ops(root, traffic["ops"]),
        end_to_end=[m for m in bench["end_to_end"] if reports(m, name)],
        per_layer=[m for m in bench["per_layer"] if reports(m, name)],
    )


def load_reader(root: Path, metric: str) -> Callable:
    """``read`` of the metric's reader module."""
    folder = Path(root) / METRICS_DIR
    path = folder / f"{metric}.py"
    if not path.exists():
        path = folder / f"{metric.split('.')[0]}.py"
    if not path.exists():
        raise FileNotFoundError(f"no reader for metric {metric!r} under {folder}")
    return _module(path, "chipbench_metric_").read
