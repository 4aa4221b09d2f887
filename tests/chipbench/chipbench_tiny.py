"""A copy of the benchmark at a size the CPU runs in seconds.

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and ``chipbench/`` into
``tmp`` and shrinks every configuration and mix there: the same cells,
files and readers, a few thousand rows of the real width and a few dozen requests.
"""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

TINY_CONFIG = dict(n=3000, d=128, kmeans_sample=3000)
TINY_SERVE = {"degree": 8, "sample-size": 128, "base-ef": 64, "base-iters": 256}
TINY_MIX = {"open": dict(rate_qps=15.0, workers=16), "closed": dict(clients=16, pool=256)}
SEED = 2**31 + 12345  # above 32 signed bits: run seeds may be that large
CELLS = [w["name"] for w in json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]


def _edit_json(path: Path, serve: dict, **changes) -> None:
    with open(path) as f:
        doc = json.load(f)
    doc.update(changes)
    doc["serve"] = {**doc["serve"], **serve}
    with open(path, "w") as f:
        json.dump(doc, f, indent=1)


def tiny_root(tmp: Path) -> Path:
    tmp = Path(tmp)
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(ROOT / "chipbench", tmp / "chipbench",
                    ignore=shutil.ignore_patterns(".jax_cache", ".trace", "__pycache__"))
    for path in (tmp / "chipbench" / "configs").glob("*.json"):
        _edit_json(path, TINY_SERVE, **TINY_CONFIG)
    for path in (tmp / "chipbench" / "workloads").glob("*.json"):
        with open(path) as f:
            loop = json.load(f)["loop"]
        _edit_json(path, {"ladder": [16]}, **TINY_MIX[loop])
    return tmp
