"""The benchmark's files and each cell's path, rehearsed on the CPU.

* ``BENCHMARK.json`` keeps to the contract's names, units and keys, and
  every cell finds its configuration, mix and readers by name.
* A configuration that sets a program flag no committed one uses, a
  traffic mix with operations of its own and a per-layer metric, all
  living only in a temporary directory, are found and run with no
  existing file edited.
* Each cell runs its whole path at a tiny size. Off a TPU it reports no
  device metric, and ``run.py`` refuses to run at all.
"""
import json
import re
import shutil

import jax
import pytest

from chipbench_tiny import CELLS, ROOT, SEED, tiny_root

from chipbench import run, spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
DEVICE_METRICS = {"iter_us.bulk", "search_roofline.bulk", "idle_share.bulk"}


@pytest.fixture(scope="module")
def bench():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for entry in bench["configs"] + bench["workloads"] + bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(entry["name"]), entry["name"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m
        for cell in m["workloads"]:
            assert spec.reports(e2e[m["moves"]], cell), (m["name"], cell)
        if "roofline" in m["name"]:
            assert m["unit"] == "%"
    for w in bench["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        reported = [m for m in bench["end_to_end"] if spec.reports(m, w["name"])]
        assert "setup_s" in {m["name"] for m in reported} and len(reported) >= 2
        assert any(spec.reports(m, w["name"]) for m in bench["per_layer"])


@pytest.mark.parametrize("name", CELLS)
def test_each_cell_finds_its_parts_by_name(name):
    cell = spec.load_cell(ROOT, name)
    assert cell.config["reduced"] == [] and cell.traffic["loop"] in ("open", "closed")
    for m in cell.end_to_end + cell.per_layer:
        assert callable(spec.load_reader(ROOT, m["name"]))


def test_an_unknown_cell_is_refused(capsys):
    assert run.main(["--workload", "nope", "--seed", "1", "--seconds", "1"]) != 0
    assert "{" not in capsys.readouterr().out


def test_run_refuses_without_a_tpu(capsys):
    assert jax.devices()[0].platform != "tpu"
    code = run.main(["--workload", CELLS[0], "--seed", str(SEED),
                     "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert code != 0 and out.out.strip() == "" and "TPU" in out.err


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("trace", [False, True], ids=["end_to_end", "per_layer"])
@pytest.mark.parametrize("name", CELLS)
def test_tiny_rehearsal_of_each_cell(tiny, name, trace):
    cell = spec.load_cell(tiny, name)
    result = run.run_cell(cell, SEED, 2.0, trace, jax.devices())
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    wanted = {m["name"] for m in (cell.per_layer if trace else cell.end_to_end)}
    got = set(result["metrics"])
    assert got == wanted - DEVICE_METRICS, (got, wanted)
    assert not got & DEVICE_METRICS
    assert set(result["device"]) == {"platform", "kind", "count"}
    assert "breakdown" not in result
    if not trace:
        assert result["metrics"]["setup_s"]["value"] > 0
        recall = [v["value"] for k, v in result["metrics"].items() if k.startswith("recall_at_k")]
        assert len(recall) == 1 and 0 < recall[0] <= 1


INSERTS_OPS = '''"""Upserts: POST /v1/upsert of a new row near a corpus row.
Each has to be acknowledged with a slot of its own past the corpus."""
import json

import numpy as np


class Item:
    route = "/v1/upsert"

    def __init__(self, vector, label):
        self.vector, self.label = vector, label

    def payload(self, timeout_s):
        return json.dumps({"vector": self.vector.tolist(), "label": self.label,
                           "attrs": [0.5], "timeout_s": timeout_s}).encode()


def make_items(seed, data, n, mix, stream=1):
    rng = np.random.default_rng([seed, stream])
    picks = rng.integers(0, data.n, size=n)
    rows = data.rows(picks) + rng.standard_normal((n, data.d), dtype=np.float32) * 0.05
    return [Item(r, int(data.labels[p])) for r, p in zip(rows, picks)]


def warm_items(seed, data, mix):
    return make_items(seed, data, 1, mix, stream=3)


def answers_of(records):
    return [r.body if r.answered and r.body.get("ok") else None for r in records]


def compare(data, items, answers, limits, serve):
    slots = [a["slot"] for a in answers if a is not None]
    fresh = {s for s in slots if s >= data.n}
    return {"unacknowledged": {"value": sum(a is None for a in answers), "limit": 0,
                               "sense": "<="},
            "slots_not_fresh": {"value": len(slots) - len(fresh), "limit": 0, "sense": "<="}}
'''


def test_a_new_config_mix_ops_and_metric_are_found_without_editing(tmp_path):
    root = tiny_root(tmp_path)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    config = json.loads((root / "chipbench/configs/sift1m-exact.json").read_text())
    committed = [json.loads(p.read_text()) for p in (ROOT / "chipbench/configs").glob("*.json")]
    assert all("churn" not in c["serve"] for c in committed)
    # --churn: the program serves through its streaming executor, the only
    # one that takes upserts (the others answer 400)
    config["name"] = "toy-churn"
    config["serve"]["churn"] = 0.25
    (root / "chipbench/configs/toy-churn.json").write_text(json.dumps(config))
    mix = json.loads((root / "chipbench/workloads/bulk.json").read_text())
    mix.update(ops="inserts", clients=4, pool=64)
    mix["serve"]["max-wait"] = 0.005
    (root / "chipbench/workloads/inserts.json").write_text(json.dumps(mix))
    (root / "chipbench/ops/inserts.py").write_text(INSERTS_OPS)
    (root / "chipbench/metrics/answered_share.py").write_text(
        "def read(ctx):\n"
        "    return sum(r.answered for r in ctx.records) / len(ctx.records)\n")
    bench["configs"].append({"name": "toy-churn", "source": "a test",
                             "file": "chipbench/configs/toy-churn.json",
                             "reduced": [], "why": "a test"})
    bench["workloads"].append({"name": "toy.inserts", "config": "toy-churn",
                               "traffic": "inserts", "chips": 1, "why": "a test"})
    bench["per_layer"].append({"name": "answered_share.inserts", "unit": "ratio",
                               "better": "higher", "source": "host_clock", "layer": "a test",
                               "moves": "setup_s", "workloads": ["toy.inserts"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    before = {p.name: p.read_bytes() for p in (ROOT / "chipbench").rglob("*.py")}

    cell = spec.load_cell(root, "toy.inserts")
    assert "--churn" in cell.serve_flags() and cell.traffic["clients"] == 4
    result = run.run_cell(cell, SEED, 1.0, True, jax.devices())
    assert result["correct"] is True, result["checks"]
    assert set(result["checks"]) == {"unacknowledged", "slots_not_fresh"}
    assert result["attempted"] > 0
    assert result["metrics"]["answered_share.inserts"]["value"] == 1.0
    assert {p.name: p.read_bytes() for p in (ROOT / "chipbench").rglob("*.py")} == before
    shutil.rmtree(root)


def test_serve_flags_pass_through_as_written():
    assert spec.flags({"base-ef": 1024, "hybrid": True, "slo": False, "ladder": [8, 32],
                       "deadline-ms": None}) == [
        "--base-ef", "1024", "--hybrid", "--ladder", "8,32"]
    cell = spec.load_cell(ROOT, CELLS[0])
    args = run.serve_args(cell)
    assert (args.n, args.d, args.k_cap) == (cell.config["n"], cell.config["d"],
                                            cell.config["serve"]["k-cap"])


@pytest.mark.parametrize("flag", ["replicas", "distributed"])
def test_the_serve_flags_choose_the_serving_path(tmp_path, flag):
    """Two replicas behind the replica router, or the mesh executor, which
    partitions and builds its own graph: chosen by a flag, run whole."""
    root = tiny_root(tmp_path)
    path = root / "chipbench/configs/sift1m-exact.json"
    config = json.loads(path.read_text())
    config["serve"][flag] = 2 if flag == "replicas" else True
    path.write_text(json.dumps(config))
    cell = spec.load_cell(root, "sift1m-exact.steady")
    assert f"--{flag}" in cell.serve_flags()
    result = run.run_cell(cell, SEED, 1.0, True, jax.devices())
    assert result["correct"] is True, result["checks"]
    assert ("graph_build_s" in result["metrics"]) == (flag == "replicas")
    shutil.rmtree(root)
