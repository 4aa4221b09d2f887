"""chip_smoke.py's phases at a tiny size on the CPU, and its refusal to
run anywhere but a TPU."""
import importlib.util
import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(smoke, capsys):
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_phases_at_tiny_size(smoke):
    args = smoke.smoke_args([
        "--n", "3000", "--d", "16", "--degree", "8", "--sample-size", "128",
        "--ladder", "8", "--fuse", "off", "--base-ef", "64", "--base-iters", "256",
    ])
    corpus, seconds = smoke.make_deployment(args)
    assert set(seconds) == {"data", "kmeans"}
    graph, build_s = smoke.build_graph(args, corpus)
    assert set(build_s) == {"graph_build", "reverse_edges"}
    assert graph.neighbors.shape == (3000, 8)
    items = smoke.workload(corpus, args)
    assert {it.kind for it in items} == {"equal", "unequal", "range"}
    truth = smoke.exact_answers(corpus, items, args.k_cap)

    answers = {}
    for fuse in ("off", "on"):
        args.fuse = fuse
        runtime, compiled, _ = smoke.start_runtime(args, corpus, graph)
        assert compiled == 4  # 2 families x 2 tiers x 1 bucket
        answers[fuse] = smoke.serve_requests(runtime, items)
        quality = smoke.check_answers(corpus, items, answers[fuse], truth)
        assert quality["all"]["recall"] >= smoke.MIN_RECALL, quality
        assert quality["equal"]["fill"] == 1.0
    # off the chip both paths run the same jnp oracle: identical answers
    assert smoke._ids_differ(answers["off"], answers["on"]) == (0, 0)


def test_check_answers_rejects_a_constraint_violation(smoke):
    args = smoke.smoke_args(["--n", "500", "--d", "8", "--labels", "4"])
    corpus, _ = smoke.make_deployment(args)
    items = [it for it in smoke.workload(corpus, args) if it.kind == "equal"]
    item = items[0]
    labels = np.asarray(corpus.labels)
    wrong = np.flatnonzero(labels != labels[0])  # some other label
    row = np.asarray(item.operand, np.uint32)
    bad = [int(i) for i in wrong if not (row[labels[i] // 32] >> (labels[i] % 32)) & 1]
    answer = {"ids": bad[: item.k], "filled": item.k, "error": None}
    with pytest.raises(smoke.SmokeFailure, match="violate"):
        smoke.check_answers(corpus, [item], [answer], [np.asarray(bad[: item.k])])
    assert json.dumps(answer)  # the payload shape the front-end returns
