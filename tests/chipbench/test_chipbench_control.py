"""The comparison that decides ``correct`` fails the control and the
faults a serving cell can have, on the CPU at a tiny size.

The control is the plain reference computed one precision below what the
configuration states, put in the program's place. The faults are planted
under a whole run, in the result of the executor's compiled search: an
answer altered where it is produced, and half of each batch left out.
"""
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench_tiny import CELLS, SEED, tiny_root

from chipbench import check, control, run, spec


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = tiny_root(tmp_path_factory.mktemp("bench"))
    # enough load that the batches carry several requests
    path = root / "chipbench" / "workloads" / "steady.json"
    mix = json.loads(path.read_text())
    mix["rate_qps"] = 120.0
    path.write_text(json.dumps(mix))
    return root


@pytest.mark.parametrize("name", CELLS)
def test_control_fails_where_the_program_holds(root, name):
    cell = spec.load_cell(root, name)
    program = run.run_cell(cell, SEED, 2.0, False, jax.devices())
    assert program["correct"] is True, program["checks"]
    r = control.readings(cell, SEED, 2.0)
    assert not check.holds(r["control"]), r["control"]
    assert r["control"]["dist_gap"]["value"] > r["control"]["dist_gap"]["limit"]


def _alter_answer(res):
    """Each lane's nearest id replaced by the next row's id."""
    n_rows = 3000  # the tiny corpus
    ids = jnp.where(res.ids[:, :1] >= 0, (res.ids[:, :1] + 1) % n_rows, -1)
    return res.replace(ids=res.ids.at[:, :1].set(ids))


def _drop_half(res):
    """Every other lane of the batch returns nothing."""
    lost = (jnp.arange(res.ids.shape[0]) % 2 == 1)[:, None]
    return res.replace(ids=jnp.where(lost, -1, res.ids),
                       dists=jnp.where(lost, jnp.inf, res.dists))


@pytest.mark.parametrize("fault", [_alter_answer, _drop_half], ids=["altered", "half"])
@pytest.mark.parametrize("name", CELLS)
def test_a_broken_timed_path_is_not_correct(root, name, fault):
    result = run.run_cell(spec.load_cell(root, name), SEED, 2.0, False, jax.devices(),
                          fault=fault)
    assert result["correct"] is False, result["checks"]
