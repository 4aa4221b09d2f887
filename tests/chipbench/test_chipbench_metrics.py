"""Metric arithmetic: the tail from due times with failures slowest, the
rate over the window, recall and the other numbers of the comparison."""
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench_tiny import ROOT  # noqa: F401  (puts the benchmark on the path)

from chipbench import check, stats
from chipbench.ops.search import Item, compare, shares
from chipbench.traffic import Record, open_loop_times


def _answered(item, due, done, ids=(1, 2), dists=(0.1, 0.2)):
    return Record(item=item, due=due, sent=due, done=done, status=200,
                  body={"ids": list(ids), "dists": list(dists), "filled": len(ids)})


def test_nearest_rank_reads_a_value_that_was_read():
    assert stats.nearest_rank([3.0, 1.0, 2.0], 0.5) == 2.0
    assert stats.nearest_rank(list(range(1, 101)), 0.95) == 95
    assert stats.nearest_rank([7.0], 0.95) == 7.0
    with pytest.raises(ValueError):
        stats.nearest_rank([], 0.5)


def test_p95_counts_from_the_due_time_not_the_send():
    # each request sent 0.5 s after it was due, answered 0.1 s later
    recs = [Record(item=i, due=float(i), sent=i + 0.5, done=i + 0.6, status=200,
                   body={"ids": [], "dists": [], "filled": 0}) for i in range(20)]
    assert stats.p95_ms(recs, give_up=100.0) == pytest.approx(600.0)


def test_an_unanswered_request_is_slower_than_every_answered_one():
    recs = [_answered(i, 0.0, 0.1 * (i + 1)) for i in range(19)]
    recs.append(Record(item=19, due=0.0, sent=0.0, done=0.05, status=429,
                       body={"error": "queue full"}))
    lat = stats.latencies_from_due(recs, give_up=1.0)
    assert max(lat) > 1.9 and lat[-1] == max(lat)
    # 20 samples: the 95th percentile is the 19th, the slowest answered one
    assert stats.p95_ms(recs, give_up=1.0) == pytest.approx(1900.0)
    recs[0] = Record(item=0, due=0.0, sent=0.0, done=None)  # never came back
    assert stats.p95_ms(recs, give_up=1.0) > 1900.0


def test_qps_counts_answers_inside_the_window_over_its_length():
    recs = [_answered(i, 0.0, t) for i, t in enumerate([0.5, 1.0, 9.9, 10.2])]
    recs.append(Record(item=9, due=0.0, sent=0.0, done=2.0, status=504,
                       body={"error": "timed out"}))
    assert stats.qps(recs, t0=0.0, seconds=10.0) == pytest.approx(0.3)


def test_drain_qps_runs_to_the_last_answer():
    recs = [_answered(i, 0.0, t) for i, t in enumerate([0.5, 1.0, 9.9, 12.5])]
    assert stats.drain_qps(recs, t0=0.5, give_up=70.0) == pytest.approx(4 / 12.0)
    recs.append(Record(item=9, due=0.0, sent=0.0, done=None))  # never came back
    assert stats.drain_qps(recs, t0=0.5, give_up=70.0) == pytest.approx(4 / 69.5)


def test_shares_and_open_loop_times_give_every_seed_the_same_work():
    assert list(shares(10, [0.4, 0.4, 0.2])) == [4, 4, 2]
    assert shares(7, [1, 1, 1]).sum() == 7
    a, b = open_loop_times(1, 20.0, 30.0), open_loop_times(2**31 + 5, 20.0, 30.0)
    assert len(a) == len(b) == 600 and np.all(np.diff(a) >= 0) and a.max() < 30.0
    assert not np.allclose(a, b)


class _Data:
    """Eight 2-d rows on a line, labels alternating, attribute = x / 8."""

    def __init__(self):
        import jax.numpy as jnp

        x = np.arange(8, dtype=np.float32)
        self.vectors = jnp.asarray(np.stack([x, np.zeros_like(x)], 1))
        self.labels = np.arange(8) % 2
        self.attrs = (x / 8)[:, None]
        self.labels_dev = jnp.asarray(self.labels, jnp.int32)
        self.attrs_dev = jnp.asarray(self.attrs)
        self.n, self.n_labels = 8, 2


LIMITS = {"max_dist_gap": 1e-6, "min_recall_at_k": 0.9}


def _compare(items, answers):
    return compare(_Data(), items, answers, LIMITS, SimpleNamespace(k_cap=2))


def test_exact_answers_hold_and_recall_is_one():
    q = np.array([2.1, 0.0], np.float32)
    items = [Item(q, 2, "equal", labels=(0,)), Item(q, 2, "range", range=(0.3, 0.6, 0))]
    answers = [(np.array([2, 4]), np.array([0.01, 3.61]), 2),
               (np.array([3, 4]), np.array([0.81, 3.61]), 2)]
    c = _compare(items, answers)
    assert check.holds(c), c
    assert c["recall_at_k"]["value"] == 1.0 and c["bad_answers"]["value"] == 0
    assert c["dist_gap"]["value"] < 1e-7  # f32 rounding of 0.1 squared


@pytest.mark.parametrize("answer, failing", [
    ((np.array([2, 3]), np.array([0.01, 0.81]), 2), "bad_answers"),  # label 1 not allowed
    ((np.array([2, 2]), np.array([0.01, 0.01]), 2), "bad_answers"),  # repeated id
    ((np.array([2, 0]), np.array([0.01, 4.41]), 1), "bad_answers"),  # filled says 1
    ((np.array([2, -1]), np.array([0.01, np.inf]), 1), "short_answers"),  # one id of two
    ((np.array([4, 2]), np.array([3.61, 0.01]), 2), "bad_answers"),  # out of order
    ((np.array([-1, 2]), np.array([np.inf, 0.01]), 1), "bad_answers"),  # pad first
    ((np.array([2, 4]), np.array([0.01, 3.6]), 2), "dist_gap"),  # distance off
    ((np.array([2, 6]), np.array([0.01, 15.21]), 2), "recall_at_k"),  # not the nearest
    (None, "unanswered"),
])
def test_each_false_answer_fails_its_number(answer, failing):
    q = np.array([2.1, 0.0], np.float32)
    c = _compare([Item(q, 2, "equal", labels=(0,))], [answer])
    assert not check.holds({failing: c[failing]}), c
    assert not check.holds(c)


def test_check_lines_name_each_number_with_its_limit():
    lines = check.lines({"dist_gap": {"value": 2e-5, "limit": 1e-5, "sense": "<="},
                         "recall_at_k": {"value": 0.95, "limit": 0.9, "sense": ">="}})
    assert lines[0].startswith("check dist_gap 2e-05 limit <= 1e-05 FAIL")
    assert lines[1].endswith("limit >= 0.9 ok")


class _Call:
    def __init__(self, seq, iters, evals, hops, bucket=4, n_real=3, rerank_rows=0):
        self.seq, self.iters, self.bucket, self.n_real = seq, iters, bucket, n_real
        self.dist_evals, self.hops = np.asarray(evals), np.asarray(hops)
        self.rerank_rows = rerank_rows


class _Ctx:
    def __init__(self, calls, device_s, counters=None):
        self.searches = calls
        self.trace = {"searches": dict(zip([c.seq for c in calls], device_s))}
        self.serve = SimpleNamespace(degree=32)
        self.config = {"walk_bytes": {
            "per_candidate": 512, "per_neighbor_id": 4, "per_rerank_row": 512}}
        self.peaks = {"hbm_byte_per_s": 819e9}
        self.counters = counters or {}

    def traced_searches(self):
        if not self.trace:
            return []
        return [(c, self.trace["searches"][c.seq]) for c in self.searches]


def _reader(name):
    from chipbench import spec

    return spec.load_reader(ROOT, name)


def test_walk_readers_count_the_walks_reads_and_iterations():
    calls = [_Call(0, 100, [10, 20, 30, 40], [1, 2, 3, 4]),
             _Call(1, 300, [5, 5, 5, 5], [1, 1, 1, 1], rerank_rows=64)]
    ctx = _Ctx(calls, [0.001, 0.003])
    assert _reader("iter_us.any")(ctx) == pytest.approx(1e6 * 0.004 / 400)
    # real lanes only: the fourth lane of each batch is padding
    assert _reader("dist_evals_per_query.any")(ctx) == pytest.approx((60 + 15) / 6)
    walk = (100 + 20) * 512 + (10 + 4) * 32 * 4 + 4 * 64 * 512
    share = _reader("search_roofline.any")(ctx)
    assert share == pytest.approx(100 * walk / 819e9 / 0.004)
    assert 0 < share < 100
    assert _reader("batch_fill.any")(_Ctx([], [], {"dispatched_real": 3,
                                                   "dispatched_slots": 4})) == 0.75


def test_walk_readers_return_nothing_without_a_trace():
    ctx = _Ctx([_Call(0, 10, [1], [1])], [0.001])
    ctx.trace = None
    for name in ("iter_us.any", "search_roofline.any", "idle_share.any"):
        assert _reader(name)(ctx) is None
    assert _reader("batch_fill.any")(ctx) is None
