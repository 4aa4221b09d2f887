"""Property tests for the fixed-capacity sorted-array priority queues."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings  # noqa: E402
from hypothesis import strategies as st  # noqa: E402

from repro.core import queue as q  # noqa: E402


@st.composite
def batch_ops(draw):
    cap = draw(st.integers(2, 16))
    n_push = draw(st.integers(1, 5))
    pushes = [
        draw(
            st.lists(
                st.floats(2.0**-20, 2.0**20, width=32), min_size=1, max_size=8
            )
        )
        for _ in range(n_push)
    ]
    return cap, pushes


@settings(deadline=None, max_examples=30)
@given(batch_ops())
def test_queue_matches_sorted_reference(ops):
    cap, pushes = ops
    qq = q.queue_init(1, cap)
    ref: list[float] = []
    next_id = 0
    for vals in pushes:
        ids = jnp.arange(next_id, next_id + len(vals), dtype=jnp.int32)[None]
        d = jnp.asarray(vals, jnp.float32)[None]
        qq = q.queue_push(qq, d, ids, jnp.ones_like(d, bool))
        ref.extend(vals)
        ref = sorted(ref)[:cap]
        next_id += len(vals)
    np.testing.assert_allclose(
        np.asarray(qq.dists[0][: len(ref)]), np.asarray(ref, np.float32), rtol=1e-6
    )
    # queue stays ascending with +inf padding
    d = np.asarray(qq.dists[0])
    assert np.all(np.diff(d) >= 0) or np.all(np.isinf(d[np.argsort(d)][len(ref):]))


@settings(deadline=None, max_examples=30)
@given(batch_ops())
def test_queue_pop_returns_min(ops):
    cap, pushes = ops
    qq = q.queue_init(1, cap)
    for i, vals in enumerate(pushes):
        ids = jnp.full((1, len(vals)), i, jnp.int32)
        qq = q.queue_push(
            qq, jnp.asarray(vals, jnp.float32)[None], ids, jnp.ones((1, len(vals)), bool)
        )
    prev = -np.inf
    while bool(q.queue_nonempty(qq)[0]):
        qq, d, _ = q.queue_pop(qq, jnp.ones((1,), bool))
        assert float(d[0]) >= prev  # pops come out ascending
        prev = float(d[0])


def test_pop_on_masked_rows_is_noop():
    qq = q.queue_init(2, 4)
    d = jnp.asarray([[1.0, 2.0], [3.0, 4.0]])
    ids = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    qq = q.queue_push(qq, d, ids, jnp.ones((2, 2), bool))
    qq2, head_d, _ = q.queue_pop(qq, jnp.asarray([True, False]))
    assert float(qq2.dists[0, 0]) == 2.0  # popped
    assert float(qq2.dists[1, 0]) == 3.0  # untouched


def test_invalid_pushes_are_ignored():
    qq = q.queue_init(1, 4)
    qq = q.queue_push(
        qq,
        jnp.asarray([[5.0, 1.0]]),
        jnp.asarray([[7, 8]], jnp.int32),
        jnp.asarray([[False, True]]),
    )
    assert int(q.queue_size(qq)[0]) == 1
    assert float(qq.dists[0, 0]) == 1.0


@st.composite
def merge_cases(draw):
    cap = draw(st.integers(2, 16))
    n_live = draw(st.integers(0, 16))
    # XLA flushes subnormals to zero on both backends, so they are kept out:
    # a flushed value would compare equal to 0.0 in one path and not the other.
    dist = st.floats(0.0, 2.0**10, width=32, allow_subnormal=False)
    live = sorted(draw(st.lists(dist, min_size=n_live, max_size=n_live)))
    m = draw(st.integers(1, 12))
    new = draw(st.lists(dist, min_size=m, max_size=m))
    valid = draw(st.lists(st.booleans(), min_size=m, max_size=m))
    # duplicate some values across queue and run to force tie-breaking
    if live and draw(st.booleans()):
        new[0] = live[0]
    return cap, live, new, valid


@settings(deadline=None, max_examples=60)
@given(merge_cases())
def test_merge_sorted_bit_for_bit_equals_push(case):
    """sort_run + queue_merge_sorted == queue_push on ANY batch — including
    ties (queue element first, then original slot order), invalid entries,
    overflow past capacity, and runs longer than the free space."""
    cap, live, new, valid = case
    qq = q.queue_init(1, cap)
    if live:
        qq = q.queue_push(
            qq,
            jnp.asarray(live, jnp.float32)[None],
            jnp.arange(len(live), dtype=jnp.int32)[None],
            jnp.ones((1, len(live)), bool),
        )
    nd = jnp.asarray(new, jnp.float32)[None]
    ni = jnp.arange(100, 100 + len(new), dtype=jnp.int32)[None]
    nv = jnp.asarray(valid)[None]
    run_d, run_i = q.sort_run(nd, ni, nv)
    merged = q.queue_merge_sorted(qq, run_d, run_i)
    pushed = q.queue_push(qq, nd, ni, nv)
    np.testing.assert_array_equal(np.asarray(merged.dists), np.asarray(pushed.dists))
    np.testing.assert_array_equal(np.asarray(merged.ids), np.asarray(pushed.ids))


def test_merge_sorted_empty_run_and_empty_queue():
    qq = q.queue_init(2, 4)
    nd = jnp.full((2, 3), jnp.inf)
    ni = jnp.full((2, 3), -1, jnp.int32)
    merged = q.queue_merge_sorted(qq, nd, ni)
    np.testing.assert_array_equal(np.asarray(merged.dists), np.asarray(qq.dists))
    np.testing.assert_array_equal(np.asarray(merged.ids), np.asarray(qq.ids))


def test_sort_run_stable_under_ties():
    d = jnp.asarray([[2.0, 1.0, 2.0, 0.5, 1.0]])
    i = jnp.asarray([[10, 11, 12, 13, 14]], jnp.int32)
    v = jnp.asarray([[True, True, True, False, True]])
    rd, ri = q.sort_run(d, i, v)
    np.testing.assert_allclose(np.asarray(rd[0]), [1.0, 1.0, 2.0, 2.0, np.inf])
    # equal distances keep original slot order; invalid slots drop to padding
    np.testing.assert_array_equal(np.asarray(ri[0]), [11, 14, 10, 12, -1])


def test_partition_sorted_runs_splits_and_truncates():
    d = jnp.asarray([[3.0, 1.0, 2.0, 1.0, 5.0, 0.5]])
    i = jnp.asarray([[10, 11, 12, 13, 14, 15]], jnp.int32)
    first = jnp.asarray([[True, False, True, False, False, False]])
    second = jnp.asarray([[False, True, False, True, True, False]])
    (fd, fi), (sd, si) = q.partition_sorted_runs(d, i, first, second, 4, 2)
    np.testing.assert_allclose(np.asarray(fd[0]), [2.0, 3.0, np.inf, np.inf])
    np.testing.assert_array_equal(np.asarray(fi[0]), [12, 10, -1, -1])
    # second run truncated to capacity 2: best two of {1.0@11, 1.0@13, 5.0@14}
    np.testing.assert_allclose(np.asarray(sd[0]), [1.0, 1.0])
    np.testing.assert_array_equal(np.asarray(si[0]), [11, 13])


def test_topk_threshold_inf_until_full():
    qq = q.queue_init(1, 3)
    assert np.isinf(float(q.topk_threshold(qq, 3)[0]))
    qq = q.queue_push(
        qq,
        jnp.asarray([[1.0, 2.0, 3.0]]),
        jnp.asarray([[1, 2, 3]], jnp.int32),
        jnp.ones((1, 3), bool),
    )
    assert float(q.topk_threshold(qq, 3)[0]) == 3.0


def _ties_case(rng):
    # distances drawn from four values: ties within every push and between
    # the queue and each push
    d = rng.integers(0, 4, size=(6, 4, 9)).astype(np.float32)
    return 8, d, np.ones(d.shape, bool)


def _padding_case(rng):
    # rows 0 and 2 never get a valid entry (all-invalid rows stay padding);
    # the others get a few, so the queue keeps +inf slots after the live ones
    d = rng.random((4, 4, 5)).astype(np.float32)
    valid = rng.random(d.shape) < 0.3
    valid[:, [0, 2]] = False
    d[:, 3, 1] = np.inf  # a valid entry that is itself +inf
    return 6, d, valid


def _m1_case(rng):
    d = rng.integers(0, 3, size=(10, 3, 1)).astype(np.float32)
    return 4, d, rng.random(d.shape) < 0.8


def _m_ge_c_case(rng):
    d = rng.integers(0, 6, size=(5, 3, 12)).astype(np.float32)
    return 4, d, rng.random(d.shape) < 0.7


def _capacity1_case(rng):
    d = rng.integers(0, 3, size=(6, 3, 4)).astype(np.float32)
    return 1, d, rng.random(d.shape) < 0.8


def _random_case(rng):
    # a frontier-sized queue under many pushes, distances on a coarse grid
    d = (rng.integers(0, 512, size=(30, 8, 16)) / 8.0).astype(np.float32)
    d[:, :, 0] = 0.0
    return 64, d, rng.random(d.shape) < 0.9


@pytest.mark.parametrize(
    "make_case",
    [_ties_case, _padding_case, _m1_case, _m_ge_c_case, _capacity1_case,
     _random_case],
    ids=["ties", "padding", "m1", "m_ge_c", "capacity1", "random"],
)
def test_push_lowerings_identical(monkeypatch, make_case):
    """queue_push's TPU lowering (one stable sort carrying the ids) and its
    top_k + gather lowering give the same queue, bit for bit, after every
    push; sort_run + queue_merge_sorted gives it too."""
    rng = np.random.default_rng(14)
    cap, d, valid = make_case(rng)
    # distinct ids in no particular order, so a tie broken by id (and not
    # by position) shows
    ids = rng.permutation(d.size).astype(np.int32).reshape(d.shape)
    n_push, b, m = d.shape
    # one jitted function per lowering, each traced under its own backend
    by_top_k = jax.jit(lambda qq, *p: q.queue_push(qq, *p))
    by_sort = jax.jit(lambda qq, *p: q.queue_push(qq, *p))
    by_merge = jax.jit(lambda qq, *p: q.queue_merge_sorted(qq, *q.sort_run(*p)))
    ref = got = merged = q.queue_init(b, cap)
    for t in range(n_push):
        push = (jnp.asarray(d[t]), jnp.asarray(ids[t]), jnp.asarray(valid[t]))
        ref = by_top_k(ref, *push)
        with monkeypatch.context() as mp:
            mp.setattr(jax, "default_backend", lambda: "tpu")
            got = by_sort(got, *push)
        merged = by_merge(merged, *push)
        for other in (got, merged):
            np.testing.assert_array_equal(
                np.asarray(other.dists), np.asarray(ref.dists))
            np.testing.assert_array_equal(
                np.asarray(other.ids), np.asarray(ref.ids))
    assert np.isfinite(np.asarray(ref.dists)).any()
    # the two lowerings really differ: only the top_k one gathers ids
    args = (q.queue_init(b, cap), *push)
    assert "gather" in by_top_k.lower(*args).as_text()
    with monkeypatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        assert "gather" not in by_sort.lower(*args).as_text()
