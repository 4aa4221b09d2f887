"""Metrics registry + Prometheus exposition (DESIGN.md §12).

Two layers: the primitives (families, label sets, render) against the
satellite line-format parser, and the runtime adapters — after a real
replayed workload, the scraped ``/metrics`` text must parse back
*bit-identical* to ``Telemetry``'s in-process state (the PR 9 acceptance
criterion: no double bookkeeping, no drift).
"""
import math
import re

import jax
import pytest

from repro.data.synthetic import make_labeled_corpus
from repro.graph.index import build_index
from repro.obs import (
    Counter,
    ExpositionParseError,
    MetricsRegistry,
    format_value,
    instrument_runtime,
    latency_hist_samples,
    parse_exposition,
)
from repro.serving import (
    LatencyHistogram,
    LocalExecutor,
    ServingRuntime,
    VirtualClock,
    label_words_row,
    make_tier_ladder,
    mixed_workload,
    replay_poisson,
)

N, D, L = 1500, 16, 5
NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*\Z")


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------


def test_format_value_round_trips():
    for v in (0.0, 17.0, -3.0, 0.1, 1e-6, 59.999999999, 2.5, 1 / 3):
        assert float(format_value(v).replace("+Inf", "inf")) == v
    assert format_value(17.0) == "17"  # integral counters scrape as ints
    assert format_value(float("inf")) == "+Inf"
    assert format_value(float("-inf")) == "-Inf"
    assert format_value(float("nan")) == "NaN"


def test_counter_gauge_basics_and_validation():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "help", labels=("kind",))
    c.labels(kind="a").inc()
    c.labels(kind="a").inc(2)
    c.labels(kind="b").inc()
    with pytest.raises(ValueError):
        c.labels(kind="a").inc(-1)  # counters only go up
    with pytest.raises(ValueError):
        c.labels(wrong="a")  # undeclared label name
    g = reg.gauge("g", "help")
    g.set(5)
    g.dec(2)
    assert g.value == 3
    g.set_function(lambda: 42.0)
    assert g.value == 42.0
    with pytest.raises(ValueError):
        reg.counter("c_total", "dup")  # duplicate registration
    with pytest.raises(ValueError):
        reg.counter("0bad", "bad name")
    with pytest.raises(ValueError):
        Counter("ok", "h", ("__reserved",))
    fams = parse_exposition(reg.render_prometheus())
    assert fams["c_total"].value(kind="a") == 3
    assert fams["c_total"].value(kind="b") == 1
    assert fams["g"].value() == 42.0


def test_histogram_family_render_and_parse():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", "h", buckets=(0.1, 1.0, 10.0))
    for x in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(x)
    fams = parse_exposition(reg.render_prometheus())
    fam = fams["lat_seconds"]
    assert fam.mtype == "histogram"
    assert fam.buckets() == [(0.1, 1), (1.0, 3), (10.0, 4), (math.inf, 5)]
    assert fam.hist_count() == 5
    assert fam.hist_sum() == pytest.approx(56.05)
    with pytest.raises(ValueError):
        reg.histogram("bad", "h", buckets=(1.0, 0.5))  # unsorted edges


def test_label_value_escaping_round_trips():
    reg = MetricsRegistry()
    c = reg.counter("esc_total", "has \\ and \n newline", labels=("v",))
    tricky = 'a"b\\c\nd'
    c.labels(v=tricky).inc()
    fams = parse_exposition(reg.render_prometheus())
    assert fams["esc_total"].label_values("v") == [tricky]
    assert "\n" in fams["esc_total"].help


def test_exposition_line_format_discipline():
    """Every non-comment line: valid name charset, HELP/TYPE seen before
    any sample of that family."""
    reg = MetricsRegistry()
    reg.counter("a_total", "ha").inc()
    reg.gauge("b", "hb", labels=("x",)).labels(x="1").set(2)
    reg.histogram("h_seconds", "hh").observe(0.3)
    text = reg.render_prometheus()
    seen_meta = set()
    for line in text.splitlines():
        if line.startswith("# "):
            _, kind, name = line.split(None, 3)[:3]
            assert kind in ("HELP", "TYPE")
            seen_meta.add(name)
            continue
        name = re.split(r"[{\s]", line, maxsplit=1)[0]
        assert NAME_RE.match(name), line
        base = re.sub(r"_(bucket|sum|count)\Z", "", name)
        assert name in seen_meta or base in seen_meta, line


def test_parser_rejects_malformed_payloads():
    with pytest.raises(ExpositionParseError):
        parse_exposition("x_total{oops} 1\n")
    with pytest.raises(ExpositionParseError):
        parse_exposition("x_total one\n")
    with pytest.raises(ExpositionParseError):
        parse_exposition("# TYPE h histogram\nh_bucket{le=\"1\"} 2\n"
                         "h_bucket{le=\"+Inf\"} 1\nh_sum 1\nh_count 1\n")
    with pytest.raises(ExpositionParseError):
        # non-cumulative then missing +Inf
        parse_exposition("# TYPE h histogram\nh_bucket{le=\"1\"} 1\n"
                         "h_sum 1\nh_count 1\n")
    with pytest.raises(ExpositionParseError):
        parse_exposition("# HELP a one\n# HELP a two\na 1\n")


def test_latency_hist_samples_bit_identical():
    """The adapter's native-histogram view reproduces a LatencyHistogram
    exactly: cumulative counts, _sum, _count, and the quantile rule."""
    hist = LatencyHistogram()
    import numpy as np

    rng = np.random.default_rng(3)
    for x in np.exp(rng.uniform(math.log(1e-5), math.log(50.0), 500)):
        hist.record(float(x))
    hist.record(0.0)  # underflow
    hist.record(100.0)  # overflow
    reg = MetricsRegistry()
    reg.callback("lh_seconds", "histogram", "h",
                 lambda: latency_hist_samples(hist))
    fam = parse_exposition(reg.render_prometheus())["lh_seconds"]
    assert fam.hist_count() == hist.total
    assert fam.hist_sum() == hist.sum  # bit-identical, not approx
    buckets = fam.buckets()
    assert buckets[-1][0] == math.inf
    assert buckets[-1][1] == hist.total
    for p in (1, 50, 90, 99, 100):
        assert fam.quantile(p) == hist.quantile(p), p


# ---------------------------------------------------------------------------
# runtime adapters: scrape == Telemetry, after a real workload
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def served_runtime():
    corpus = make_labeled_corpus(jax.random.PRNGKey(0), n=N, d=D, n_labels=L)
    corpus = corpus.replace(
        attrs=jax.random.uniform(jax.random.PRNGKey(50), (N, 2))
    )
    graph = build_index(jax.random.PRNGKey(1), corpus, degree=12,
                        sample_size=128)
    rt = ServingRuntime(
        LocalExecutor(corpus, graph),
        n_labels=L,
        tiers=make_tier_ladder(k_cap=8, base_ef=32, base_iters=64, n_tiers=2),
        ladder=(4, 16),
        max_wait=0.002,
        clock=VirtualClock(),
    )
    rt.warmup()
    items = mixed_workload(7, corpus, 64, L, k_choices=(4, 8))
    responses, rejected = replay_poisson(rt, items, rate=20000.0, seed=11)
    assert rejected == 0
    return rt, [r for r in responses if r is not None]


def test_scrape_matches_telemetry_exactly(served_runtime):
    rt, served = served_runtime
    fams = parse_exposition(instrument_runtime(rt).render_prometheus())
    tel = rt.telemetry
    events = fams["repro_serving_events_total"]
    host = fams["repro_serving_host_seconds_total"]
    for key, v in tel.counters.items():
        if key.endswith("_us"):
            # Host-stage microseconds: one seconds family, not events.
            assert host.value(stage=key[:-3]) == v / 1e6, key
        else:
            assert events.value(event=key) == v, key
    host_stages = {k[:-3] for k in tel.counters if k.endswith("_us")}
    assert set(host.label_values("stage")) == host_stages
    assert {"assemble", "dispatch", "device_wait", "readback", "complete",
            "queue_wait", "host_turn"} <= host_stages
    assert not any(e.endswith("_us") for e in events.label_values("event"))
    lat = fams["repro_serving_latency_seconds"]
    assert lat.hist_count() == tel.latency_hist.total
    assert lat.hist_sum() == tel.latency_hist.sum
    for p in (50, 99):
        assert lat.quantile(p) == tel.latency_hist.quantile(p)
    # Per-stage histograms (tracing was on) carry the same discipline.
    stages = fams["repro_serving_stage_seconds"]
    for stage, hist in tel.stage_hists.items():
        assert stages.hist_count(stage=stage) == hist.total
        assert stages.hist_sum(stage=stage) == hist.sum
        assert stages.quantile(99, stage=stage) == hist.quantile(99)
    cache = fams["repro_serving_compile_cache_hits_total"]
    assert cache.value() == rt.cache.hits
    assert fams["repro_serving_trace_budget"].value() == rt.trace_budget
    assert fams["repro_serving_in_flight"].value() == 0
    assert fams["repro_serving_queue_depth"].value() == 0
    assert fams["repro_serving_degradation_level"].value() == 0


def test_scrape_is_pull_time_not_snapshot(served_runtime):
    """Two renders straddling new work must disagree — the registry reads
    live state, it does not cache."""
    rt, _ = served_runtime
    reg = instrument_runtime(rt, namespace="pull")
    before = parse_exposition(reg.render_prometheus())
    rt.submit([0.0] * D, 4, "label", label_words_row([0], L))
    rt.drain()
    after = parse_exposition(reg.render_prometheus())

    def completed(fams):
        return fams["pull_serving_events_total"].value(event="completed")

    assert completed(after) == completed(before) + 1
