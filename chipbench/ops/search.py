"""Search requests: ``POST /v1/search``, each with its own k and one
constraint, held against the plain reference.

A traffic mix names the module of its operations (``"ops": "search"``);
the harness finds it under ``chipbench/ops/`` by that name. The module
makes the requests (``make_items``, ``warm_items``), turns the replies
into answers (``answers_of``) and compares them (``compare``).

The generator is a copy of the repository's ``mixed_workload``
(``serving/workload.py``): queries drawn near corpus points, each with its
own k and constraint, in one stream of equal-label, unequal-X% label-set
and numeric-range requests. The counts of each kind and each k are exact
shares of the stream, shuffled, so that every seed carries the same work
in another order.

The numbers compared, each with its limit:

* ``unanswered`` -- requests with no answer: an error, a shed, or no reply
  a minute past the window's close. Limit 0.
* ``bad_answers`` -- answers that say something false: an id outside the
  corpus or repeated, a returned row that fails the request's constraint,
  a padding slot before a filled one, distances out of ascending order, or
  ``filled`` other than the number of ids returned. Limit 0.
* ``short_answers`` -- answers with fewer ids than the request's k, where
  the corpus holds k rows that satisfy it: the configuration states that
  its tier ladder fills every answer. Limit 0.
* ``dist_gap`` -- the widest gap between a returned distance and the
  reference's distance of that same row to the query, over |q|^2 + |x|^2:
  the scale at which a product of the rows rounds, so that a near row's
  small distance does not magnify the rounding of a correct f32 answer.
* ``recall_at_k`` -- mean recall@k against the reference top-k; a floor,
  stated by the configuration.
"""
from __future__ import annotations

import dataclasses
import json
from typing import List, Optional

import numpy as np

from chipbench import reference

KINDS = ("equal", "unequal", "range")


@dataclasses.dataclass
class Item:
    query: np.ndarray  # (d,) float32
    k: int
    kind: str  # equal | unequal | range
    labels: tuple = ()  # allowed labels (label families)
    range: tuple = ()  # (lo, hi, col) (range family)
    route = "/v1/search"

    @property
    def family(self) -> str:
        return "range" if self.kind == "range" else "label"

    def payload(self, timeout_s: float) -> bytes:
        body = {"query": self.query.tolist(), "k": self.k, "family": self.family,
                "timeout_s": timeout_s}
        if self.kind == "range":
            body["range"] = list(self.range)
        else:
            body["labels"] = list(self.labels)
        return json.dumps(body).encode()


def shares(n: int, weights: list) -> np.ndarray:
    """Exact counts summing to n in proportion to weights (largest remainder)."""
    w = np.asarray(weights, float) / np.sum(weights)
    counts = np.floor(w * n).astype(int)
    rest = np.argsort(-(w * n - counts))[: n - counts.sum()]
    counts[rest] += 1
    return counts


def make_items(seed: int, data, n: int, mix: dict, stream: int = 1) -> List[Item]:
    """``n`` requests of the mix, drawn from the seed's ``stream``."""
    rng = np.random.default_rng([seed, stream])
    kinds = np.repeat(np.arange(3), shares(n, [mix["mix"][k] for k in KINDS]))
    ks = np.repeat(mix["k_choices"], shares(n, [1] * len(mix["k_choices"])))
    rng.shuffle(kinds)
    rng.shuffle(ks)
    picks = rng.integers(0, data.n, size=n)
    queries = data.rows(picks) + rng.standard_normal(
        (n, data.d), dtype=np.float32) * np.float32(mix["jitter"])
    n_allowed = max(1, int(round(data.n_labels * mix["unequal_pct"] / 100.0)))
    col = int(mix["range_col"])
    items = []
    for q, k, kind, pick in zip(queries, ks, kinds, picks):
        own = int(data.labels[pick])
        if kind == 0:
            items.append(Item(q, int(k), "equal", labels=(own,)))
        elif kind == 1:
            others = [lab for lab in range(data.n_labels) if lab != own]
            allowed = rng.choice(others, size=min(n_allowed, len(others)), replace=False)
            items.append(Item(q, int(k), "unequal", labels=tuple(int(a) for a in allowed)))
        else:
            centre = float(data.attrs[pick, col])
            width = float(rng.uniform(*mix["range_width"]))
            items.append(Item(q, int(k), "range",
                              range=(centre - width / 2, centre + width / 2, col)))
    return items


def warm_items(seed: int, data, mix: dict) -> List[Item]:
    """``warm_requests`` of the mix's kinds in equal shares (two of each of
    three by default), sent before the window opens."""
    kinds = {k: 1 for k in KINDS if mix["mix"][k] > 0}
    return make_items(seed, data, mix.get("warm_requests", 6),
                      {**mix, "mix": {k: kinds.get(k, 0) for k in KINDS}}, stream=3)


def answers_of(records) -> list:
    """Per record: (ids, dists, filled) of the answer, or None."""
    out = []
    for rec in records:
        if not rec.answered:
            out.append(None)
            continue
        b = rec.body
        out.append((np.asarray(b["ids"], np.int64), np.asarray(b["dists"], np.float64),
                    int(b["filled"])))
    return out


def _bad(ids: np.ndarray, dists: np.ndarray, filled: int, k: int, item, data) -> bool:
    if len(ids) != k or len(dists) != k:
        return True
    valid = ids >= 0
    n_valid = int(valid.sum())
    if filled != n_valid or not valid[:n_valid].all() or np.any(ids >= data.n):
        return True
    got = ids[:n_valid]
    if len(set(got.tolist())) != n_valid:
        return True
    if np.any(np.diff(dists[:n_valid]) < 0):
        return True
    if item.kind == "range":
        lo, hi, col = item.range
        vals = data.attrs[got, col]
        return bool(np.any((vals < lo) | (vals > hi)))
    return not set(data.labels[got].tolist()) <= set(item.labels)


def compare(data, items, answers: List[Optional[tuple]], limits: dict, serve) -> dict:
    """-> {name: {"value", "limit", "sense"}}, one entry per number compared.
    ``serve`` holds the program's flags; ``k_cap`` sizes the answers."""
    k_cap = serve.k_cap
    ref_ids, _, n_sat = reference.constrained_topk(data, items, k_cap)
    unanswered = sum(a is None for a in answers)
    bad = short = 0
    recalls = []
    gap = 0.0
    q_items, q_ids, q_dists = [], [], []
    for item, ans, want, sat in zip(items, answers, ref_ids, n_sat):
        if ans is None:
            recalls.append(0.0)
            continue
        ids, dists, filled = ans
        if _bad(ids, dists, filled, item.k, item, data):
            bad += 1
        valid = (ids >= 0) & (ids < data.n)
        got, got_d = ids[valid][:k_cap], dists[valid][:k_cap]
        due = min(item.k, int(sat))
        short += filled < due
        hit = len(set(got.tolist()) & set(want[:due].tolist()))
        recalls.append(hit / due if due else float(len(got) == 0))
        if len(got):
            q_items.append(item)
            q_ids.append(np.pad(got, (0, k_cap - len(got)), constant_values=-1))
            q_dists.append(np.pad(got_d, (0, k_cap - len(got)), constant_values=np.inf))
    if q_items:
        ids = np.stack(q_ids)
        ref_d, scale = reference.point_dists(data, q_items, ids)
        mask = ids >= 0
        gap = float((np.abs(np.stack(q_dists)[mask] - ref_d[mask]) / scale[mask]).max())
    return {
        "unanswered": {"value": unanswered, "limit": 0, "sense": "<="},
        "bad_answers": {"value": bad, "limit": 0, "sense": "<="},
        "short_answers": {"value": int(short), "limit": 0, "sense": "<="},
        "dist_gap": {"value": gap, "limit": limits["max_dist_gap"], "sense": "<="},
        "recall_at_k": {"value": float(np.mean(recalls)) if recalls else 0.0,
                        "limit": limits["min_recall_at_k"], "sense": ">="},
    }
