"""Whether the numbers compared hold, and how they are printed.

A traffic mix's operations module (``chipbench/ops/<name>.py``) compares a
run's answers with the plain reference and returns one entry per number:
``{"value": v, "limit": l, "sense": "<=" | ">="}``. A run is correct when
every number keeps to its limit.
"""
from __future__ import annotations


def _holds(c: dict) -> bool:
    return c["value"] >= c["limit"] if c["sense"] == ">=" else c["value"] <= c["limit"]


def holds(checks: dict) -> bool:
    return all(_holds(c) for c in checks.values())


def lines(checks: dict) -> list:
    """One plain line per number compared, with its limit."""
    return [f"check {name} {c['value']!r} limit {c['sense']} {c['limit']!r} "
            f"{'ok' if _holds(c) else 'FAIL'}" for name, c in checks.items()]
