"""Requests answered over the time from the window's start to the last
answer of a request sent in it (a closed loop's callers stop sending when
the window closes; the rate counts their last flush whole)."""
from chipbench import stats


def read(ctx):
    return stats.drain_qps(ctx.records, ctx.t0, ctx.give_up) if ctx.records else None
