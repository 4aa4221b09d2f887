"""HTTP front end: time a search spends in the front end's own stages,
mean per answered request in ms: body parse, admission, the wait from the
response's completion to its handler holding it, and the reply (program
counters ``http_*_us`` / ``http_requests``). None where the program keeps
no such counter."""

STAGES = ("http_parse_us", "http_admit_us", "http_reply_lag_us", "http_reply_us")


def read(ctx):
    n = ctx.counters.get("http_requests", 0)
    return sum(ctx.counters.get(k, 0) for k in STAGES) / n / 1000 if n else None
