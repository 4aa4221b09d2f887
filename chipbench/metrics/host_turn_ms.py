"""Serving runtime: the host's turn between query batches, from one
batch's read-back end to the next batch's dispatch, mean in ms (program
counters ``host_turn_us`` / ``host_turn_n``). None where the program keeps
no such counter."""


def read(ctx):
    n = ctx.counters.get("host_turn_n", 0)
    return ctx.counters.get("host_turn_us", 0) / n / 1000 if n else None
