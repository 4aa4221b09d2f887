"""Search program on the device: the least time the walk's reads could
take at the chip's HBM bandwidth, over the search programs' device time in
the traced window, in percent.

The bytes are counted from what the walk must read, whatever implements
it: each distance evaluation reads one candidate row at the configuration's
declared bytes per candidate, each expansion reads one neighbour list
(degree x 4 B), and an approximate walk re-reads its ef_result survivors as
full rows for the exact re-rank. Its operations are a few per byte, far
below the chip's ratio of peak FLOP/s to bytes/s, so bandwidth bounds it.
"""


def read(ctx):
    traced = ctx.traced_searches()
    seconds = sum(s for _, s in traced)
    if not traced or seconds <= 0:
        return None
    walk = ctx.config["walk_bytes"]
    degree = ctx.serve.degree
    total = 0.0
    for c, _ in traced:
        total += float(c.dist_evals.sum()) * walk["per_candidate"]
        total += float(c.hops.sum()) * degree * walk["per_neighbor_id"]
        total += c.bucket * c.rerank_rows * walk["per_rerank_row"]
    return 100.0 * total / ctx.peaks["hbm_byte_per_s"] / seconds
