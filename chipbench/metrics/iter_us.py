"""Search loop: device time of the search programs in the traced window
over the lock-step iterations of those batches (``SearchStats.iters``)."""


def read(ctx):
    traced = ctx.traced_searches()
    iters = sum(c.iters for c, _ in traced)
    return 1e6 * sum(s for _, s in traced) / iters if iters else None
