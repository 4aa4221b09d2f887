"""Search loop: distance evaluations per request lane and search pass
(``SearchStats.dist_evals`` over the real lanes of the window's batches)."""


def read(ctx):
    lanes = [int(v) for c in ctx.searches for v in c.dist_evals[: c.n_real]]
    return sum(lanes) / len(lanes) if lanes else None
