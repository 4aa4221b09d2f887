"""Device: share of the traced window in which no operation ran."""


def read(ctx):
    return ctx.trace["idle_share"] if ctx.trace else None
