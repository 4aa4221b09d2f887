"""Set-up: process start to the first request of the window (host clock)."""


def read(ctx):
    return ctx.setup_s
