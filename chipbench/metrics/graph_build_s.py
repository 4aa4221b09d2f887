"""Build (set-up): seconds of the program's graph build, ``build_index``
over the cell's corpus (host clock)."""


def read(ctx):
    return ctx.phases.get("graph")
