"""Build (set-up): seconds of the runtime's warm-up, ``ServingRuntime.warmup``,
which compiles each closure of the cell's bucket and runs it once (host
clock)."""


def read(ctx):
    return ctx.phases.get("warmup")
