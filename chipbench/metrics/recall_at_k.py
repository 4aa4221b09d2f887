"""Mean recall@k of the window's answers against the plain reference."""


def read(ctx):
    c = ctx.checks.get("recall_at_k")
    return c["value"] if c else None
