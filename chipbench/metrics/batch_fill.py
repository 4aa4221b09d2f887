"""Serving runtime: real requests over batch slots dispatched in the window
(telemetry counters dispatched_real / dispatched_slots)."""


def read(ctx):
    slots = ctx.counters.get("dispatched_slots", 0)
    return ctx.counters.get("dispatched_real", 0) / slots if slots else None
