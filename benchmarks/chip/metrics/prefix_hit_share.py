"""Scheduler: share of the prompt pages admitted in the window that were
mapped from the prefix index instead of allocated (the engine's
``pages_requested`` and ``pages_alloced`` counters, read around each
admission).  Moves ``ttft_p95_ms``."""


def read(ctx):
    if ctx.kind != "serve":
        return None
    asked = sum(c.pages_requested for c in ctx.calls if c.kind == "admit")
    fresh = sum(c.pages_alloced for c in ctx.calls if c.kind == "admit")
    if asked == 0:
        return None
    return 100.0 * (asked - fresh) / asked
