"""Device: share of the traced stretch of a serving window in which no
operation ran on the chip.  Moves ``output_tokens_per_s``."""


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
