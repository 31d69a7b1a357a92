"""Device: share of the traced stretch of a training window in which no
operation ran on the chip.  Moves ``train_tokens_per_s``."""


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
