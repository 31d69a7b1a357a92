"""Serve steps: device time of one decode-step module (``serve_step``),
the mean over the traced stretch.  Moves ``output_tokens_per_s``."""

MODULE = "serve_step"


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None:
        return None
    seconds, calls = ctx.trace.module_time(MODULE)
    return 1e3 * seconds / calls if calls else None
