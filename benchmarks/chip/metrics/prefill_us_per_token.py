"""Serve steps: device time of the prefill-step modules (``prefill_step``)
in the traced stretch, per prompt token admitted there.  Padding rows and
chunks the prefix cache lets the engine skip show here.  Moves
``ttft_p95_ms``."""

MODULE = "prefill_step"


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None or ctx.stretch is None:
        return None
    tokens = sum(c.prompt_tokens for c in ctx.stretch.calls
                 if c.kind == "admit")
    seconds, calls = ctx.trace.module_time(MODULE)
    if not tokens or not calls:
        return None
    return 1e6 * seconds / tokens
