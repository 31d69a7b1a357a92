"""Whole step, share of the chip's peak: forward and backward operations
per token (three times the forward; recomputation does not count) times
the tokens of the steps in the traced stretch, over (stretch seconds x bf16
peak).  Moves ``train_tokens_per_s``."""
from benchmarks.chip import work


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or ctx.stretch is None:
        return None
    steps = ctx.stretch[2]
    if not steps:
        return None
    b, s = ctx.traffic["batch"], ctx.traffic["seq"]
    flops = 3.0 * work.forward_flops(ctx.config, steps * b * s,
                                     steps * b * work.causal_context_sum(s))
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peaks["bf16_flops_per_s"])
