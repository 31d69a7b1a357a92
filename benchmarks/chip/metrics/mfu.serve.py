"""Whole step, share of the chip's peak: model operations of the prompt and
generated tokens processed in the traced stretch of a serving window, over
(stretch seconds x bf16 peak).  Prompt tokens mapped from the prefix cache
are not processed and do not count.  Moves ``output_tokens_per_s``."""
from benchmarks.chip import work


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None or ctx.stretch is None:
        return None
    c = ctx.config
    flops = 0.0
    for call in ctx.stretch.calls:
        if call.kind == "decode":
            flops += work.forward_flops(c, len(call.held), sum(call.held))
        elif call.prompt_tokens:
            done = max(call.prompt_tokens - call.cached_tokens, 0)
            ctx_sum = sum(work.causal_context_sum(p) for p in call.prompts)
            flops += work.forward_flops(c, done,
                                        ctx_sum * done / call.prompt_tokens)
    if flops == 0.0:
        return None
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peaks["bf16_flops_per_s"])
