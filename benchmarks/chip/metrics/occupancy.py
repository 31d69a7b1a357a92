"""Scheduler: mean share of the slots active per decode step, over the
window (the engine's own ``occupancy`` counter).  Moves
``output_tokens_per_s``."""


def read(ctx):
    if ctx.kind != "serve" or not ctx.occupancy:
        return None
    return 100.0 * sum(ctx.occupancy) / len(ctx.occupancy)
