"""Kernels: the flash-attention forward and backward kernels' device time
in the traced stretch of a training window against the least time the chip
needs for causal attention's forward and backward work (the backward twice
the forward's operations; a forward recomputed for the backward is not
needed work).  Moves ``train_tokens_per_s``."""

# forward and backward kernels' calls are named after
# ``kernels/dispatch._flash_call``
KERNEL = ("_flash_call",)


def work(c: dict, batch: int, seq: int) -> tuple:
    """(operations, bytes) of one training step's attention, bf16."""
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    layers = c["num_hidden_layers"]
    fwd = 4.0 * batch * hq * hd * seq * (seq + 1) / 2.0
    q_side, kv_side = batch * seq * hq * hd, batch * seq * hkv * hd
    # forward reads q, k, v and writes o; backward reads q, k, v, o, do
    # and writes dq, dk, dv
    nbytes = 2.0 * ((2 * q_side + 2 * kv_side) + (4 * q_side + 4 * kv_side))
    return 3.0 * fwd * layers, nbytes * layers


def read(ctx):
    if ctx.kind != "train" or ctx.trace is None or ctx.stretch is None:
        return None
    steps = ctx.stretch[2]
    seconds, n = ctx.trace.op_time(KERNEL)
    if not steps or not n or seconds <= 0:
        return None
    f, b = work(ctx.config, ctx.traffic["batch"], ctx.traffic["seq"])
    least = max(steps * f / ctx.peaks["bf16_flops_per_s"],
                steps * b / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
