"""Kernels: the decode-attention kernel's device time in the traced stretch
against the least time the chip needs for the work the algorithm needs:
each active slot's query against the keys and values of the tokens it
holds, in every layer (idle slots and cache rows past a slot's position
are not needed work).  Moves ``output_tokens_per_s``."""

# the kernel's calls are named after ``kernels/dispatch._decode_call``
KERNEL = ("_decode_call",)
KV_BYTES = {"bf16": 2, "f32": 4, "int8": 1}


def work(c: dict, held) -> tuple:
    """(operations, bytes) of one decode step whose active slots attend to
    ``held`` tokens each."""
    hq, hkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    layers = c["num_hidden_layers"]
    kvb = KV_BYTES[c["serving"]["kv_dtype"]]
    flops = sum(4.0 * hq * hd * h for h in held) * layers
    nbytes = sum(2.0 * h * hkv * hd * kvb + 2.0 * hq * hd * 2
                 for h in held) * layers
    return flops, nbytes


def read(ctx):
    if ctx.kind != "serve" or ctx.trace is None or ctx.stretch is None:
        return None
    seconds, n = ctx.trace.op_time(KERNEL)
    if not n or seconds <= 0:
        return None
    flops = nbytes = 0.0
    for call in ctx.stretch.calls:
        if call.kind == "decode":
            f, b = work(ctx.config, call.held)
            flops, nbytes = flops + f, nbytes + b
    if flops == 0.0:
        return None
    least = max(flops / ctx.peaks["bf16_flops_per_s"],
                nbytes / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least / seconds
