"""Why the weighted HLO walk exists: XLA's cost_analysis counts While (scan)
bodies once.  These tests pin that fact and validate the weighted parser."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch import hlo_analysis as H


def _scan_model(n):
    def f(x, w):
        def body(c, wi):
            return jnp.tanh(c @ wi), None
        y, _ = jax.lax.scan(body, x, w)
        return y
    return f


def test_xla_cost_analysis_undercounts_scan():
    f = _scan_model(8)
    x = jnp.zeros((4, 128))
    w = jnp.zeros((8, 128, 128))
    c_scan = jax.jit(f).lower(x, w).compile()
    flops_scan = c_scan.cost_analysis().get("flops", 0)

    def unrolled(x, w):
        for i in range(8):
            x = jnp.tanh(x @ w[i])
        return x
    c_unr = jax.jit(unrolled).lower(x, w).compile()
    flops_unr = c_unr.cost_analysis().get("flops", 0)
    # the documented defect: scan counted once vs 8x
    assert flops_unr > 6 * flops_scan


def test_weighted_walk_recovers_trip_count():
    f = _scan_model(8)
    x = jnp.zeros((4, 128))
    w = jnp.zeros((8, 128, 128))
    c = jax.jit(f).lower(x, w).compile()
    tot = H.weighted_totals(c.as_text())
    expect = 8 * 2 * 4 * 128 * 128     # 8 iterations x 2MNK
    assert abs(tot["flops"] - expect) / expect < 0.05, tot["flops"]


def test_shape_parsing():
    assert H._type_bytes("bf16[16,4096,512]{2,1,0}") == 16 * 4096 * 512 * 2
    assert H._type_bytes("(f32[8,8], f32[4])") == 8 * 8 * 4 + 16
    assert H._shape_dims("f32[3,5]{1,0}") == [3, 5]


def test_operand_name_extraction():
    ops = H._operands("(%copy.1, %all-gather.1), channel_id=1")
    assert ops == ["copy.1", "all-gather.1"]
