"""Percentiles, rates, operation and byte counts, and the readers built on
them, on numbers worked out by hand."""
from __future__ import annotations

import types

import numpy as np
import pytest

import bc_tiny  # noqa: F401
from benchmarks.chip import common, serving, stats, work


def test_percentile_is_nearest_rank_over_all_values():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == 95
    assert stats.percentile(xs, 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    # one slow value in twenty is the 95th percentile's own
    assert stats.percentile([1.0] * 19 + [9.0], 95) == 1.0
    assert stats.percentile([1.0] * 18 + [9.0, 9.0], 95) == 9.0
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def _req(rid, arrival, n, plen=4):
    from repro.launch import serve
    r = serve.Request(rid=rid, prompt=np.zeros(plen, np.int32), max_new=n,
                      arrival=arrival)
    r.tokens = [1] * n
    return r


def test_serving_rates_and_tails_cover_the_whole_window():
    reqs = [_req(0, 0.0, 3), _req(1, 1.0, 3), _req(2, 9.5, 2)]
    rec = types.SimpleNamespace(
        stamps={0: [0.5, 1.5, 2.5], 1: [1.25, 2.0, 3.0], 2: [10.5, 11.0]},
        end=11.0, late=[0.0, 0.001, 0.0],
        calls=[serving.Call("admit", 0.0, 0.5),
               serving.Call("admit", 1.0, 1.25),
               serving.Call("admit", 10.0, 10.5)])
    cell = types.SimpleNamespace(reqs=reqs, traffic={"kind": "open_loop"})
    out = serving.end_to_end(cell, rec, 10.0, 0.0)
    v, info = out["values"], out["info"]
    # six tokens land inside the 10 s window; the rate is over all of it
    assert v["output_tokens_per_s"] == pytest.approx(0.6)
    # TTFT from the scheduled arrival, every due request: 0.5, 0.25, 1.0
    assert v["ttft_p95_ms"] == pytest.approx(1000.0)
    assert info["ttft_p50_ms"] == pytest.approx(500.0)
    # gaps ending inside the window: 1, 1, 0.75, 1 (11.0 is after it)
    assert info["itl_gaps"] == 4
    assert v["itl_p95_ms"] == pytest.approx(1000.0)
    # the gap 0.5 -> 1.5 holds the admission that ended at 1.25
    assert info["itl_share_with_admission"] == pytest.approx(0.25)
    assert out["attempted"] == 3 and out["failed"] == 0


def test_an_unfinished_request_fails_and_counts_in_the_tail():
    reqs = [_req(0, 0.0, 2), _req(1, 0.5, 2)]
    reqs[1].tokens = []
    rec = types.SimpleNamespace(stamps={0: [0.1, 0.2]}, calls=[], late=[],
                                end=61.0)
    cell = types.SimpleNamespace(reqs=reqs, traffic={"kind": "open_loop"})
    out = serving.end_to_end(cell, rec, 1.0, 0.0)
    assert out["failed"] == 1
    # counted at the loop's end, a lower bound, not dropped
    assert out["values"]["ttft_p95_ms"] == pytest.approx(1e3 * 60.5)


STABLELM = common.config("stablelm-1.6b")


def test_matmul_params_of_stablelm():
    per_layer = 4 * 2048 * 2048 + 3 * 2048 * 5632
    assert work.matmul_params(STABLELM) == \
        24 * per_layer + 2048 * 100352 + 2048


def test_forward_flops_and_attention():
    c = dict(STABLELM, num_hidden_layers=1)
    assert work.attention_flops(c, 10) == 4 * 32 * 64 * 10
    assert work.causal_context_sum(4) == 10
    assert work.forward_flops(c, 3, 10) == \
        2 * work.matmul_params(c) * 3 + 4 * 32 * 64 * 10


def test_decode_attention_work_counts_held_tokens_only():
    m = common.reader("decode_attention_roofline")
    f, b = m.work(STABLELM, (100, 300))
    assert f == 4 * 32 * 64 * 400 * 24
    assert b == (2 * 400 * 32 * 64 * 2 + 2 * (2 * 32 * 64 * 2)) * 24


def test_flash_attention_work_of_a_causal_step():
    m = common.reader("flash_attention_roofline")
    c = dict(STABLELM, num_hidden_layers=1)
    f, b = m.work(c, 1, 128)
    fwd = 4 * 32 * 64 * 128 * 129 / 2
    assert f == 3 * fwd
    assert b == 2 * (6 * 128 * 32 * 64 + 6 * 128 * 32 * 64)


PEAKS = common.peaks("TPU v5 lite")


def _trace(kernel_s, window_s=1.0, busy_s=0.5):
    return types.SimpleNamespace(
        window_s=window_s, busy_s=busy_s,
        op_time=lambda pats: (kernel_s, 7 if kernel_s else 0),
        module_time=lambda pat: (0.2, 4))


def test_roofline_is_least_time_over_kernel_time():
    m = common.reader("decode_attention_roofline")
    held = (1024,) * 8
    f, b = m.work(STABLELM, held)
    least = max(f / PEAKS["bf16_flops_per_s"], b / PEAKS["hbm_bytes_per_s"])
    ctx = types.SimpleNamespace(
        kind="serve", config=STABLELM, peaks=PEAKS, trace=_trace(4 * least),
        stretch=types.SimpleNamespace(calls=[serving.Call(
            "decode", 0, 1, held=held)]))
    assert m.read(ctx) == pytest.approx(25.0)
    # a kernel that never ran gives no reading, not 0
    ctx.trace = _trace(0.0)
    assert m.read(ctx) is None


def test_mfu_and_idle_readers():
    c = STABLELM
    mfu = common.reader("mfu.serve")
    calls = [serving.Call("decode", 0, 1, held=(10, 20)),
             serving.Call("admit", 1, 2, prompt_tokens=6, cached_tokens=0,
                          prompts=(6,))]
    ctx = types.SimpleNamespace(kind="serve", config=c, peaks=PEAKS,
                                trace=_trace(0.1, window_s=2.0),
                                stretch=types.SimpleNamespace(calls=calls))
    want = work.forward_flops(c, 2, 30) + work.forward_flops(c, 6, 21)
    assert mfu.read(ctx) == pytest.approx(
        100 * want / (2.0 * PEAKS["bf16_flops_per_s"]))
    assert common.reader("idle_share.serve").read(ctx) == \
        pytest.approx(75.0)
    assert common.reader("idle_share.train").read(ctx) is None
    assert common.reader("decode_step_ms").read(ctx) == pytest.approx(50.0)


def test_counter_readers():
    calls = [serving.Call("admit", 0, 1, pages_requested=8, pages_alloced=2),
             serving.Call("admit", 1, 2, pages_requested=2, pages_alloced=2),
             serving.Call("decode", 2, 3, held=(1,))]
    ctx = types.SimpleNamespace(kind="serve", calls=calls,
                                occupancy=[0.5, 1.0, 0.75])
    assert common.reader("prefix_hit_share").read(ctx) == \
        pytest.approx(60.0)
    assert common.reader("occupancy").read(ctx) == pytest.approx(75.0)
    ctx.calls = calls[2:]
    assert common.reader("prefix_hit_share").read(ctx) is None


def test_peaks_refuse_an_unknown_device_kind():
    assert PEAKS["bf16_flops_per_s"] == 197e12
    assert PEAKS["hbm_bytes_per_s"] == 819e9
    with pytest.raises(common.UnknownDevice):
        common.peaks("TPU v9 imaginary")
