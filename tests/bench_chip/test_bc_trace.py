"""The reduction from trace events to busy, idle, module and op times."""
from __future__ import annotations

import json

import pytest

import bc_tiny  # noqa: F401
from benchmarks.chip import trace
from benchmarks.chip.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def synthetic():
    return [
        _ev(HOST, "python", "bench.decode", 0, 100),
        _ev(HOST, "python", "bench.admit", 100, 100),
        _ev(HOST, "python", "bench.wait", 200, 50),
        _ev(DEV, "XLA Modules", "jit_serve_step(1)", 10, 60),
        # a loop op holds the step's ops: its time is theirs
        _ev(DEV, "XLA Ops", "%while.5 = (s32[]) while(...)", 10, 45),
        _ev(DEV, "XLA Ops", "%fusion.1 = bf16[8] fusion(%_decode_call.2)",
            10, 20),
        _ev(DEV, "XLA Ops", "%_decode_call.2 = bf16[8] custom-call(...)",
            25, 30),
        _ev(DEV, "XLA Modules", "jit_prefill_step(2)", 120, 60),
        _ev(DEV, "XLA Ops", "%fusion.3 = bf16[8] fusion(...)", 120, 60),
        # another device and events outside the spans do not count
        _ev("/device:TPU:1", "XLA Ops", "fusion.9", 0, 250),
        _ev(DEV, "XLA Ops", "fusion.4", 300, 10),
    ]


def test_busy_idle_and_gaps():
    r = trace.reduce(synthetic())
    assert r.window_s == pytest.approx(250e-9)
    # union of [10, 55) and [120, 180)
    assert r.busy_s == pytest.approx(105e-9)
    assert r.gaps == [(0.0, 10.0), (55.0, 120.0), (180.0, 250.0)]
    idle = dict(r.idle_by_span())
    assert idle["bench.decode"] == pytest.approx(10e-9 + 45e-9)
    assert idle["bench.admit"] == pytest.approx(20e-9 + 20e-9)
    assert idle["bench.wait"] == pytest.approx(50e-9)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


def test_module_and_op_times():
    r = trace.reduce(synthetic())
    assert r.module_time("serve_step") == (pytest.approx(60e-9), 1)
    assert r.module_time("prefill_step") == (pytest.approx(60e-9), 1)
    # by the op's own name, not an operand that names it
    assert r.op_time(["_decode_call"]) == (pytest.approx(30e-9), 1)
    assert r.op_time(["fusion"]) == (pytest.approx(80e-9), 2)
    assert r.op_time(["while"]) == (0, 0)
    assert r.top_ops(2) == [["fusion.3", pytest.approx(60e-9)],
                            ["_decode_call.2", pytest.approx(30e-9)]]


def test_no_device_or_no_span_reads_nothing():
    ev = synthetic()
    assert trace.reduce([e for e in ev if e.plane == HOST]) is None
    assert trace.reduce([e for e in ev if e.plane != HOST]) is None


def test_events_round_trip_as_json():
    ev = synthetic()
    back = [Event.from_json(json.loads(json.dumps(e.to_json())))
            for e in ev]
    assert back == ev
