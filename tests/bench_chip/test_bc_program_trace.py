"""The reduction of the program's own spans, scopes and counters
(``program_trace.py``), on hand-made events, and its runs at reduced widths
on the CPU."""
from __future__ import annotations

import argparse
import types

import pytest

import bc_tiny
from benchmarks.chip import common, program_trace as pt, serving, trace
from benchmarks.chip.trace import Event

DEV = "/device:TPU:0"
HOST = "/host:CPU"


def _ev(plane, line, name, start, dur):
    return Event(plane, line, name, float(start), float(dur))


def _span(name, start, end):
    return _ev(HOST, "python", name, start, end - start)


def _hlo(op_names):
    """A compiled module's text whose instructions carry these
    ``op_name``s."""
    return "\n".join(f'  %{k} = bf16[8]{{0}} fusion(%p), '
                     f'metadata={{op_name="{v}" stack_frame_id=1}}'
                     for k, v in op_names.items())


def decode_step():
    """One decode step in a ``bench.decode`` span: the program's spans
    inside it, the step's module and ops (two of them gathers), and the
    module's compiled text."""
    events = [
        _ev(HOST, "python", "bench.decode", 0, 100),
        _ev(DEV, "XLA Modules", "jit_serve_step(1)", 20, 60),
        _ev(DEV, "XLA Ops", "%gather.1 = bf16[8] gather(...)", 20, 10),
        _ev(DEV, "XLA Ops", "%copy.2 = bf16[8] copy(...)", 30, 15),
        _ev(DEV, "XLA Ops", "%_decode_call.3 = bf16[8] custom-call(...)",
            45, 35),
    ]
    spans = [_span("serve.decode", 5, 95),
             _span("serve.decode.pages", 5, 15),
             _span("serve.decode.dispatch", 15, 25),
             _span("serve.decode.wait", 25, 85),
             _span("serve.decode.retire", 85, 95)]
    hlo = {"gather.1": "jit(serve_step)/paged_gather/gather",
           "copy.2": "jit(serve_step)/paged_gather/concatenate",
           "_decode_call.3": "jit(serve_step)/decode_attention"}
    return events + spans, {"jit_serve_step": _hlo(hlo)}


def test_a_gap_goes_to_the_innermost_span():
    prog = pt.reduce(*decode_step())
    assert prog.red.gaps == [(0.0, 20.0), (80.0, 100.0)]
    idle = dict(prog.idle_by_span())
    want = {"bench.decode": 5 + 5, "serve.decode.pages": 10,
            "serve.decode.dispatch": 5, "serve.decode.wait": 5,
            "serve.decode.retire": 10}
    assert idle == {k: pytest.approx(v * 1e-9) for k, v in want.items()}
    assert sum(idle.values()) == pytest.approx(prog.red.window_s
                                               - prog.red.busy_s)
    # the spans' own totals, children included
    assert prog.idle_in("serve.decode") == pytest.approx(30e-9)
    assert prog.idle_in("bench.decode") == pytest.approx(40e-9)


def test_idle_outside_every_span_is_named_so():
    events, hlo = decode_step()
    events.append(_ev(HOST, "python", "bench.admit", 150, 50))
    idle = dict(pt.reduce(events, hlo).idle_by_span())
    assert idle[pt.OUTSIDE] == pytest.approx(50e-9)
    assert idle["bench.admit"] == pytest.approx(50e-9)


def test_scope_time_sums_only_scoped_ops():
    events, hlo = decode_step()
    events += [_ev(DEV, "XLA Modules", "jit_prefill_step(2)", 100, 40),
               _ev(DEV, "XLA Ops", "%gather.7 = bf16[8] gather(...)",
                   100, 40),
               _ev(HOST, "python", "bench.admit", 100, 50)]
    hlo["jit_prefill_step"] = _hlo(
        {"gather.7": "jit(prefill_step)/paged_gather/gather"})
    prog = pt.reduce(events, hlo)
    assert prog.scope_time("paged_gather") == (pytest.approx(65e-9), 3)
    assert prog.scope_time("paged_gather", module="serve_step") == \
        (pytest.approx(25e-9), 2)
    assert prog.scope_time("lm_head") == (0, 0)
    assert pt.paged_gather_ms(prog) == pytest.approx(25e-6)
    assert pt.decode_idle_ms(prog) == pytest.approx(30e-6)


@pytest.mark.parametrize("path,holds", [
    ("jit(step)/jvp(lm_head)/dot_general", True),
    ("jit(step)/transpose(jvp(lm_head))/dot_general", True),
    ("jit(serve_step)/lm_head/dot_general", True),
    ("params['lm_head']['w']", False),
    ("jit(step)/lm_head_bias/add", False),
    ("", False),
])
def test_in_scope(path, holds):
    assert pt.in_scope(path, "lm_head") is holds


def test_lm_head_ms_reads_forward_and_transpose():
    events = [_ev(HOST, "python", "bench.train_step", 0, 100),
              _ev(DEV, "XLA Modules", "jit_train_step(3)", 0, 100)]
    paths = {}
    for i, (t, path) in enumerate([
            (0, "jit(train_step)/jvp(lm_head)/dot_general"),
            (20, "jit(train_step)/transpose(jvp(lm_head))/dot_general"),
            (40, "jit(train_step)/optimizer/mul"),
            (60, "params['lm_head']")]):
        events.append(_ev(DEV, "XLA Ops", f"%fusion.{i} = f32[8] fusion()",
                          t, 20))
        paths[f"fusion.{i}"] = path
    prog = pt.reduce(events, {"jit_train_step": _hlo(paths)})
    assert pt.lm_head_ms(prog, steps=2) == pytest.approx(20e-6)
    assert pt.scope_ms(prog, "optimizer", 2) == pytest.approx(10e-6)
    assert prog.time_by_scope("train_step") == [
        ["jit(train_step)/jvp(lm_head)", pytest.approx(20e-9)],
        ["jit(train_step)/transpose(jvp(lm_head))", pytest.approx(20e-9)],
        ["jit(train_step)/optimizer", pytest.approx(20e-9)],
        ["params['lm_head']", pytest.approx(20e-9)]]


def test_op_names_from_compiled_text():
    text = ('  %fusion.3 = bf16[8]{0} fusion(%p), kind=kLoop, '
            'calls=%fused, metadata={op_name="jit(f)/paged_gather/gather" '
            'stack_frame_id=2}\n'
            '  ROOT %copy.1 = bf16[8]{0} copy(%fusion.3)\n')
    assert pt._op_names(text) == {"fusion.3": "jit(f)/paged_gather/gather"}
    assert pt.module_key("jit_serve_step(12)") == "jit_serve_step"
    # an op takes the op_name its instruction has in its own module
    events = [_ev(HOST, "python", "bench.decode", 0, 100),
              _ev(DEV, "XLA Modules", "jit_serve_step(12)", 0, 40),
              _ev(DEV, "XLA Ops", "%fusion.3 = bf16[8]{0} fusion(%p)", 0, 20),
              _ev(DEV, "XLA Modules", "jit_other(13)", 50, 40),
              _ev(DEV, "XLA Ops", "%fusion.3 = bf16[8]{0} fusion(%q)", 50,
                  20)]
    red = trace.reduce(events)
    assert pt.scopes_of(red, {"jit_serve_step": text}) == {
        (0.0, "fusion.3"): "jit(f)/paged_gather/gather"}


def test_counter_readings():
    req = types.SimpleNamespace
    reqs = [req(arrival=0.0, t_sched=0.25), req(arrival=1.0, t_sched=3.0),
            req(arrival=2.0, t_sched=-1.0), req(arrival=9.0, t_sched=9.5)]
    # due before the close at 5 s; the one never picked waits until 6 s
    assert pt.queue_wait_p95_ms(reqs, 5.0, 6.0) == pytest.approx(4000.0)
    assert pt.queue_wait_p95_ms([], 5.0, 6.0) is None
    # prefix hits: prompt tokens admitted exceed those computed
    eng = types.SimpleNamespace(prefill_tokens=900,
                                prefill_tokens_computed=300,
                                prefill_token_rows=1200)
    assert pt.prefill_pad_share(eng) == pytest.approx(75.0)
    eng.prefill_token_rows = 0
    assert pt.prefill_pad_share(eng) is None


def test_a_trace_without_program_spans_or_scopes_reads_nothing():
    events, _ = decode_step()
    prog = pt.reduce([e for e in events
                      if not e.name.startswith(pt.PROGRAM_PREFIX)], {})
    assert pt.paged_gather_ms(prog) is None
    assert pt.decode_idle_ms(prog) is None
    assert pt.lm_head_ms(prog, 3) is None


def test_load_keeps_the_harness_and_program_spans(tmp_path):
    """One pass over a CPU profiler trace: the ``bench.*`` and
    ``serve.*`` host spans, nothing else of the host."""
    import jax
    from repro.launch import tracing
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.decode"):
            with tracing.span("serve.decode"):
                with jax.profiler.TraceAnnotation("other"):
                    pass
    finally:
        jax.profiler.stop_trace()
    names = [e.name for e in pt.load(str(tmp_path))
             if not trace._is_device(e.plane)]
    assert sorted(names) == ["bench.decode", "serve.decode"]


def _reads(events):
    """Every per-layer reader of ``BENCHMARK.json`` on one trace."""
    import run as run_mod  # noqa: F401 (benchmarks/chip on sys.path)
    red = trace.reduce(events)
    call = serving.Call("decode", 0.0, 1.0, held=(100, 200))
    ctx = run_mod.Context(
        "serve", common.config("stablelm-1.6b"), common.traffic("chat"),
        common.peaks("TPU v5 lite"), red, calls=[call],
        stretch=serving.Stretch(0.0, 1.0, [call]), occupancy=[0.5])
    return {m["name"]: common.reader(m["name"]).read(ctx)
            for m in common.table()["per_layer"]}


def test_existing_metrics_read_the_same_with_program_events(monkeypatch):
    import os
    monkeypatch.syspath_prepend(os.path.join(bc_tiny.ROOT, "benchmarks",
                                             "chip"))
    events, _ = decode_step()
    bare = [e for e in events if not e.name.startswith(pt.PROGRAM_PREFIX)]
    assert _reads(events) == _reads(bare)
    assert _reads(bare)["decode_step_ms"] == pytest.approx(60e-6)


# -- whole runs on the CPU ---------------------------------------------------

SEED = 2 ** 31 + 91


def _patch(monkeypatch, config, traffic):
    cfgj, cfg = bc_tiny.tiny_config(config)
    bc_tiny.use_program(monkeypatch, cfg)
    monkeypatch.setattr(common, "config", lambda name, here=None: cfgj)
    monkeypatch.setattr(common, "traffic", lambda name, here=None: traffic)
    return cfgj


def _run(fn, cfgj, traffic, seconds, tmp_path):
    from repro.launch.tracing import CompileCounter
    args = argparse.Namespace(seed=SEED, seconds=seconds)
    with CompileCounter() as counter:
        return fn(cfgj, traffic, args, str(tmp_path), counter)


def test_serving_run_reads_the_counters(monkeypatch, tmp_path):
    """The CPU has no device plane: the trace's readings are absent, the
    counters' are read; warm-up compiles everything the window runs."""
    t = bc_tiny.tiny_chat()
    cfgj = _patch(monkeypatch, "stablelm-1.6b", t)
    out = _run(pt.run_serve, cfgj, t, 3.0, tmp_path)
    assert out["compiles_setup"] > 0
    assert out["compiles_in_window"] == 0
    assert out["queue_wait_p95_ms"] >= 0
    assert out["ttft_p95_ms"] >= out["queue_wait_p95_ms"]
    assert 0 < out["prefill_pad_share"] < 100
    assert out["prefill_token_rows"] > out["prefill_tokens"] > 0
    # chat has no prefix hits: every admitted prompt token is computed
    assert out["prefill_tokens_computed"] == out["prefill_tokens"]
    assert "paged_gather_ms" not in out


def test_training_run_counts_compiles(monkeypatch, tmp_path):
    t = {"kind": "train_batches", "batch": 2, "seq": 128}
    cfgj = _patch(monkeypatch, "stablelm-1.6b-a3c-train", t)
    out = _run(pt.run_train, cfgj, t, 2.0, tmp_path)
    assert out["compiles_in_window"] == 0 and out["steps"] > 0
    assert out["train_tokens_per_s"] > 0
