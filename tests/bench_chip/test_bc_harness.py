"""Whole runs of the harness at reduced widths on the CPU, with the chip
check skipped and the attention ops on the Pallas kernels in interpret
mode: a sound run is ``correct``; a run whose timed path is broken
underneath is not."""
from __future__ import annotations

import argparse
import os
import sys

import pytest

import bc_tiny
from benchmarks.chip import common, serving, training

sys.path.insert(0, os.path.join(bc_tiny.ROOT, "benchmarks", "chip"))
import run as run_mod  # noqa: E402

sys.path.pop(0)

SEED = 2 ** 31 + 77


def _harness(monkeypatch, config: str, traffic: dict, layers: int = 2):
    cfgj, cfg = bc_tiny.tiny_config(config, layers)
    bc_tiny.use_program(monkeypatch, cfg)
    bc_tiny.pallas_kernels(monkeypatch)
    monkeypatch.setattr(common, "config", lambda name, here=None: cfgj)
    monkeypatch.setattr(common, "traffic", lambda name, here=None: traffic)
    monkeypatch.setattr(run_mod, "device_info", lambda cell: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    return cfgj, cfg


def _run(workload, seconds=3.0, trace=0):
    return run_mod.run(argparse.Namespace(
        workload=workload, seed=SEED, seconds=seconds, trace=trace,
        readings=0))


def _broken_serve_step(monkeypatch, cfg):
    """Every decoded token altered where the serve step produces it."""
    from repro.core import llm_a3c
    orig = llm_a3c.make_serve_step

    def make(c, *, sample=True):
        step = orig(c, sample=sample)

        def altered(*a, **k):
            tok, value, cache = step(*a, **k)
            return (tok + 1) % c.vocab_size, value, cache
        return altered
    monkeypatch.setattr(llm_a3c, "make_serve_step", make)


@pytest.mark.parametrize("fault", [None, "token_altered"])
def test_serving_run(monkeypatch, fault):
    _, cfg = _harness(monkeypatch, "stablelm-1.6b", bc_tiny.tiny_chat())
    if fault:
        _broken_serve_step(monkeypatch, cfg)
    out = _run("stablelm-1.6b.chat")
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device", "checks"}
    assert list(out)[-1] == "checks"
    assert out["attempted"] == 12 and out["failed"] == 0
    m = out["metrics"]
    assert set(m) == {"output_tokens_per_s", "ttft_p95_ms", "itl_p95_ms",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    gap = out["checks"]["served_logit_gap"]
    assert out["correct"] is (fault is None), gap


def test_traced_serving_run_marks_its_stretch(monkeypatch, tmp_path):
    """The profiler runs across the whole window; the stretch it is read
    over holds engine calls, and the counters' metrics are reported (the
    CPU has no device plane, so the trace's own metrics are absent)."""
    cfgj, _ = _harness(monkeypatch, "stablelm-1.6b", bc_tiny.tiny_chat())
    cell = serving.setup(cfgj, bc_tiny.tiny_chat(), SEED, 4.0)
    rec, origin, tracer = serving.run_window(cell, 4.0, str(tmp_path))
    st = tracer.stretch
    assert tracer.done and origin <= st.t0 < st.t1 <= origin + rec.end
    assert st.calls and all(st.t0 <= c.t0 and c.t1 <= st.t1 + 1.0
                            for c in st.calls)
    assert not rec.spans
    v5e = common.peaks("TPU v5 lite")
    monkeypatch.setattr(common, "peaks", lambda kind, here=None: v5e)
    out = _run("stablelm-1.6b.chat", seconds=4.0, trace=1)
    assert out["correct"] is True
    assert {"occupancy", "prefix_hit_share"} <= set(out["metrics"])


def test_serving_window_counts_what_it_served(monkeypatch):
    from repro.kernels import dispatch
    cfgj, _ = _harness(monkeypatch, "stablelm-1.6b", bc_tiny.tiny_chat())
    t = bc_tiny.tiny_chat()
    dispatch.clear_decision_log()
    cell = serving.setup(cfgj, t, SEED, 2.0)
    rec, origin, _ = serving.run_window(cell, 2.0)
    ops = {r["op"]: r["backend"] for r in dispatch.decision_summary()}
    assert ops["flash_append"] == ops["decode_attention"] == "pallas"
    assert origin == 0.0
    for r in cell.reqs:
        assert len(r.tokens) == r.max_new
        assert len(rec.stamps[r.rid]) == r.max_new
        assert rec.stamps[r.rid][0] >= r.arrival
        assert rec.stamps[r.rid] == sorted(rec.stamps[r.rid])
    admits = [c for c in rec.calls if c.kind == "admit"]
    assert sum(len(c.prompts) for c in admits) == len(cell.reqs)
    assert sum(c.prompt_tokens for c in admits) == \
        sum(len(r.prompt) for r in cell.reqs)


def _broken_train_step(monkeypatch, fault):
    from repro.core import llm_a3c
    orig = llm_a3c.make_train_step

    def make(cfg, opt, **kw):
        step = orig(cfg, opt, **kw)

        def broken(params, opt_state, batch, i):
            if fault == "state_unchanged":
                _, _, m = step(params, opt_state, batch, i)
                return params, opt_state, m
            half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
            return step(params, opt_state, half, i)
        return broken
    monkeypatch.setattr(llm_a3c, "make_train_step", make)


@pytest.mark.parametrize("fault", [None, "state_unchanged", "half_batch"])
def test_training_run(monkeypatch, fault):
    _harness(monkeypatch, "stablelm-1.6b-a3c-train",
             {"kind": "train_batches", "batch": 2, "seq": 128})
    if fault:
        _broken_train_step(monkeypatch, fault)
    out = _run("stablelm-1.6b-a3c-train.b4x1024", seconds=2.0)
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert out["attempted"] > 0
    checks = out["checks"]
    assert out["correct"] is (fault is None), checks
    if fault == "state_unchanged":
        # nothing moved: the change reads 1 by its measure
        assert checks["delta_norm_rel_gap"]["value"] == pytest.approx(1.0)


def test_training_readings_tie_to_the_reference(monkeypatch):
    cfgj, _ = _harness(monkeypatch, "stablelm-1.6b-a3c-train",
                       {"kind": "train_batches", "batch": 2, "seq": 128})
    cell = training.setup(cfgj, {"kind": "train_batches", "batch": 2,
                                 "seq": 128}, SEED)
    training.free_program(cell)
    sound = training.compare(cell.got, training.reference_run(cell))
    # same arithmetic in f32 on both sides here: round-off only
    assert sound["loss_rel_gap"] < 1e-5
    for k in ("grad_norm_rel_gap", "delta_norm_rel_gap", "grad_elem_err",
              "delta_elem_err"):
        assert sound[k] < 1e-4, (k, sound[k])


def test_serving_control_reads_above_the_sound_run(monkeypatch):
    """The control, the reference in int8 in the program's place, reads no
    narrower a gap than the program at the same prompts and tokens (at
    this size few of its first choices differ; ``test_bc_reference`` shows
    its logits move)."""
    cfgj, _ = _harness(monkeypatch, "stablelm-1.6b", bc_tiny.tiny_chat())
    cell = serving.setup(cfgj, bc_tiny.tiny_chat(), SEED, 2.0)
    serving.run_window(cell, 2.0)
    serving.free_engine(cell)
    out = serving.check(cell, serving.check_sample(cell, SEED),
                        control=True)
    assert out["tokens_compared"] > 0
    assert out["control_logit_gap"] >= out["served_logit_gap"]
    assert out["control_logit_gap_mean"] >= out["served_logit_gap_mean"]


def test_training_control_and_half_batch_read_above_the_sound_run(
        monkeypatch):
    tj = {"kind": "train_batches", "batch": 2, "seq": 128}
    # four layers: int8 rounding grows with depth, and at two the control
    # reads within 1% of the element limit at these widths
    cfgj, _ = _harness(monkeypatch, "stablelm-1.6b-a3c-train", tj, layers=4)
    cell = training.setup(cfgj, tj, SEED)
    training.free_program(cell)
    ref = training.reference_run(cell)
    sound = training.compare(cell.got, ref)
    ctrl = training.compare(training.reference_run(cell, quant=True), ref)
    half = training.compare(training.reference_run(cell, rows=[0]), ref)
    keys = ("loss_rel_gap", "grad_norm_rel_gap", "delta_norm_rel_gap",
            "grad_elem_err", "delta_elem_err")
    assert all(ctrl[k] > 10 * sound[k] for k in keys)
    limits = cfgj["correct"]
    assert set(limits) == set(keys)
    assert run_mod.passes(run_mod.checks_of(sound, limits))
    # through the run's own comparison, the control and the fault fail
    assert not run_mod.passes(run_mod.checks_of(ctrl, limits)), ctrl
    assert not run_mod.passes(run_mod.checks_of(half, limits)), half


def test_closed_backlog_run(monkeypatch):
    """The closed-backlog mix, not yet a cell, run as one would be."""
    t = {"kind": "closed_backlog", "initial": 2, "queued": 2,
         "prompt": {"dist": "uniform", "min": 64, "max": 128},
         "output_min": 40, "check_requests": 2}
    _harness(monkeypatch, "yi-6b", t)
    tbl = common.table()
    tbl["workloads"].append({"name": "yi-6b.long-decode", "config": "yi-6b",
                             "traffic": "long-decode", "chips": 1,
                             "why": "closed backlog"})
    for m in tbl["end_to_end"]:
        if m["name"] == "output_tokens_per_s":
            m["workloads"].append("yi-6b.long-decode")
    monkeypatch.setattr(common, "table", lambda path=None: tbl)
    out = _run("yi-6b.long-decode", seconds=2.0)
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert out["metrics"]["output_tokens_per_s"]["value"] > 0
    assert out["attempted"] >= 2 and out["correct"] is True
