"""``chip_smoke.py``'s phases at ``reduced()`` size on the CPU.

The script itself refuses to run without a TPU; its phases are functions of
their settings, so the same code paths (engine, kernels against oracles,
train step, decode-cp against one device) run here small, with the Pallas
kernels in interpret mode where a phase calls them explicitly.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod          # dataclasses resolve their module
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def cfg(smoke):
    return get_config(smoke.ARCH).reduced()


def _serve_settings(smoke, **kw):
    base = dict(slots=2, requests=3, prompt_range=(128, 160),
                gen_range=(2, 4), cache_len=256, chunk=128, page_size=128,
                pages=0)
    base.update(kw)
    return smoke.ServeSettings(**base)


def test_main_refuses_without_tpu(smoke, capsys):
    assert jax.devices()[0].platform != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr().out
    assert '"ok"' not in out


def test_serve_phase_reduced(smoke, cfg):
    s = _serve_settings(smoke)
    rec = smoke.serve_phase(cfg, s)
    assert rec["requests"] == s.requests
    assert rec["generated_tokens"] >= s.requests * s.gen_range[0]
    assert {r["op"] for r in rec["kernel_dispatch"]} >= {"decode_paged",
                                                         "append_paged"}


def test_train_phase_reduced(smoke, cfg):
    rec = smoke.train_phase(cfg, smoke.TrainSettings(layers=2, batch=2,
                                                     seq=128, steps=3))
    assert rec["layers"] == 2 and len(rec["losses"]) == 3
    assert np.all(np.isfinite(rec["losses"]))
    json.dumps(rec)


def test_kernels_phase_small(smoke):
    res = smoke.kernels_phase(smoke.KernelSettings(
        batch=2, seq=256, train_batch=1, chunk=128, heads=4, kv_heads=2,
        head_dim=64, d_model=256, norm_rows=256, rmsprop_shape=(64, 128)))
    want = {"flash_fwd", "flash_bwd_dq", "flash_bwd_dk", "flash_bwd_dv",
            "append_bf16", "append_int8", "decode_bf16", "decode_int8",
            "decode_partials_bf16", "decode_partials_int8", "rmsnorm",
            "rmsprop_g", "rmsprop_update"}
    assert set(res) == want
    json.dumps(res)


def test_decode_cp_phase_one_device_mesh(smoke, cfg):
    rec = smoke.decode_cp_phase(cfg, _serve_settings(smoke), n_dev=1)
    assert rec["prefill_greedy_agree"]
    assert rec["first_token_agreement"] == 1.0
    assert rec["first_step_logit_max_abs_diff"] <= smoke.CP_LOGIT_TOL
    assert rec["prefill_logit_max_abs_diff"] <= smoke.CP_LOGIT_TOL
