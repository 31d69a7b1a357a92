"""The run command's refusals, and a cell added from new files alone."""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import types

import pytest

import bc_tiny
from benchmarks.chip import common, generate

RUN = os.path.join(bc_tiny.ROOT, "benchmarks", "chip", "run.py")
ARGS = ["--workload", "stablelm-1.6b.chat", "--seed", str(2 ** 31 + 9),
        "--seconds", "10", "--trace", "0"]


def _run(cwd, script):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, script, *ARGS], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_exits_non_zero_without_a_tpu():
    p = _run(bc_tiny.ROOT, RUN)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_exits_non_zero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(bc_tiny.ROOT, "BENCHMARK.json"), tmp_path)
    for path in json.load(open(tmp_path / "BENCHMARK.json"))["paths"]:
        shutil.copytree(os.path.join(bc_tiny.ROOT, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path, os.path.join("benchmarks", "chip", "run.py"))
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _devices(platform, kind, n=1):
    return [types.SimpleNamespace(platform=platform, device_kind=kind)] * n


@pytest.mark.parametrize("devs, why", [
    (_devices("tpu", "TPU v9 imaginary"), "peaks.json"),
    (_devices("cpu", "cpu"), "no TPU"),
    (_devices("gpu", "H100"), "no TPU"),
])
def test_device_check_refuses(monkeypatch, devs, why):
    import jax
    sys.path.insert(0, os.path.dirname(RUN))
    try:
        import run as run_mod
    finally:
        sys.path.remove(os.path.dirname(RUN))
    monkeypatch.setattr(jax, "devices", lambda: devs)
    with pytest.raises(run_mod.Refused, match=why):
        run_mod.device_info({"name": "x", "chips": 1})


def test_device_check_counts_chips(monkeypatch):
    import jax
    sys.path.insert(0, os.path.dirname(RUN))
    try:
        import run as run_mod
    finally:
        sys.path.remove(os.path.dirname(RUN))
    monkeypatch.setattr(jax, "devices",
                        lambda: _devices("tpu", "TPU v5 lite", 1))
    with pytest.raises(run_mod.Refused, match="needs 4 chips"):
        run_mod.device_info({"name": "x", "chips": 4})
    assert run_mod.device_info({"name": "x", "chips": 1})["kind"] == \
        "TPU v5 lite"


def test_a_new_cell_from_new_files_alone(tmp_path):
    """A later change adds a configuration, a mix, a metric and a cell as
    new files and table entries; the harness finds each by its name."""
    here = tmp_path / "chip"
    shutil.copytree(common.HERE, here,
                    ignore=shutil.ignore_patterns("__pycache__"))
    tbl = common.table()
    cfg = dict(common.config("stablelm-1.6b"), name="stablelm-1.6b-long")
    cfg["serving"] = dict(cfg["serving"], cache_len=4096)
    (here / "configs" / "stablelm-1.6b-long.json").write_text(
        json.dumps(cfg))
    mix = dict(common.traffic("chat"), rate_per_s=0.5)
    (here / "traffic" / "slow-chat.json").write_text(json.dumps(mix))
    (here / "metrics" / "queue_wait_ms.py").write_text(
        "def read(ctx):\n    return 1.5\n")
    tbl["workloads"].append({"name": "stablelm-1.6b-long.slow-chat",
                             "config": "stablelm-1.6b-long",
                             "traffic": "slow-chat", "chips": 1,
                             "why": "dummy"})
    tbl["per_layer"].append({"name": "queue_wait_ms", "unit": "ms",
                             "better": "lower", "source": "host_clock",
                             "layer": "scheduler", "moves": "ttft_p95_ms",
                             "workloads": ["stablelm-1.6b-long.slow-chat"]})
    new = common.cell("stablelm-1.6b-long.slow-chat", tbl)
    assert [w["name"] for w in tbl["workloads"]][-1] == new["name"]
    c = common.config(new["config"], here)
    t = common.traffic(new["traffic"], here)
    assert c["serving"]["cache_len"] == 4096
    assert len(generate.requests(t, 100, 1, 10.0, 4096)) == 5
    names = [m["name"] for m in common.metrics_of(new["name"], "per_layer",
                                                  tbl)]
    assert names == ["queue_wait_ms"]
    assert common.reader("queue_wait_ms", here).read(None) == 1.5
    e2e = [m["name"] for m in common.metrics_of(new["name"], "end_to_end",
                                                tbl)]
    assert e2e == ["setup_s"]
