"""What every part of the chip benchmark reads: the cell table, the data
files found by name, the peaks table, and seeds.

A cell is one entry of ``workloads`` in ``BENCHMARK.json`` at the repository
root.  It names a configuration (``configs/<name>.json`` here) and a traffic
mix (``traffic/<name>.json`` here); which per-layer and end-to-end metrics it
reports is read from the same table (each metric's ``workloads`` key), and
each per-layer metric is the reader ``metrics/<name>.py``.  Adding a cell,
a configuration, a mix or a metric therefore adds files and table entries
and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
from typing import Optional

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TABLE = ROOT / "BENCHMARK.json"


class UnknownDevice(RuntimeError):
    """The device kind has no row in ``peaks.json``."""


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def table(path: Optional[pathlib.Path] = None) -> dict:
    return _json(path or TABLE)


def cell(name: str, tbl: Optional[dict] = None) -> dict:
    tbl = tbl or table()
    for w in tbl["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                   f"(have {[w['name'] for w in tbl['workloads']]})")


def config(name: str, here: pathlib.Path = HERE) -> dict:
    return _json(here / "configs" / f"{name}.json")


def traffic(name: str, here: pathlib.Path = HERE) -> dict:
    return _json(here / "traffic" / f"{name}.json")


def peaks(device_kind: str, here: pathlib.Path = HERE) -> dict:
    rows = _json(here / "peaks.json")["devices"]
    if device_kind not in rows:
        raise UnknownDevice(f"device kind {device_kind!r} is not in "
                            f"peaks.json (have {sorted(rows)})")
    return rows[device_kind]


def metrics_of(cell_name: str, section: str, tbl: Optional[dict] = None):
    """The ``end_to_end`` or ``per_layer`` entries this cell reports: those
    whose ``workloads`` list names it, or that have no such list."""
    tbl = tbl or table()
    return [m for m in tbl[section]
            if "workloads" not in m or cell_name in m["workloads"]]


def reader(metric_name: str, here: pathlib.Path = HERE):
    """``metrics/<name>.py``, loaded by file name (names carry dots)."""
    path = here / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "chip_metric_" + metric_name.replace(".", "_").replace("-", "_"),
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed32(seed: int) -> int:
    """A 32-bit key for JAX from any whole-number seed (the driver's run
    past 2**31); numpy's seed sequence takes integers of any size."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0])


def model_config(c: dict):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture at the file's depth, after checking that every
    width in the file is the program's."""
    from repro.configs import get_config
    base = get_config(c["program_arch"])
    want = {"d_model": c["hidden_size"], "n_heads": c["num_attention_heads"],
            "n_kv_heads": c["num_key_value_heads"], "hd": c["head_dim"],
            "d_ff": c["intermediate_size"], "vocab_size": c["vocab_size"],
            "rope_theta": c["rope_theta"], "norm": c["norm"],
            "qkv_bias": c["use_qkv_bias"],
            "tie_embeddings": c["tie_word_embeddings"]}
    got = {k: getattr(base, k) for k in want}
    if got != want:
        raise ValueError(f"{c['name']}: the program's {c['program_arch']} "
                         f"is {got}, the configuration file says {want}")
    return dataclasses.replace(base, n_layers=c["num_hidden_layers"])
