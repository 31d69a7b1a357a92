"""Small stand-ins for the chip benchmark's tests: the configurations cut to
the program's ``reduced()`` widths, and a switch that sends the attention
ops through the Pallas kernels in interpret mode, as on the chip."""
from __future__ import annotations

import copy
import dataclasses
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.chip import common  # noqa: E402


def tiny_config(name: str, layers: int = 2):
    """(configuration dict, program config) at ``reduced()`` widths."""
    from repro.configs import get_config
    c = copy.deepcopy(common.config(name))
    base = get_config(c["program_arch"]).reduced()
    c.update(hidden_size=base.d_model, num_attention_heads=base.n_heads,
             num_key_value_heads=base.n_kv_heads, head_dim=base.hd,
             intermediate_size=base.d_ff, vocab_size=base.vocab_size,
             num_hidden_layers=layers)
    c["assumed"]["rope_inv_freq_dim"] = base.hd
    if "serving" in c:
        c["serving"].update(slots=2, cache_len=256, page_size=128, pages=5,
                            chunk=128)
    return c, dataclasses.replace(base, n_layers=layers)


def use_program(monkeypatch, cfg) -> None:
    """Make the harness build the program at the tiny widths."""
    monkeypatch.setattr(common, "model_config",
                        lambda c: dataclasses.replace(
                            cfg, n_layers=c["num_hidden_layers"]))


def tiny_chat() -> dict:
    t = copy.deepcopy(common.traffic("chat"))
    t.update(rate_per_s=4.0, drain_s=30, check_requests=3)
    t["prompt"].update(median=96, max=200)
    t["output"].update(median=6, min=3, max=12)
    return t


def pallas_kernels(monkeypatch) -> None:
    """Run the attention ops through the Pallas kernels (interpret mode
    off the chip) instead of the CPU's jnp arms."""
    from repro.kernels import dispatch
    for name in ("flash_attention", "flash_attention_append_paged",
                 "decode_attention_paged"):
        orig = getattr(dispatch, name)

        def forced(*a, _orig=orig, **k):
            k["backend"] = "pallas"
            return _orig(*a, **k)
        monkeypatch.setattr(dispatch, name, forced)
