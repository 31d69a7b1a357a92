"""Chunked-prefill benchmark: fused append path vs the masked-sdpa prefix
baseline (the PR-4 path this PR deletes).

Two legs, both landing in a root-level ``BENCH_prefill.json`` (uploaded
as a CI artifact — the start of the per-PR prefill perf trajectory):

  * **measured** — multi-chunk prefill tokens/s through the real engine
    path (``llm_a3c.make_prefill_step``) at prompt 512 / 2048, against a
    faithful in-bench reconstruction of the masked-sdpa prefix branch.
    On TPU the fused number rides the append kernel; off-TPU auto
    dispatch (correctly) serves the jnp append oracle, so the measured
    CPU ratio reflects the oracle, not the kernel — interpret-mode
    kernel timings are emulation-only (see bench_kernels.py).
  * **analytic_hbm** — the attention term's HBM bytes from the traffic
    model (``traffic.prefill_attn_bytes``): the masked path materializes
    f32 (C, Sk) scores + Hq-repeated K/V streams every chunk, the fused
    kernel keeps score tiles in VMEM — the ratio that governs the TPU
    roofline.

The multi-device decode-cp serving path is exercised by
``tests/test_serve_engine.py::test_engine_decode_cp_smoke`` and, on four
chips, by ``python chip_smoke.py --chips 4``.

  PYTHONPATH=src python -m benchmarks.run --quick
"""
from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp

from benchmarks import common

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_JSON = os.path.join(ROOT, "BENCH_prefill.json")


# ---------------------------------------------------------------------------
# masked-sdpa baseline: faithful reconstruction of the pre-append
# attend_prefill (PR 4) — chunk 0 through the flash path, later chunks
# over the cache prefix via concat + repeat_kv + masked dense sdpa
# ---------------------------------------------------------------------------

def _attend_prefill_masked(params, x, cache, pos0, cfg, *, window=None,
                           use_rope=True, backend="auto", true_len=None):
    from repro.kernels import dispatch
    from repro.models import attention as attn
    from repro.models import common as cm

    n_h, n_kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    b, c, _ = x.shape
    q = attn._split_heads(cm.linear(params["wq"], x), n_h, hd)
    k = attn._split_heads(cm.linear(params["wk"], x), n_kv, hd)
    v = attn._split_heads(cm.linear(params["wv"], x), n_kv, hd)
    if use_rope:
        positions = pos0 + jnp.arange(c)[None]
        cos, sin = cm.rope_cos_sin(positions, hd, cfg.rope_theta)
        rd = getattr(cfg, "rotary_dim", None)
        q = cm.apply_rope(q, cos, sin, rotary_dim=rd)
        k = cm.apply_rope(k, cos, sin, rotary_dim=rd)
    cache_len = cache["k"].shape[1]
    ck = jax.lax.dynamic_update_slice(
        cache["k"], k.astype(cache["k"].dtype), (0, pos0, 0, 0))
    cv = jax.lax.dynamic_update_slice(
        cache["v"], v.astype(cache["v"].dtype), (0, pos0, 0, 0))
    new_cache = {"k": ck, "v": cv,
                 "index": jnp.asarray(pos0 + c, jnp.int32)}
    if pos0 == 0:
        o = dispatch.flash_attention(q, k, v, causal=True, window=window,
                                     backend=backend)
    else:
        k_pre = cache["k"][:, :min(pos0, cache_len)].astype(q.dtype)
        v_pre = cache["v"][:, :min(pos0, cache_len)].astype(q.dtype)
        k_all = jnp.concatenate([k_pre, k], axis=1)
        v_all = jnp.concatenate([v_pre, v], axis=1)
        kpos_all = jnp.concatenate([jnp.arange(k_pre.shape[1]),
                                    pos0 + jnp.arange(c)])
        qpos = pos0 + jnp.arange(c)
        mask = (kpos_all[None, :] >= 0) & \
            (kpos_all[None, :] <= qpos[:, None])
        n_rep = n_h // n_kv
        o = attn.sdpa(q, attn._repeat_kv(k_all, n_rep),
                      attn._repeat_kv(v_all, n_rep), mask[None, None])
    return cm.linear(params["wo"], o.reshape(b, c, n_h * hd)), new_cache


def _prefill_tok_s(cfg, params, prompt_len: int, chunk: int,
                   masked: bool) -> float:
    """Wall tok/s for one full multi-chunk prefill chain (B=1)."""
    from repro.core import llm_a3c
    from repro.models import attention as attn
    from repro.models import model as M

    cache_len = prompt_len + 128
    prompt = jax.random.randint(jax.random.key(1), (1, prompt_len), 0,
                                cfg.vocab_size)
    orig = attn.attend_prefill
    if masked:
        attn.attend_prefill = _attend_prefill_masked
    try:
        step = llm_a3c.make_prefill_step(cfg)

        def chain():
            cache = M.init_cache(cfg, 1, cache_len, dtype=jnp.float32)
            for p0 in range(0, prompt_len, chunk):
                logits, cache = step(params, cache,
                                     {"tokens": prompt[:, p0:p0 + chunk]},
                                     pos0=p0)
            return logits

        us = common.timed(chain, iters=3)
    finally:
        attn.attend_prefill = orig
    return prompt_len * 1e6 / us


def run(*, arch: str = "stablelm-1.6b") -> list:
    from repro.configs import get_config
    from repro.launch import traffic
    from repro.models import model as M

    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.key(0))
    chunk = 128                          # the engine's default grid
    rows = [{"name": "prefill_meta", "us_per_call": 0.0,
             "derived": f"arch={cfg.name} backend={jax.default_backend()}"}]
    measured, analytic = [], []
    for prompt_len in (512, 2048):
        tok_m = _prefill_tok_s(cfg, params, prompt_len, chunk, masked=True)
        tok_f = _prefill_tok_s(cfg, params, prompt_len, chunk,
                               masked=False)
        measured.append({
            "prompt": prompt_len, "chunk": chunk,
            "masked_sdpa_tok_s": round(tok_m, 1),
            "fused_append_tok_s": round(tok_f, 1),
            "ratio": round(tok_f / tok_m, 3),
        })
        bm = traffic.prefill_attn_bytes(cfg, 1, prompt_len, chunk,
                                        fused=False)
        bf = traffic.prefill_attn_bytes(cfg, 1, prompt_len, chunk,
                                        fused=True)
        analytic.append({
            "prompt": prompt_len, "chunk": chunk,
            "masked_sdpa_attn_bytes": bm, "fused_append_attn_bytes": bf,
            "ratio": round(bm / bf, 2),
        })
        rows.append({
            "name": f"prefill_masked_sdpa_p{prompt_len}",
            "us_per_call": prompt_len * 1e6 / tok_m,
            "derived": f"tok_s={tok_m:.1f}"})
        rows.append({
            "name": f"prefill_fused_append_p{prompt_len}",
            "us_per_call": prompt_len * 1e6 / tok_f,
            "derived": f"tok_s={tok_f:.1f} vs_masked={tok_f / tok_m:.2f}x "
                       f"hbm_ratio={bm / bf:.1f}x"})

    record = {
        "arch": cfg.name,
        "platform": jax.default_backend(),
        "provenance": common.provenance(),
        "note": ("fused_append numbers ride the Pallas append kernel on "
                 "TPU; off-TPU auto dispatch serves the jnp append "
                 "oracle (Pallas runs interpret-only there), so the "
                 "measured off-TPU ratio is oracle-vs-masked — the "
                 "analytic_hbm ratio is the kernel's roofline term"),
        "measured": measured,
        "analytic_hbm": analytic,
    }
    with open(BENCH_JSON, "w") as f:
        json.dump(record, f, indent=1)
        f.write("\n")
    common.save_rows("prefill_append", rows)
    return rows


if __name__ == "__main__":
    for r in run():
        common.emit(r["name"], r["us_per_call"], r["derived"])
