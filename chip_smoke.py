"""Smoke run on a TPU: the serve engine, the Pallas kernels and the A3C
train step at stablelm-1.6b's published widths, in one process.

    python chip_smoke.py            # one chip: serve, kernels, train
    python chip_smoke.py --chips 4  # four chips: decode-cp serving only

Phases (each a function of its settings, so tests can run them small):

  * serve   — all 24 layers through ``serve.run_engine`` (what
    ``serve.main`` calls): paged bf16 KV, greedy, 16 requests.  Every
    request must finish with its requested token count, each first token
    must match the training forward's argmax wherever that argmax is
    decided by more than the bf16 noise, and the dispatch log must show
    the attention ops on Pallas kernels only.
  * kernels — every Pallas kernel of the main paths against its
    ``kernels/ref.py`` oracle, at the interpret-mode tests' tolerances.
  * train   — three steps of ``llm_a3c.make_train_step`` built as
    ``train.run_llm`` builds it, depth cut to fit one chip; losses finite.
  * decode_cp (``--chips 4`` only) — serving with the KV cache's sequence
    axis sharded over the chips, against the same requests on one chip.

This is a smoke run, not a benchmark: the times it prints are single runs
that include compilation where noted.  Any failing phase raises, so the
script exits non-zero and never prints the final line.  Without a TPU it
exits non-zero before running anything.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time
from typing import Tuple

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "stablelm-1.6b"
ATTN_OPS = ("flash_append", "append_paged", "decode_paged",
            "decode_attention")
# bf16 noise bound on logits between two implementations of the same
# forward: a reference argmax whose top-2 margin is below it is a tie
LOGIT_TOL = 0.1
# one chip against the cache sharded over chips: the same bf16 forward
# with other reduction orders differs by ~0.05 in its logits (0.0546875
# at 8 layers on four CPU devices; the logits' std is ~0.9), while a
# wrong layout moves them by their spread
CP_LOGIT_TOL = 0.25
# the interpret-mode parity tests' tolerances (tests/test_kernels.py,
# tests/test_flash_append.py): bf16 outputs, bf16 gradients, f32 rmsprop
BF16_TOL, BF16_GRAD_TOL = 2e-2, 5e-2


def _log(phase: str, **rec) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    mem = {k: stats[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                 "bytes_limit") if k in stats}
    print(json.dumps({"phase": phase, **rec, "device0_memory": mem}),
          flush=True)


class SmokeFailure(RuntimeError):
    """A phase's check failed."""


def _require(ok, what) -> None:
    # an explicit raise, not ``assert``: ``python -O`` must not skip checks
    if not ok:
        raise SmokeFailure(what)


def _on_tpu() -> bool:
    """On a chip every attention op must resolve to a Pallas kernel; on
    the CPU (the phases' tests) auto dispatch serves the jnp oracles."""
    from repro.distributed import ctx
    return ctx.current_platform() == "tpu"


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ServeSettings:
    slots: int = 8
    requests: int = 16
    prompt_range: Tuple[int, int] = (256, 1024)
    gen_range: Tuple[int, int] = (32, 64)
    cache_len: int = 2048
    chunk: int = 128
    page_size: int = 128
    # page pool: 8 slots x 9 pages (the longest request, 1024 + 64 tokens)
    # + 1 sink page, 1.84 GB of bf16 KV over 24 layers.  The worst case
    # for cache_len 2048 (129 pages) does not fit one v5e next to the
    # engine's warmup cache and the step's own output cache.
    pages: int = 73
    kv_dtype: str = "bf16"
    seed: int = 0


def _serving_params(cfg, seed: int):
    """Random bf16 weights, made as ``serve.main`` makes them."""
    import jax

    from repro.models import model as M
    return jax.jit(lambda k: M.cast_params(cfg, M.init_params(cfg, k)))(
        jax.random.key(seed))


def _first_token_check(cfg, params, trace) -> dict:
    """Each request's first generated token against the argmax of the
    training forward (a different attention path: flash forward over the
    whole prompt, no cache) at the last prompt position, wherever the
    reference's top-2 margin exceeds the bf16 noise."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import model as M

    plen = max(len(r.prompt) for r in trace)
    plen = -(-plen // 128) * 128          # one compile for every request
    fwd = jax.jit(lambda p, t: M.forward(cfg, p, {"tokens": t})["logits"])
    checked, margins = 0, []
    for r in trace:
        toks = np.zeros((1, plen), np.int32)
        toks[0, :len(r.prompt)] = r.prompt
        last = np.asarray(
            fwd(params, jnp.asarray(toks))[0, len(r.prompt) - 1], np.float32)
        _require(np.all(np.isfinite(last)),
                 f"request {r.rid}: non-finite logits")
        top2 = np.sort(last)[-2:]
        margins.append(float(top2[1] - top2[0]))
        if top2[1] - top2[0] > LOGIT_TOL:
            checked += 1
            want = int(np.argmax(last))
            _require(r.tokens[0] == want,
                     f"request {r.rid}: first token {r.tokens[0]} != "
                     f"reference argmax {want} (margin "
                     f"{top2[1] - top2[0]:.4f})")
    return {"first_tokens_checked": checked,
            "first_token_margin_min": min(margins),
            "first_token_margin_median": float(np.median(margins))}


def serve_phase(cfg, s: ServeSettings) -> dict:
    from repro.kernels import dispatch
    from repro.launch import serve

    params = _serving_params(cfg, s.seed)
    trace = serve.gen_trace(s.requests, vocab=cfg.vocab_size,
                            prompt_range=s.prompt_range,
                            gen_range=s.gen_range, arrival_rate=0.0,
                            seed=s.seed)
    dispatch.clear_decision_log()
    rec = serve.run_engine(cfg, params, trace, n_slots=s.slots,
                           cache_len=s.cache_len, chunk=s.chunk,
                           sample=False, seed=s.seed,
                           page_size=s.page_size, n_pages=s.pages,
                           paged=True, kv_dtype=s.kv_dtype)
    gc.collect()                          # the engine's caches go now
    _require(rec["requests"] == len(trace), rec)
    for r in trace:
        _require(len(r.tokens) == r.max_new,
                 f"request {r.rid}: {len(r.tokens)} of {r.max_new} tokens")
        _require(all(0 <= t < cfg.vocab_size for t in r.tokens),
                 f"request {r.rid}: token outside the vocabulary")
    rows = [r for r in dispatch.decision_summary() if r["op"] in ATTN_OPS]
    if _on_tpu():
        off = [r for r in rows if not r["backend"].startswith("pallas")]
        _require(not off, f"attention ops off the Pallas kernels: {off}")
        _require({r["op"] for r in rows} == set(ATTN_OPS), rows)
    out = {k: rec[k] for k in ("requests", "generated_tokens",
                               "prefill_tokens", "warmup_s", "wall_s",
                               "tokens_per_s", "n_pages", "pool_high_water")}
    out["kernel_dispatch"] = rows
    out.update(_first_token_check(cfg, params, trace))
    return out


# ---------------------------------------------------------------------------
# kernels against their oracles
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class KernelSettings:
    batch: int = 8
    seq: int = 2048                # cache length / training sequence
    train_batch: int = 2           # flash fwd/bwd (oracle holds S x S)
    chunk: int = 128
    heads: int = 32
    kv_heads: int = 32
    head_dim: int = 64
    d_model: int = 2048
    norm_rows: int = 8192
    rmsprop_shape: Tuple[int, int] = (2048, 5632)
    seed: int = 0


def _maxerr(got, want) -> float:
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(got.astype(jnp.float32) -
                                 want.astype(jnp.float32))))


def _bound(got, want, tol: float) -> float:
    """Largest |got - want| - tol * |want| (<= tol passes): the
    ``assert_allclose(atol=tol, rtol=tol)`` criterion as one number."""
    import jax.numpy as jnp
    g, w = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(g - w) - tol * jnp.abs(w)))


def kernels_phase(k: KernelSettings) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.kernels import dispatch, kv_quant, ref

    dispatch.clear_decision_log()
    bf = jnp.bfloat16
    keys = iter(jax.random.split(jax.random.key(k.seed), 16))
    rnd = lambda shape, dt=bf: jax.random.normal(next(keys), shape, dt)
    b, s, hq, hkv, d, c = (k.batch, k.seq, k.heads, k.kv_heads, k.head_dim,
                           k.chunk)
    results = {}

    def check(name, got, want, tol):
        results[name] = {"max_abs_err": _maxerr(got, want), "tol": tol}
        _require(_bound(got, want, tol) <= tol, (name, results[name]))

    oracle = jax.default_matmul_precision("highest")

    # flash forward + backward (training path)
    tb = k.train_batch
    q, kk, v, do = (rnd((tb, s, hq, d)), rnd((tb, s, hkv, d)),
                    rnd((tb, s, hkv, d)), rnd((tb, s, hq, d)))
    o = jax.jit(lambda q, k_, v: dispatch.flash_attention(
        q, k_, v, causal=True, backend="pallas"))(q, kk, v)
    with oracle:
        o_ref = jax.jit(lambda q, k_, v: ref.flash_attention_ref(
            q, k_, v, causal=True))(q, kk, v)
    check("flash_fwd", o, o_ref, BF16_TOL)

    def loss(attn):
        return lambda q, k_, v: jnp.sum(attn(q, k_, v).astype(jnp.float32)
                                        * do.astype(jnp.float32))
    g_pl = jax.jit(jax.grad(loss(lambda q, k_, v: dispatch.flash_attention(
        q, k_, v, causal=True, backend="pallas")), argnums=(0, 1, 2)))(
        q, kk, v)
    with oracle:
        g_rf = jax.jit(jax.grad(loss(lambda q, k_, v: ref.flash_attention_ref(
            q, k_, v, causal=True)), argnums=(0, 1, 2)))(q, kk, v)
    for name, got, want in zip(("dq", "dk", "dv"), g_pl, g_rf):
        check(f"flash_bwd_{name}", got, want, BF16_GRAD_TOL)
    del q, kk, v, do, o, o_ref, g_pl, g_rf

    # serving kernels over a (B, L, Hkv, D) cache: bf16 and int8 + scales
    kc, vc = rnd((b, s, hkv, d)), rnd((b, s, hkv, d))
    k8, ks = kv_quant.quantize(kc)
    v8, vs = kv_quant.quantize(vc)
    pos0 = s - c
    qa = rnd((b, c, hq, d))
    kpos_a = jnp.broadcast_to(jnp.arange(s), (b, s))
    qd = rnd((b, hq, d))
    pos = jnp.asarray([(s - 1) * (i + 1) // b for i in range(b)], jnp.int32)
    kpos_d = jnp.where(jnp.arange(s)[None, :] <= pos[:, None],
                       jnp.arange(s)[None, :], -1)
    for quant in (False, True):
        tag = "int8" if quant else "bf16"
        kv = (k8, v8) if quant else (kc, vc)
        sc = dict(k_scale=ks, v_scale=vs) if quant else {}
        got = jax.jit(lambda q, k_, v_, kp: dispatch.flash_attention_append(
            q, k_, v_, kp, pos0=pos0, kpos_linear=True, backend="pallas",
            **sc))(qa, *kv, kpos_a)
        with oracle:
            want = (ref.flash_attention_append_quant_ref(
                qa, k8, v8, ks, vs, kpos_a, pos0=pos0) if quant else
                ref.flash_attention_append_ref(qa, kc, vc, kpos_a,
                                               pos0=pos0))
        check(f"append_{tag}", got, want, BF16_TOL)
        got = jax.jit(lambda q, k_, v_, kp, p: dispatch.decode_attention(
            q, k_, v_, kp, p, backend="pallas", **sc))(qd, *kv, kpos_d, pos)
        with oracle:
            want = (ref.decode_attention_quant_ref(qd, k8, v8, ks, vs,
                                                   kpos_d, pos) if quant
                    else ref.decode_attention_ref(qd, kc, vc, kpos_d, pos))
        check(f"decode_{tag}", got, want, BF16_TOL)
        # the flash-decoding partials the context-parallel combine reads:
        # two halves of the cache, combined as the pallas_cp arm does
        from repro.kernels.decode_attention import decode_attention_partials
        from repro.kernels.decode_attention import kv_block_rows
        half = s // 2
        bk = kv_block_rows(half, hkv, d, kv[0].dtype.itemsize)
        parts = []
        for lo in (0, half):
            sl = slice(lo, lo + half)
            psc = (dict(k_scale=ks[:, sl], v_scale=vs[:, sl]) if quant
                   else {})
            parts.append(jax.jit(lambda q, k_, v_, kp, p, psc=psc:
                                 decode_attention_partials(
                                     q, k_, v_, kp, p, block_k=bk, **psc))(
                qd, kv[0][:, sl], kv[1][:, sl], kpos_d[:, sl], pos))
        m = jnp.maximum(parts[0][1], parts[1][1])
        w = [jnp.exp(p[1] - m) for p in parts]
        acc = sum(p[0] * wi[..., None] for p, wi in zip(parts, w))
        den = sum(p[2] * wi for p, wi in zip(parts, w))
        got = (acc / den[..., None]).reshape(b, hq, d)
        check(f"decode_partials_{tag}", got, want, BF16_TOL)
        rows = [r for r in dispatch.decision_summary()
                if r["op"] in ("flash_append", "decode_attention")]
        _require(all(r["backend"].startswith("pallas") for r in rows), rows)

    # rmsnorm (bf16 activations) and the shared-RMSProp update (f32)
    x = rnd((k.norm_rows, k.d_model))
    scale = 1.0 + 0.1 * rnd((k.d_model,), jnp.float32)
    got = jax.jit(lambda x, sc: dispatch.rmsnorm(x, sc, backend="pallas"))(
        x, scale)
    check("rmsnorm", got, ref.rmsnorm_ref(x, scale), BF16_TOL)
    g = jnp.abs(rnd(k.rmsprop_shape, jnp.float32))
    grad = rnd(k.rmsprop_shape, jnp.float32)
    new_g, upd = jax.jit(lambda g, gr: dispatch.rmsprop_update(
        g, gr, lr=1e-2))(g, grad)
    ng_ref, upd_ref = ref.rmsprop_update_ref(g, grad, lr=1e-2)
    for name, got, want, atol in (("rmsprop_g", new_g, ng_ref, 1e-7),
                                  ("rmsprop_update", upd, upd_ref, 1e-9)):
        err = float(jnp.max(jnp.abs(got - want) - 1e-5 * jnp.abs(want)))
        results[name] = {"max_abs_err": _maxerr(got, want),
                         "tol": {"rtol": 1e-5, "atol": atol}}
        _require(err <= atol, (name, results[name]))
    return results


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class TrainSettings:
    layers: int = 12               # depth cut to fit one chip's HBM
    batch: int = 4
    seq: int = 1024
    steps: int = 3
    optimizer: str = "shared_rmsprop"
    lr: float = 7e-3
    seed: int = 0


def train_phase(cfg, t: TrainSettings) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import llm_a3c
    from repro.data.pipeline import TokenPipeline
    from repro.models import model as M
    from repro.optim import optimizers as opt_mod

    of_layers = cfg.n_layers
    cfg = dataclasses.replace(cfg, n_layers=t.layers)
    params = M.init_params(cfg, jax.random.key(t.seed))
    opt = opt_mod.OPTIMIZERS[t.optimizer]()
    opt_state = opt.init(params)
    pipe = TokenPipeline(vocab=cfg.vocab_size, seq_len=t.seq,
                         global_batch=t.batch)
    step_fn = jax.jit(llm_a3c.make_train_step(cfg, opt, lr0=t.lr,
                                              total_steps=t.steps),
                      donate_argnums=(0, 1))
    losses, times = [], []
    for step in range(t.steps):
        batch = pipe.batch(jax.random.key(t.seed + 2), step)
        t0 = time.perf_counter()
        params, opt_state, metrics = step_fn(params, opt_state, batch,
                                             jnp.asarray(step))
        losses.append(float(metrics["loss"]))
        times.append(time.perf_counter() - t0)
    _require(np.all(np.isfinite(losses)), f"non-finite losses {losses}")
    _require(all(bool(jnp.all(jnp.isfinite(x)))
                 for x in jax.tree.leaves(params)), "non-finite params")
    return {"layers": t.layers, "of_layers": of_layers, "batch": t.batch,
            "seq": t.seq, "losses": losses,
            "first_step_s_incl_compile": times[0],
            "step_s": times[1:]}


# ---------------------------------------------------------------------------
# decode-cp across chips
# ---------------------------------------------------------------------------

def _first_step_logits(cfg, params, prompts, *, cache_len: int,
                       chunk: int, kv_dtype):
    """Chunked prefill of ``prompts`` (B, P), then one decode step fed
    each row's first prompt token — the same input under every layout, so
    a near-tie argmax flip cannot feed the layouts different tokens.
    Returns the last prefill position's logits and the decode step's,
    each (B, V) f32."""
    import jax.numpy as jnp

    from repro.core import llm_a3c
    from repro.models import model as M

    b, p = prompts.shape
    cache = M.init_cache(cfg, b, cache_len, dtype=jnp.float32,
                         kv_dtype=kv_dtype)
    prefill = llm_a3c.make_prefill_step(cfg)
    for p0 in range(0, p, chunk):
        logits, cache = prefill(params, cache,
                                {"tokens": prompts[:, p0:p0 + chunk]},
                                pos0=p0)
    out, _ = M.decode_step(cfg, params, cache, {"tokens": prompts[:, :1]},
                           jnp.full((b,), p, jnp.int32))
    return logits[:, -1], out["logits"][:, -1].astype(jnp.float32)


def decode_cp_phase(cfg, s: ServeSettings, n_dev: int) -> dict:
    """The same requests served on one device and with the KV cache's
    sequence axis sharded over ``n_dev`` devices (``serve --decode-cp``):
    prefill and first-step logits within ``CP_LOGIT_TOL``; greedy-token
    agreement is reported."""
    import contextlib

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.distributed import ctx, sharding
    from repro.kernels import dispatch, kv_quant
    from repro.launch import serve
    from repro.launch.mesh import make_mesh

    params = _serving_params(cfg, s.seed)
    prompts = jnp.asarray(np.random.default_rng(s.seed).integers(
        0, cfg.vocab_size, (s.slots, s.prompt_range[0])), jnp.int32)
    kvd = kv_quant.resolve_kv_dtype(s.kv_dtype)
    mesh = make_mesh((1, n_dev), ("data", "model"))
    rules = sharding.decode_rules(cfg, mesh, batch_size=s.slots)

    def layout(cp: bool):
        if not cp:
            return contextlib.nullcontext()
        stack = contextlib.ExitStack()
        for cm in (jax.set_mesh(mesh), ctx.use_mesh(mesh),
                   ctx.sharding_rules(rules)):
            stack.enter_context(cm)
        return stack

    runs = {}
    for cp in (False, True):
        trace = serve.gen_trace(s.requests, vocab=cfg.vocab_size,
                                prompt_range=s.prompt_range,
                                gen_range=s.gen_range, arrival_rate=0.0,
                                seed=s.seed)
        with layout(cp):
            dispatch.clear_decision_log()
            prefill_logits, logits = _first_step_logits(
                cfg, params, prompts, cache_len=s.cache_len, chunk=s.chunk,
                kv_dtype=kvd)
            rec = serve.run_engine(cfg, params, trace, n_slots=s.slots,
                                   cache_len=s.cache_len, chunk=s.chunk,
                                   sample=False, seed=s.seed,
                                   page_size=s.page_size, n_pages=s.pages,
                                   kv_dtype=s.kv_dtype)
            rows = dispatch.decision_summary()
        _require(rec["requests"] == len(trace), rec)
        runs[cp] = {"prefill_logits": np.asarray(prefill_logits),
                    "logits": np.asarray(logits),
                    "tokens": {r.rid: list(r.tokens) for r in trace},
                    "rec": rec, "rows": rows}
    one, cp = runs[False], runs[True]
    if _on_tpu():
        dec = [r for r in cp["rows"] if r["op"] == "decode_attention"]
        _require(dec and all(r["backend"] == "pallas_cp" for r in dec), dec)
    pre_diff = float(np.max(np.abs(one["prefill_logits"] -
                                   cp["prefill_logits"])))
    diff = float(np.max(np.abs(one["logits"] - cp["logits"])))
    _require(max(pre_diff, diff) <= CP_LOGIT_TOL,
             f"logits differ by {pre_diff} (prefill), {diff} (first step)")
    n_tok = sum(len(t) for t in one["tokens"].values())
    same = sum(int(a == b) for rid, ts in one["tokens"].items()
               for a, b in zip(ts, cp["tokens"][rid]))
    first_same = sum(int(ts[0] == cp["tokens"][rid][0])
                     for rid, ts in one["tokens"].items())
    # bf16 noise flips a greedy token only at a near-tie of its top two
    # logits, and a flip changes the rest of that stream, so token
    # agreement is reported, not bounded; but the first tokens of most
    # requests must agree, which a wrong layout (agreement near 1/vocab)
    # cannot fake
    _require(2 * first_same >= len(one["tokens"]),
             f"first tokens agree on {first_same} of {len(one['tokens'])}")
    return {"devices": n_dev, "prefill_logit_max_abs_diff": pre_diff,
            "first_step_logit_max_abs_diff": diff,
            "logit_tol": CP_LOGIT_TOL,
            "prefill_greedy_agree": bool(np.all(
                one["prefill_logits"].argmax(-1) ==
                cp["prefill_logits"].argmax(-1))),
            "greedy_token_agreement": same / n_tok,
            "first_token_agreement": first_same / len(one["tokens"]),
            "tokens_per_s_one_device": one["rec"]["tokens_per_s"],
            "tokens_per_s_decode_cp": cp["rec"]["tokens_per_s"],
            "kernel_dispatch_cp": [r for r in cp["rows"]
                                   if r["op"] in ATTN_OPS]}


# ---------------------------------------------------------------------------
# main
# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve, kernels and train on one chip; 4: only "
                    "decode-cp serving over four chips vs one")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        print(f"chip_smoke: no TPU found (devices: {dev}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch import compile_cache

    _log("start", device=dev, smoke_run_not_benchmark=True,
         compile_cache=compile_cache.enable(), jax=jax.__version__)
    cfg = get_config(ARCH)
    t0 = time.perf_counter()
    if args.chips == 4:
        _log("decode_cp", **decode_cp_phase(cfg, ServeSettings(
            requests=8, prompt_range=(256, 512), gen_range=(16, 32)),
            n_dev=4),
            seconds=time.perf_counter() - t0)
    else:
        _log("serve", **serve_phase(cfg, ServeSettings()),
             seconds=time.perf_counter() - t0)
        t0 = time.perf_counter()
        _log("kernels", **kernels_phase(KernelSettings()),
             seconds=time.perf_counter() - t0)
        t0 = time.perf_counter()
        _log("train", **train_phase(cfg, TrainSettings()),
             seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
