"""int8 KV-cache quantization: the quant/dequant primitives, dispatch-arm
parity against the explicit-dequant oracles (contiguous, paged, mesh),
the garbage-row safety properties, the engine-level quality sweep
(teacher-forced greedy match + logit MSE across linear / ring / GQA
archs), and the capacity model's int8 column.
"""
import dataclasses
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.distributed import ctx
from repro.kernels import dispatch, kv_quant, ref
from repro.launch import serve as serve_mod
from repro.launch import traffic
from repro.models import model as M
from repro.launch.mesh import make_mesh

KEY = jax.random.key(11)
MULTI = len(jax.devices()) >= 2
PS = 128


def _rand_kv(b=2, s=256, hq=4, hkv=2, d=64):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    return q, k, v


def _paged_from_contiguous(x, *, ps=PS, perm_seed=0):
    """Scatter (B, S, H, D) rows into a page pool (page 0 = garbage
    sink) under a permuted assignment; returns (pool, page_table)."""
    b, s, h, d = x.shape
    m = s // ps
    rng = np.random.default_rng(perm_seed)
    pages = 1 + rng.permutation(b * m)
    pt = pages.reshape(b, m).astype(np.int32)
    pool = np.zeros((b * m + 2, ps, h, d), x.dtype)
    for bi in range(b):
        for mi in range(m):
            pool[pt[bi, mi]] = np.asarray(x[bi, mi * ps:(mi + 1) * ps])
    return jnp.asarray(pool), jnp.asarray(pt)


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def test_resolve_kv_dtype():
    assert kv_quant.resolve_kv_dtype("f32") == jnp.float32
    assert kv_quant.resolve_kv_dtype("bf16") == jnp.bfloat16
    assert kv_quant.resolve_kv_dtype("int8") == jnp.int8
    assert kv_quant.resolve_kv_dtype(jnp.int8) == jnp.dtype(jnp.int8)
    with pytest.raises(ValueError):
        kv_quant.resolve_kv_dtype("fp8")
    assert kv_quant.is_quantized(jnp.int8)
    assert not kv_quant.is_quantized(jnp.bfloat16)
    assert kv_quant.dtype_name(jnp.float32) == "f32"
    assert kv_quant.dtype_name(jnp.int8) == "int8"


def test_quantize_roundtrip_error_bound():
    x = jax.random.normal(KEY, (4, 32, 3, 64))
    q, s = kv_quant.quantize(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1] + (1,)
    dq = kv_quant.dequantize(q, s)
    # round-to-nearest: per-row error <= half a quantization step
    err = jnp.abs(dq - x)
    assert float(jnp.max(err - 0.5 * s)) <= 1e-6


def test_quantize_zero_row_safe():
    """All-zero rows (unwritten cache, garbage sink init) quantize with
    scale 0 and dequantize to exact zeros — no div-by-zero, no NaN."""
    x = jnp.zeros((2, 4, 2, 64))
    q, s = kv_quant.quantize(x)
    assert float(jnp.max(jnp.abs(s))) == 0.0
    dq = kv_quant.dequantize(q, s)
    assert float(jnp.max(jnp.abs(dq))) == 0.0


# ---------------------------------------------------------------------------
# dispatch arms vs the explicit-dequant oracles
# ---------------------------------------------------------------------------

def test_decode_quant_dispatch_parity():
    q, k, v = _rand_kv()
    k8, ks = kv_quant.quantize(k)
    v8, vs = kv_quant.quantize(v)
    kpos = jnp.broadcast_to(jnp.arange(k.shape[1]), k.shape[:2])
    pos = jnp.asarray([200, 131])
    want = ref.decode_attention_quant_ref(q, k8, v8, ks, vs, kpos, pos)
    for backend in ("auto", "pallas", "jnp"):
        dispatch.clear_decision_log()
        got = dispatch.decode_attention(q, k8, v8, kpos, pos,
                                        k_scale=ks, v_scale=vs,
                                        backend=backend)
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-5, backend
        d = dispatch.last_decision("decode_attention")
        assert "int8 kv" in d.reason, (backend, d)


def test_append_quant_dispatch_parity():
    b, c, pos0 = 2, 128, 128
    q, k, v = _rand_kv(b=b, s=pos0 + c)
    q = jax.random.normal(KEY, (b, c, 4, 64))
    k8, ks = kv_quant.quantize(k)
    v8, vs = kv_quant.quantize(v)
    kpos = jnp.arange(pos0 + c)
    want = ref.flash_attention_append_quant_ref(q, k8, v8, ks, vs, kpos,
                                                pos0=pos0)
    for backend in ("auto", "pallas", "jnp"):
        dispatch.clear_decision_log()
        got = dispatch.flash_attention_append(
            q, k8, v8, kpos, pos0=pos0, kpos_linear=True,
            k_scale=ks, v_scale=vs, backend=backend)
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-5, backend
        d = dispatch.last_decision("flash_append")
        assert "int8 kv" in d.reason, (backend, d)


def test_decode_paged_quant_delegates_with_scales():
    q, k, v = _rand_kv()
    k8, ks = kv_quant.quantize(k)
    v8, vs = kv_quant.quantize(v)
    kp, pt = _paged_from_contiguous(k8)
    vp, _ = _paged_from_contiguous(v8)
    kps, _ = _paged_from_contiguous(ks)
    vps, _ = _paged_from_contiguous(vs)
    pos = jnp.asarray([200, 131])
    kpos = jnp.broadcast_to(jnp.arange(k.shape[1]), k.shape[:2])
    want = ref.decode_attention_quant_ref(q, k8, v8, ks, vs, kpos, pos)
    dispatch.clear_decision_log()
    got = dispatch.decode_attention_paged(q, kp, vp, pt, pos,
                                          length=k.shape[1],
                                          k_scale=kps, v_scale=vps)
    assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
    d = dispatch.last_decision("decode_paged")
    assert "scale pool gathered together" in d.reason
    # misaligned page size falls back to the paged quant oracle
    dispatch.clear_decision_log()
    got64 = dispatch.decode_attention_paged(
        q, kp[:, :64], vp[:, :64], pt, pos,
        k_scale=kps[:, :64], v_scale=vps[:, :64])
    d = dispatch.last_decision("decode_paged")
    assert d.backend == "jnp" and "int8 kv dequantized" in d.reason
    assert got64.shape == got.shape


@pytest.mark.skipif(not MULTI, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
def test_decode_quant_shard_map_and_cp():
    q, k, v = _rand_kv()
    k8, ks = kv_quant.quantize(k)
    v8, vs = kv_quant.quantize(v)
    kpos = jnp.broadcast_to(jnp.arange(k.shape[1]), k.shape[:2])
    pos = jnp.asarray([200, 131])
    want = ref.decode_attention_quant_ref(q, k8, v8, ks, vs, kpos, pos)
    mesh = make_mesh((1, 2), ("data", "model"))
    with ctx.use_mesh(mesh):
        dispatch.clear_decision_log()
        got = dispatch.decode_attention(q, k8, v8, kpos, pos,
                                        k_scale=ks, v_scale=vs,
                                        backend="pallas_shard_map")
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
        d = dispatch.last_decision("decode_attention")
        assert d.backend == "pallas_shard_map"
        assert "dequant-in-kernel" in d.reason
    cp_rules = {"decode_cp": {"mesh": mesh, "seq_axes": ("model",),
                              "dp_axes": ("data",), "n_shards": 2}}
    with ctx.sharding_rules(cp_rules):
        dispatch.clear_decision_log()
        got = dispatch.decode_attention(q, k8, v8, kpos, pos,
                                        k_scale=ks, v_scale=vs)
        assert float(jnp.max(jnp.abs(got - want))) <= 1e-5
        d = dispatch.last_decision("decode_attention")
        assert d.backend == "pallas_cp"
        assert "dequant-in-kernel" in d.reason


def test_garbage_rows_never_poison_output():
    """Rows beyond kpos validity — the paged garbage sink, unwritten
    slots — may hold arbitrary int8 bytes and arbitrary scales (incl. the
    zero-init); attention output must not depend on them."""
    q, k, v = _rand_kv()
    k8, ks = kv_quant.quantize(k)
    v8, vs = kv_quant.quantize(v)
    pos = jnp.asarray([150, 99])
    kpos = jnp.where(jnp.arange(k.shape[1])[None] <= pos[:, None],
                     jnp.arange(k.shape[1])[None], -1)
    live = jnp.arange(k.shape[1])[None, :, None, None] <= \
        pos[:, None, None, None]
    junk8 = jnp.where(live, k8, jnp.asarray(127, jnp.int8))
    junks = jnp.where(live[..., :1, :], ks, 1e6)
    base = dispatch.decode_attention(q, k8, v8, kpos, pos,
                                     k_scale=ks, v_scale=vs)
    poisoned = dispatch.decode_attention(
        q, junk8, jnp.where(live, v8, jnp.asarray(-128, jnp.int8)),
        kpos, pos, k_scale=junks,
        v_scale=jnp.where(live[..., :1, :], vs, 0.0))
    assert float(jnp.max(jnp.abs(base - poisoned))) <= 1e-6


# ---------------------------------------------------------------------------
# model-level quality sweep: linear / ring / GQA archs
# ---------------------------------------------------------------------------

def _teacher_forced(cfg, params, toks, kv_dtype, T):
    """Feed a fixed token stream through the decode loop and return
    (per-step argmax, per-step full logits) under the given cache."""
    step = jax.jit(lambda p, c, b_, pos: M.decode_step(cfg, p, c, b_, pos))
    cache = M.init_cache(cfg, 1, T + 8, dtype=jnp.float32,
                         kv_dtype=kv_dtype)
    arg, logs = [], []
    for i in range(T):
        out, cache = step(params, cache, {"tokens": toks[:, i:i + 1]},
                          jnp.asarray(i))
        lg = np.asarray(out["logits"][:, -1], np.float32)
        arg.append(int(lg.argmax()))
        logs.append(lg)
    return np.array(arg), np.stack(logs)


@pytest.mark.parametrize("arch", ["linear", "ring", "gqa"])
def test_quality_sweep_int8_vs_f32(arch):
    """The acceptance sweep across the three attention layouts: tiny
    logit MSE (vs the logit variance) and teacher-forced greedy match
    >= 0.99 on decisive steps for an int8 cache against the f32 cache.

    Random-init params produce near-uniform logits whose top-2 margin is
    routinely smaller than ANY ~1% perturbation (bf16 rounding included),
    so raw greedy match is an unstable metric here: a step only counts
    against the 0.99 bar when the f32 decision itself is decisive — top-2
    margin above tau = 4x the measured int8 logit-perturbation RMS.  tau
    is asserted to stay tiny relative to the logit scale so the tolerance
    cannot hide real degradation, and raw match must still be >= 0.95."""
    if arch == "linear":
        cfg = get_config("stablelm-1.6b").reduced()
    elif arch == "ring":
        cfg = dataclasses.replace(
            get_config("stablelm-1.6b").reduced(),
            block_cycle=("attn", "attn_local"), sliding_window=8)
    else:
        cfg = get_config("qwen2-72b").reduced()   # Hq=4, Hkv=1
        assert cfg.n_heads > cfg.n_kv_heads
    params = M.init_params(cfg, jax.random.key(0))
    T = 64
    toks = jax.random.randint(jax.random.key(3), (1, T), 0,
                              cfg.vocab_size)
    a_f32, l_f32 = _teacher_forced(cfg, params, toks, None, T)
    a_i8, l_i8 = _teacher_forced(cfg, params, toks, jnp.int8, T)
    lf, li = l_f32.reshape(T, -1), l_i8.reshape(T, -1)
    mse = float(((lf - li) ** 2).mean())
    var = float(lf.var())
    assert mse <= 1e-3 * max(var, 1e-6), (arch, mse, var)

    tau = 4.0 * float(np.sqrt(mse))
    assert tau <= 0.1 * float(lf.std()), (arch, tau)   # tolerance is tiny
    srt = np.sort(lf, axis=-1)
    decisive = (srt[:, -1] - srt[:, -2]) >= tau
    match = (a_f32 == a_i8)
    raw = float(match.mean())
    dec = float(match[decisive].mean()) if decisive.any() else 1.0
    assert decisive.mean() > 0.5, arch      # the metric has teeth
    assert dec >= 0.99, (arch, dec, raw)
    assert raw >= 0.95, (arch, raw)


# ---------------------------------------------------------------------------
# engine + capacity model
# ---------------------------------------------------------------------------

def test_engine_int8_runs_and_reports():
    cfg = get_config("stablelm-1.6b").reduced()
    params = M.init_params(cfg, jax.random.key(0))
    trace = serve_mod.gen_trace(4, vocab=cfg.vocab_size,
                                prompt_range=(16, 48), gen_range=(4, 8),
                                arrival_rate=0.0, seed=0)
    dispatch.clear_decision_log()
    rec = serve_mod.run_engine(cfg, params, trace, n_slots=2,
                               cache_len=128, chunk=64, sample=False,
                               seed=0, prefix_cache=True, kv_dtype="int8")
    assert rec["kv_dtype"] == "int8"
    assert all(len(r.tokens) > 0 for r in trace)
    reasons = " | ".join(d.reason for d in dispatch.decision_log())
    assert "int8" in reasons


def test_engine_no_attention_arch_falls_back(caplog):
    """--kv-dtype int8 on an arch with no attention layers must log a
    fallback and serve with f32 state, not crash."""
    cfg = get_config("zamba2-1.2b").reduced()     # pure mamba2
    assert not any(k in ("attn", "attn_local") for k in cfg.layer_kinds())
    params = M.init_params(cfg, jax.random.key(0))
    with caplog.at_level(logging.WARNING):
        eng = serve_mod.ServeEngine(cfg, params, n_slots=2, cache_len=64,
                                    chunk=32, sample=False, seed=0,
                                    kv_dtype="int8")
    assert eng.kv_dtype_name == "f32"
    assert any("falling back" in r.message for r in caplog.records)


def test_paged_capacity_int8_column():
    cfg = get_config("stablelm-1.6b").reduced()
    kw = dict(n_slots=8, cache_len=1024, page_size=128,
              resident_tokens_per_req=384, shared_tokens=128)
    f32 = traffic.paged_capacity(cfg, kv_dtype="f32", **kw)
    i8 = traffic.paged_capacity(cfg, kv_dtype="int8", **kw)
    # same bf16 contiguous budget, >= 1.9x the slots on int8 pools
    assert i8["budget_bytes"] == f32["budget_bytes"]
    assert i8["slots_paged"] >= 1.9 * f32["slots_paged"]
    assert i8["kv_dtype"] == "int8" and f32["kv_dtype"] == "f32"
    # page bytes match the eval_shape'd real pools (scale pools included)
    for kv in ("f32", "int8"):
        n_pages = 9
        got = traffic.paged_cache_bytes(cfg, 1, 1024, page_size=128,
                                        n_pages=n_pages, kv_dtype=kv)
        base = traffic.paged_cache_bytes(cfg, 1, 1024, page_size=128,
                                         n_pages=1, kv_dtype=kv)
        per_page = traffic.page_pool_bytes(cfg, 1, 128, kv_dtype=kv)
        assert got - base == (n_pages - 1) * per_page


def test_cache_bytes_int8_counts_scales():
    cfg = get_config("stablelm-1.6b").reduced()
    b, s = 2, 256
    f32 = traffic.cache_bytes(cfg, b, s, kv_dtype="f32")
    i8 = traffic.cache_bytes(cfg, b, s, kv_dtype="int8")
    n_attn = sum(1 for k in cfg.layer_kinds()
                 if k in ("attn", "attn_local"))
    d = cfg.head_dim
    # per KV row: 4D -> D + 4 bytes (int8 payload + f32 scale)
    want_delta = n_attn * 2 * b * s * cfg.n_kv_heads * (4 * d - d - 4)
    assert f32 - i8 == want_delta
    assert traffic.decode_bytes_per_token(cfg, b, s, kv_dtype="f32") - \
        traffic.decode_bytes_per_token(cfg, b, s, kv_dtype="int8") == \
        want_delta
