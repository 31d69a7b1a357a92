"""Continuous-batching serve engine: chunked prefill + slot table.

Chunked prefill must reproduce the teacher-forced forward logits (per
chunk, including ring-buffer sliding-window caches), and the engine's
greedy generations must match per-request sequential decoding exactly —
admission order, padding garbage in the cache, and per-slot positions must
not leak between slots.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import llm_a3c
from repro.launch import serve as serve_mod
from repro.models import model as M
from repro.launch.mesh import make_mesh


def _cfg():
    return get_config("stablelm-1.6b").reduced()


def test_prefill_chunks_match_forward():
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    b, s = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    full = M.forward(cfg, params, {"tokens": tokens})["logits"]
    cache = M.init_cache(cfg, b, 24, dtype=jnp.float32)
    o1, cache = M.prefill_step(cfg, params, cache,
                               {"tokens": tokens[:, :8]}, 0)
    o2, cache = M.prefill_step(cfg, params, cache,
                               {"tokens": tokens[:, 8:]}, 8)
    got = jnp.concatenate([o1["logits"], o2["logits"]], axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=2e-3, rtol=2e-3)
    # and the decode that continues from the prefilled cache agrees with
    # the one continuing from a token-by-token cache
    loop_cache = M.init_cache(cfg, b, 24, dtype=jnp.float32)
    for t in range(s):
        _, loop_cache = M.decode_step(cfg, params, loop_cache,
                                      {"tokens": tokens[:, t:t + 1]},
                                      jnp.asarray(t))
    nxt = jnp.argmax(full[:, -1], -1)[:, None]
    d1, _ = M.decode_step(cfg, params, cache, {"tokens": nxt},
                          jnp.asarray(s))
    d2, _ = M.decode_step(cfg, params, loop_cache, {"tokens": nxt},
                          jnp.asarray(s))
    np.testing.assert_allclose(np.asarray(d1["logits"]),
                               np.asarray(d2["logits"]),
                               atol=2e-3, rtol=2e-3)


def test_prefill_ring_window_cache():
    """Sliding-window arch: chunk writes wrap the ring cache (chunk ==
    window, so chunks 2+ hit the wrap path and the masked prefix read)."""
    cfg = dataclasses.replace(_cfg(), block_cycle=("attn_local",),
                              sliding_window=8)
    params = M.init_params(cfg, jax.random.key(0))
    b, s = 1, 24
    tokens = jax.random.randint(jax.random.key(1), (b, s), 0,
                                cfg.vocab_size)
    full = M.forward(cfg, params, {"tokens": tokens})["logits"]
    cache = M.init_cache(cfg, b, s, dtype=jnp.float32)  # ring len = window
    outs = []
    for p0 in range(0, s, 8):
        o, cache = M.prefill_step(cfg, params, cache,
                                  {"tokens": tokens[:, p0:p0 + 8]}, p0)
        outs.append(o["logits"])
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(full),
                               atol=2e-3, rtol=2e-3)


def test_supports_chunked_prefill_gating():
    assert M.supports_chunked_prefill(_cfg())
    assert llm_a3c.make_prefill_step(_cfg()) is not None
    xl = get_config("xlstm-1.3b").reduced()
    assert not M.supports_chunked_prefill(xl)
    assert llm_a3c.make_prefill_step(xl) is None
    # ring (sliding-window) archs chunk-prefill too now: per-row true_len
    # masks ring writes past each row's real prompt length, so the padded
    # admission chunks that used to alias ring rows are safe
    ring = dataclasses.replace(_cfg(), block_cycle=("attn_local",),
                               sliding_window=8)
    assert M.supports_chunked_prefill(ring)
    assert llm_a3c.make_prefill_step(ring) is not None


def _reference_greedy(cfg, params, prompt, max_new, cache_len):
    """Per-request sequential decode (scalar pos, argmax)."""
    serve = llm_a3c.make_serve_step(cfg, sample=False)
    cache = M.init_cache(cfg, 1, cache_len, dtype=jnp.float32)
    key = jax.random.key(0)
    tok = None
    for i, t in enumerate(prompt):
        tok, _, cache = serve(params, cache,
                              {"tokens": jnp.asarray([[int(t)]])},
                              jnp.asarray(i), key)
    toks = [int(tok[0])]
    pos = len(prompt)
    while len(toks) < max_new:
        tok, _, cache = serve(params, cache,
                              {"tokens": jnp.asarray([[toks[-1]]])},
                              jnp.asarray(pos), key)
        toks.append(int(tok[0]))
        pos += 1
    return toks


def test_engine_matches_sequential_greedy():
    """Mixed-length requests through the slot table == per-request
    sequential greedy decode, token for token.  gen_range starts at 1 so
    a request satisfied by its prefill token (max_new == 1) is covered;
    chunk > cache_len exercises the clamped chunk grid (the full-cache
    overflow that used to clobber prompt rows)."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    trace = serve_mod.gen_trace(6, vocab=cfg.vocab_size,
                                prompt_range=(4, 20), gen_range=(1, 8),
                                arrival_rate=0.0, seed=3)
    assert min(r.max_new for r in trace) == 1   # seed chosen to cover it
    cache_len = 32
    rec = serve_mod.run_engine(cfg, params, trace, n_slots=2,
                               cache_len=cache_len, chunk=64,
                               sample=False, seed=0)
    assert rec["requests"] == 6
    assert rec["chunked_prefill"]
    assert rec["generated_tokens"] == sum(r.max_new for r in trace)
    for r in trace:
        want = _reference_greedy(cfg, params, r.prompt, r.max_new,
                                 cache_len)
        assert r.tokens == want, (r.rid, r.tokens, want)


def test_chunk_grid_clamps_to_cache_len():
    assert serve_mod._chunk_grid(48, 128, 80) == [(0, 80)]
    assert serve_mod._chunk_grid(48, 32, 80) == [(0, 32), (32, 32)]
    assert serve_mod._chunk_grid(70, 32, 80) == [(0, 32), (32, 32),
                                                 (64, 16)]
    assert serve_mod._chunk_grid(16, 8, 64) == [(0, 8), (8, 8)]
    with pytest.raises(ValueError):
        serve_mod._chunk_grid(100, 32, 80)
    # a chunk overflowing a full cache is a loud trace-time error, not a
    # silent prompt-row clobber
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    cache = M.init_cache(cfg, 1, 12, dtype=jnp.float32)
    with pytest.raises(ValueError, match="overflows"):
        M.prefill_step(cfg, params, cache,
                       {"tokens": jnp.zeros((1, 16), jnp.int32)}, 0)


def test_engine_ring_arch_chunked_prefill_matches():
    """Sliding-window arch through the engine, now on the CHUNKED prefill
    path (true_len-masked ring writes make right-padded admission chunks
    safe): mixed-length requests must match per-request sequential greedy
    decode.  chunk > window covers the ring-wrap write; prompts shorter
    than the padded grid cover the masked-write rows."""
    cfg = dataclasses.replace(_cfg(), block_cycle=("attn_local",),
                              sliding_window=8)
    params = M.init_params(cfg, jax.random.key(0))
    trace = serve_mod.gen_trace(4, vocab=cfg.vocab_size,
                                prompt_range=(3, 12), gen_range=(2, 5),
                                arrival_rate=0.0, seed=4)
    rec = serve_mod.run_engine(cfg, params, trace, n_slots=2,
                               cache_len=20, chunk=16, sample=False,
                               seed=0)
    assert rec["chunked_prefill"]
    for r in trace:
        want = _reference_greedy(cfg, params, r.prompt, r.max_new, 20)
        assert r.tokens == want, (r.rid, r.tokens, want)


@pytest.mark.parametrize("layout", ["paged", "ring"])
def test_lone_request_prefills_one_row(layout):
    """Admission prefills one row a request: a lone request, also into
    the second slot while the first decodes, and each request of a group
    of two on a row of its own.  Every request matches per-request
    sequential greedy decode."""
    cfg = _cfg()
    if layout == "ring":
        cfg = dataclasses.replace(cfg, block_cycle=("attn_local",),
                                  sliding_window=8)
    params = M.init_params(cfg, jax.random.key(0))
    cache_len, chunk = 32, 8
    eng = serve_mod.ServeEngine(cfg, params, n_slots=2, cache_len=cache_len,
                                chunk=chunk, sample=False, seed=0,
                                page_size=8)
    assert eng.paged == (layout == "paged")
    reqs = serve_mod.gen_trace(4, vocab=cfg.vocab_size,
                               prompt_range=(4, 20), gen_range=(3, 6),
                               arrival_rate=0.0, seed=7)

    def grid_tokens(rs):
        pmax = max(len(r.prompt) for r in rs)
        return sum(c for _, c in serve_mod._chunk_grid(pmax, chunk,
                                                       cache_len))

    def admit(rs):
        rows0 = eng.prefill_token_rows
        for r in rs:
            eng.enqueue(r)
        pairs = eng.schedule_admissions(0.0)
        assert [r for r, _ in pairs] == rs
        eng.admit(pairs, 0.0)
        return eng.prefill_token_rows - rows0

    assert admit(reqs[:1]) == grid_tokens(reqs[:1])
    eng.decode_step_all()
    assert eng.req_of[0] is reqs[0]
    assert admit(reqs[1:2]) == grid_tokens(reqs[1:2])
    assert eng.prefill_one_row_admissions == 2
    while any(r is not None for r in eng.req_of):
        eng.decode_step_all()
    assert admit(reqs[2:]) == grid_tokens(reqs[2:3]) + grid_tokens(reqs[3:])
    assert eng.prefill_one_row_admissions == 4
    while any(r is not None for r in eng.req_of):
        eng.decode_step_all()
    for r in reqs:
        want = _reference_greedy(cfg, params, r.prompt, r.max_new,
                                 cache_len)
        assert r.tokens == want, (r.rid, r.tokens, want)


def test_engine_fallback_loop_prefill():
    """Recurrent-cache arch: the engine falls back to token-by-token
    prefill and still matches sequential greedy decode."""
    cfg = get_config("xlstm-1.3b").reduced()
    params = M.init_params(cfg, jax.random.key(0))
    trace = serve_mod.gen_trace(3, vocab=cfg.vocab_size,
                                prompt_range=(3, 6), gen_range=(2, 4),
                                arrival_rate=0.0, seed=5)
    rec = serve_mod.run_engine(cfg, params, trace, n_slots=2,
                               cache_len=16, chunk=8, sample=False, seed=0)
    assert not rec["chunked_prefill"]
    for r in trace:
        want = _reference_greedy(cfg, params, r.prompt, r.max_new, 16)
        assert r.tokens == want, (r.rid, r.tokens, want)


def test_lockstep_ring_wave_matches_sequential():
    """Regression: a lockstep wave mixing short and long prompts on a
    sliding-window arch must match per-request sequential greedy — the old
    standalone wave prefill re-fed short rows' last tokens past their true
    length, wrapping the ring and clobbering rows kpos attributed to real
    positions."""
    cfg = dataclasses.replace(_cfg(), block_cycle=("attn_local",),
                              sliding_window=8)
    params = M.init_params(cfg, jax.random.key(0))
    # one wave of 2: plen 4 next to plen 20 (> window), the aliasing case
    trace = [serve_mod.Request(rid=0, prompt=np.arange(4, dtype=np.int32),
                               max_new=4, arrival=0.0),
             serve_mod.Request(rid=1,
                               prompt=np.arange(20, dtype=np.int32) % 7,
                               max_new=3, arrival=0.0)]
    rec = serve_mod.run_lockstep(cfg, params, trace, n_slots=2,
                                 cache_len=26, chunk=8, sample=False,
                                 seed=0)
    assert rec["requests"] == 2
    for r in trace:
        want = _reference_greedy(cfg, params, r.prompt, r.max_new, 26)
        assert r.tokens == want, (r.rid, r.tokens, want)


def test_lockstep_runner_smoke():
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    trace = serve_mod.gen_trace(4, vocab=cfg.vocab_size,
                                prompt_range=(4, 12), gen_range=(2, 4),
                                arrival_rate=0.0, seed=1)
    rec = serve_mod.run_lockstep(cfg, params, trace, n_slots=2,
                                 cache_len=20, chunk=8, sample=True,
                                 seed=0)
    assert rec["requests"] == 4
    assert rec["generated_tokens"] == sum(r.max_new for r in trace)
    # satellite: sample_tokens is the FIRST REQUEST's first generated
    # tokens, not the first decode step across the batch
    assert rec["sample_tokens"] == trace[0].tokens[:4]
    assert rec["warmup_s"] > 0


@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
def test_engine_decode_cp_smoke():
    """Serve-engine smoke on the 2-dev host mesh: mixed-length requests
    with the seq-sharded cache layout must resolve pallas_cp and match the
    unruled sequential reference."""
    from repro.distributed import ctx, sharding
    from repro.kernels import dispatch

    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    trace = serve_mod.gen_trace(4, vocab=cfg.vocab_size,
                                prompt_range=(4, 16), gen_range=(2, 5),
                                arrival_rate=0.0, seed=2)
    cache_len = 256                     # 128-aligned per-shard slices
    mesh = make_mesh((1, 2), ("data", "model"))
    rules = sharding.decode_rules(cfg, mesh, batch_size=2)
    with jax.set_mesh(mesh), ctx.use_mesh(mesh), \
            ctx.sharding_rules(rules):
        dispatch.clear_decision_log()
        rec = serve_mod.run_engine(cfg, params, trace, n_slots=2,
                                   cache_len=cache_len, chunk=8,
                                   sample=False, seed=0)
        d = dispatch.last_decision("decode_attention")
        assert d is not None and d.backend == "pallas_cp", d
    assert rec["requests"] == 4
    for r in trace:
        want = _reference_greedy(cfg, params, r.prompt, r.max_new,
                                 cache_len)
        assert r.tokens == want, (r.rid, r.tokens, want)
