"""Paged KV cache: page-gathered dispatch arms, page allocator / prefix
index bookkeeping, and the engine's shared-prefix reuse.

The load-bearing property is BIT-FOR-BIT equality with the contiguous
layout: the paged arms gather pool pages into a dense view statically
sliced to the logical cache length, so the delegated contiguous kernels
see byte-identical inputs and produce byte-identical outputs (same XLA
reduction trees).  Greedy generations through the engine therefore cannot
drift when the layout flips.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import dispatch, ref
from repro.launch import serve as serve_mod
from repro.launch import traffic
from repro.models import attention as attn
from repro.models import model as M
from repro.launch.mesh import make_mesh

KEY = jax.random.key(7)
PS = 128


def _paged_from_contiguous(k, v, *, ps=PS, n_extra=1, perm_seed=0):
    """Scatter a contiguous (B, S, Hkv, D) cache into a page pool under a
    permuted page assignment; returns (k_pool, v_pool, page_table)."""
    b, s, hkv, d = k.shape
    assert s % ps == 0
    m = s // ps
    rng = np.random.default_rng(perm_seed)
    pages = 1 + rng.permutation(b * m)            # page 0 = garbage sink
    pt = pages.reshape(b, m).astype(np.int32)
    n_pages = b * m + 1 + n_extra
    kp = np.zeros((n_pages, ps, hkv, d), k.dtype)
    vp = np.zeros((n_pages, ps, hkv, d), v.dtype)
    for bi in range(b):
        for mi in range(m):
            kp[pt[bi, mi]] = np.asarray(k[bi, mi * ps:(mi + 1) * ps])
            vp[pt[bi, mi]] = np.asarray(v[bi, mi * ps:(mi + 1) * ps])
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(pt)


# ---------------------------------------------------------------------------
# dispatch arms
# ---------------------------------------------------------------------------

def test_decode_paged_bitwise_matches_contiguous():
    b, s, hq, hkv, d = 2, 256, 4, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    kp, vp, pt = _paged_from_contiguous(k, v)
    pos = jnp.asarray([200, 131])
    kpos = jnp.broadcast_to(jnp.arange(s), (b, s))

    dispatch.clear_decision_log()
    want = dispatch.decode_attention(q, k, v, kpos, pos)
    got = dispatch.decode_attention_paged(q, kp, vp, pt, pos, length=s)
    assert jnp.array_equal(got, want)
    d_own = dispatch.last_decision("decode_paged")
    d_in = dispatch.last_decision("decode_attention")
    assert d_own is not None and d_in is not None
    assert d_own.backend == d_in.backend      # delegation, not a fork
    # and the pure-jnp oracle agrees numerically
    orc = ref.decode_attention_paged_ref(q, kp, vp, pt, pos, length=s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(orc),
                               atol=2e-5, rtol=2e-5)


def test_decode_paged_unmapped_tail_pages():
    """Rows behind unmapped (-1) table entries are invisible: equality
    with a contiguous call whose kpos masks the same rows."""
    b, s, hq, hkv, d = 2, 256, 4, 2, 64
    ks = jax.random.split(jax.random.fold_in(KEY, 1), 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    k = jax.random.normal(ks[1], (b, s, hkv, d))
    v = jax.random.normal(ks[2], (b, s, hkv, d))
    kp, vp, pt = _paged_from_contiguous(k, v)
    pt = pt.at[:, 1].set(-1)                      # second page unmapped
    pos = jnp.asarray([100, 64])                  # within the first page
    kpos = jnp.where(jnp.arange(s) < PS, jnp.arange(s), -1)
    want = dispatch.decode_attention(
        q, k, v, jnp.broadcast_to(kpos, (b, s)), pos)
    got = dispatch.decode_attention_paged(q, kp, vp, pt, pos, length=s)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_append_paged_bitwise_matches_contiguous():
    b, c, hq, hkv, d = 2, 128, 4, 2, 64
    pos0 = 128
    ks = jax.random.split(jax.random.fold_in(KEY, 2), 5)
    q = jax.random.normal(ks[0], (b, c, hq, d))
    k_pre = jax.random.normal(ks[1], (b, pos0, hkv, d))
    v_pre = jax.random.normal(ks[2], (b, pos0, hkv, d))
    k_c = jax.random.normal(ks[3], (b, c, hkv, d))
    v_c = jax.random.normal(ks[4], (b, c, hkv, d))
    kp, vp, pt = _paged_from_contiguous(k_pre, v_pre)

    k_stream = jnp.concatenate([k_pre, k_c], axis=1)
    v_stream = jnp.concatenate([v_pre, v_c], axis=1)
    kpos = jnp.arange(pos0 + c)
    dispatch.clear_decision_log()
    want = dispatch.flash_attention_append(q, k_stream, v_stream, kpos,
                                           pos0=pos0, kpos_linear=True)
    got = dispatch.flash_attention_append_paged(q, kp, vp, pt, k_c, v_c,
                                                pos0=pos0)
    assert jnp.array_equal(got, want)
    d_own = dispatch.last_decision("append_paged")
    assert d_own is not None
    orc = ref.flash_attention_append_paged_ref(q, kp, vp, pt, k_c, v_c,
                                               pos0=pos0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(orc),
                               atol=2e-5, rtol=2e-5)


def test_append_paged_first_chunk_ignores_pool():
    """pos0 == 0: the key stream is the chunk alone, whatever garbage the
    pool holds."""
    b, c, hq, hkv, d = 2, 128, 4, 2, 64
    ks = jax.random.split(jax.random.fold_in(KEY, 3), 3)
    q = jax.random.normal(ks[0], (b, c, hq, d))
    k_c = jax.random.normal(ks[1], (b, c, hkv, d))
    v_c = jax.random.normal(ks[2], (b, c, hkv, d))
    kp = jax.random.normal(jax.random.fold_in(KEY, 4), (3, PS, hkv, d))
    pt = jnp.full((b, 2), -1, jnp.int32)
    want = dispatch.flash_attention_append(q, k_c, v_c, jnp.arange(c),
                                           pos0=0, kpos_linear=True)
    got = dispatch.flash_attention_append_paged(q, kp, kp, pt, k_c, v_c,
                                                pos0=0)
    assert jnp.array_equal(got, want)


def test_paged_misalignment_falls_back_to_jnp():
    """Non-128-multiple page_size routes to the jnp oracle with a logged
    reason, never a kernel arm."""
    b, hq, hkv, d, ps = 2, 4, 2, 64, 64
    ks = jax.random.split(jax.random.fold_in(KEY, 5), 3)
    q = jax.random.normal(ks[0], (b, hq, d))
    kp = jax.random.normal(ks[1], (5, ps, hkv, d))
    vp = jax.random.normal(ks[2], (5, ps, hkv, d))
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    pos = jnp.asarray([100, 60])
    dispatch.clear_decision_log()
    got = dispatch.decode_attention_paged(q, kp, vp, pt, pos)
    dec = dispatch.last_decision("decode_paged")
    assert dec is not None and dec.backend == "jnp"
    assert "128" in dec.reason
    orc = ref.decode_attention_paged_ref(q, kp, vp, pt, pos)
    assert jnp.array_equal(got, orc)


# ---------------------------------------------------------------------------
# model layer
# ---------------------------------------------------------------------------

def _map_tables(cache, n_slots, max_pages):
    """Give every layer's page table the identity mapping (slot b owns
    pages [1 + b*M, 1 + (b+1)*M) of its layer's pool)."""
    pt = np.arange(1, n_slots * max_pages + 1,
                   dtype=np.int32).reshape(n_slots, max_pages)

    def fix(path, leaf):
        if getattr(path[-1], "key", None) == "pt":
            # leaves are layer-stacked: (L, n_slots, max_pages); every
            # layer indexes its own pool, so the same ids per layer work
            return jnp.broadcast_to(jnp.asarray(pt), leaf.shape)
        return leaf
    return jax.tree_util.tree_map_with_path(fix, cache)


def test_model_paged_cache_bitwise_matches_contiguous():
    """Chunked prefill + per-slot decode through init_cache(paged=...)
    produce byte-identical logits to the contiguous layout."""
    cfg = get_config("stablelm-1.6b").reduced()
    params = M.init_params(cfg, jax.random.key(0))
    b, cache_len, chunk = 2, 256, 128
    layout = attn.PagedLayout(page_size=PS, n_pages=2 * (cache_len // PS) + 1)
    tokens = jax.random.randint(jax.random.key(1), (b, cache_len), 0,
                                cfg.vocab_size)

    cont = M.init_cache(cfg, b, cache_len, dtype=jnp.float32)
    paged = _map_tables(
        M.init_cache(cfg, b, cache_len, dtype=jnp.float32, paged=layout),
        b, cache_len // PS)
    for p0 in range(0, cache_len, chunk):
        oc, cont = M.prefill_step(cfg, params, cont,
                                  {"tokens": tokens[:, p0:p0 + chunk]}, p0)
        op, paged = M.prefill_step(cfg, params, paged,
                                   {"tokens": tokens[:, p0:p0 + chunk]}, p0)
        assert jnp.array_equal(oc["logits"], op["logits"]), p0
    # cache_len == prompt here, so decode from a shorter prefill instead
    cont2 = M.init_cache(cfg, b, cache_len, dtype=jnp.float32)
    paged2 = _map_tables(
        M.init_cache(cfg, b, cache_len, dtype=jnp.float32, paged=layout),
        b, cache_len // PS)
    _, cont2 = M.prefill_step(cfg, params, cont2,
                              {"tokens": tokens[:, :chunk]}, 0)
    _, paged2 = M.prefill_step(cfg, params, paged2,
                               {"tokens": tokens[:, :chunk]}, 0)
    nxt = tokens[:, chunk:chunk + 1]
    dc, _ = M.decode_step(cfg, params, cont2, {"tokens": nxt},
                          jnp.asarray(chunk))
    dp, _ = M.decode_step(cfg, params, paged2, {"tokens": nxt},
                          jnp.asarray(chunk))
    assert jnp.array_equal(dc["logits"], dp["logits"])


def test_init_paged_cache_requires_whole_pages():
    with pytest.raises(ValueError):
        attn.init_paged_kv_cache(2, 200, 2, 64, page_size=128, n_pages=5)


# ---------------------------------------------------------------------------
# allocator + prefix index
# ---------------------------------------------------------------------------

def test_page_allocator_refcount_and_versions():
    a = serve_mod.PageAllocator(4)                # pages 1..3 usable
    p1, p2, p3 = a.alloc(), a.alloc(), a.alloc()
    assert sorted((p1, p2, p3)) == [1, 2, 3]
    assert a.used_pages == 3
    with pytest.raises(RuntimeError):
        a.alloc()                                 # exhausted (0 reserved)
    a.incref(p1)
    v0 = int(a.version[p1])
    a.decref(p1)
    assert a.ref[p1] == 1 and int(a.version[p1]) == v0
    a.decref(p1)                                  # ref -> 0: recycled
    assert int(a.version[p1]) == v0 + 1
    assert a.alloc() == p1                        # back on the free list


def test_prefix_index_chain_and_staleness():
    a = serve_mod.PageAllocator(8)
    idx = serve_mod.PrefixIndex(4)
    prompt = np.arange(10, dtype=np.int32)        # 2 full blocks + tail 2
    pages = [a.alloc(), a.alloc(), a.alloc()]
    idx.register(prompt, pages, a)
    hits = idx.lookup(prompt, a)
    assert [p for p, _ in hits] == pages
    assert sum(n for _, n in hits) == 10          # partial tail matches too
    # an extended prompt shares only the full blocks
    longer = np.concatenate([prompt[:8], np.asarray([9, 9, 9], np.int32)])
    hits = idx.lookup(longer, a)
    assert [p for p, _ in hits] == pages[:2]
    # a diverging second block stops the chain after block 0
    div = prompt.copy()
    div[5] = 99
    assert [p for p, _ in idx.lookup(div, a)] == pages[:1]
    # recycling a page invalidates (version bump), entry pruned lazily
    a.decref(pages[1])
    assert a.ref[pages[1]] == 0
    assert [p for p, _ in idx.lookup(prompt, a)] == pages[:1]


# ---------------------------------------------------------------------------
# engine: reset reuse, shared-prefix parity, COW
# ---------------------------------------------------------------------------

def _cfg():
    return get_config("stablelm-1.6b").reduced()


def _drive(eng, trace):
    """Minimal admission/decode loop (all arrivals at t=0)."""
    qi, done = 0, []
    while qi < len(trace) or any(r is not None for r in eng.req_of):
        pairs = []
        for j in range(eng.n_slots):
            if qi >= len(trace) or eng.req_of[j] is not None:
                continue
            pairs.append((trace[qi], j))
            qi += 1
        done.extend(eng.admit(pairs, 0.0))
        if any(r is not None for r in eng.req_of):
            done.extend(eng.decode_step_all())
    return {r.rid: list(r.tokens) for r in trace}


def _copy_trace(trace):
    return [serve_mod.Request(rid=r.rid, prompt=np.asarray(r.prompt).copy(),
                              max_new=r.max_new, arrival=r.arrival)
            for r in trace]


def _shared_trace(vocab, *, n=8, shared_len=192, seed=0):
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, shared_len).astype(np.int32)
    dup_tail = rng.integers(0, vocab, 9).astype(np.int32)
    out = []
    for rid in range(n):
        tail = dup_tail if rid in (1, 2) else \
            rng.integers(0, vocab, 1 + (rid % 3) * 7).astype(np.int32)
        out.append(serve_mod.Request(
            rid=rid, prompt=np.concatenate([shared, tail]),
            max_new=2 + (rid % 3) * 5, arrival=0.0))
    return out


def test_engine_reset_reproduces_fresh_engine():
    """reset() + the same trace again == a fresh engine, bit for bit —
    recycled pool pages and a cleared prefix index leak nothing."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    trace = _shared_trace(cfg.vocab_size, n=5)
    kw = dict(n_slots=2, cache_len=256, chunk=64, sample=False, seed=0)
    eng = serve_mod.ServeEngine(cfg, params, **kw)
    assert eng.paged
    first = _drive(eng, _copy_trace(trace))
    eng.reset()
    second = _drive(eng, _copy_trace(trace))
    fresh = _drive(serve_mod.ServeEngine(cfg, params, **kw),
                   _copy_trace(trace))
    assert first == second == fresh


def test_engine_shared_prefix_matches_no_sharing():
    """Shared-long-prefix trace: identical greedy tokens with the prefix
    cache on and off, with dedup > 1, skipped prefill chunks, and COW
    exercised by the duplicate prompts' divergent decode writes."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    t_on = _shared_trace(cfg.vocab_size)
    t_off = _copy_trace(t_on)
    kw = dict(n_slots=4, cache_len=256, chunk=64, sample=False, seed=0)
    rec_on = serve_mod.run_engine(cfg, params, t_on, **kw)
    rec_off = serve_mod.run_engine(cfg, params, t_off, prefix_cache=False,
                                   **kw)
    assert rec_on["paged"] and rec_off["paged"]
    assert {r.rid: r.tokens for r in t_on} == \
           {r.rid: r.tokens for r in t_off}
    assert rec_on["dedup_ratio"] > 1.0
    assert rec_on["cow_events"] > 0
    assert rec_on["prefill_chunks_skipped"] > 0
    assert rec_off["dedup_ratio"] == 1.0
    assert rec_off["prefill_chunks_skipped"] == 0
    assert rec_on["pages_alloced"] < rec_off["pages_alloced"]


def test_engine_shared_prefix_ring_archs():
    """Mixed attn/ring arch: paged covers the global-attention layers,
    ring layers stay contiguous and chunk skipping stays off — tokens
    must still match the no-sharing engine.  A pure-ring arch has no
    paged layers at all and the engine must say so."""
    cfg = dataclasses.replace(_cfg(), block_cycle=("attn", "attn_local"),
                              sliding_window=8)
    params = M.init_params(cfg, jax.random.key(0))
    t_on = _shared_trace(cfg.vocab_size, n=5)
    t_off = _copy_trace(t_on)
    kw = dict(n_slots=2, cache_len=256, chunk=64, sample=False, seed=0)
    rec_on = serve_mod.run_engine(cfg, params, t_on, **kw)
    rec_off = serve_mod.run_engine(cfg, params, t_off, prefix_cache=False,
                                   **kw)
    assert rec_on["paged"]
    assert rec_on["prefill_chunks_skipped"] == 0     # ring needs chunks
    assert rec_on["dedup_ratio"] > 1.0               # sharing still on
    assert {r.rid: r.tokens for r in t_on} == \
           {r.rid: r.tokens for r in t_off}

    pure = dataclasses.replace(_cfg(), block_cycle=("attn_local",),
                               sliding_window=8)
    params_p = M.init_params(pure, jax.random.key(0))
    t_pure = _shared_trace(pure.vocab_size, n=3)
    rec = serve_mod.run_engine(pure, params_p, t_pure, **kw)
    assert not rec["paged"]                          # nothing to page


# ---------------------------------------------------------------------------
# analysis
# ---------------------------------------------------------------------------

def test_paged_capacity_model():
    cfg = _cfg()
    cap = traffic.paged_capacity(cfg, n_slots=8, cache_len=1024,
                                 page_size=128,
                                 resident_tokens_per_req=256,
                                 shared_tokens=128)
    assert cap["slot_ratio"] >= 4.0
    assert cap["dedup_ratio_model"] > 1.5
    # the paged budget actually fits: pages + per-slot overhead <= budget
    spend = (cap["shared_pages"] + cap["slots_paged"]
             * cap["unique_pages_per_req"]) * cap["page_bytes"] \
        + cap["slots_paged"] * cap["per_slot_overhead_bytes"]
    assert spend <= cap["budget_bytes"]
    # pool bytes match the eval_shape'd real cache
    n_pages = 9
    got = traffic.paged_cache_bytes(cfg, 1, 1024, page_size=128,
                                    n_pages=n_pages)
    pool = traffic.page_pool_bytes(cfg, n_pages, 128)
    assert got > pool and (got - pool) == cap["per_slot_overhead_bytes"]


# ---------------------------------------------------------------------------
# 2-dev host mesh
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
def test_engine_paged_two_device_mesh():
    """Paged engine under the (batch, heads) mesh: greedy tokens must
    match the single-device no-mesh run, and the paged dispatch arms must
    appear in the decision log."""
    from repro.distributed import ctx, sharding

    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    trace = _shared_trace(cfg.vocab_size, n=4)
    kw = dict(n_slots=2, cache_len=256, chunk=64, sample=False, seed=0)
    ref_trace = _copy_trace(trace)
    base = serve_mod.run_engine(cfg, params, ref_trace, **kw)
    assert base["paged"]
    want = {r.rid: r.tokens for r in ref_trace}

    mesh = make_mesh((1, 2), ("data", "model"))
    rules = sharding.decode_rules(cfg, mesh, batch_size=2)
    mesh_trace = _copy_trace(trace)
    with jax.set_mesh(mesh), ctx.use_mesh(mesh), \
            ctx.sharding_rules(rules):
        dispatch.clear_decision_log()
        rec = serve_mod.run_engine(cfg, params, mesh_trace, **kw)
        ops = {d.op for d in dispatch.decision_log()}
    assert rec["paged"]
    assert "decode_paged" in ops and "append_paged" in ops
    assert {r.rid: r.tokens for r in mesh_trace} == want
