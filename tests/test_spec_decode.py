"""Speculative decoding through the serve engine: accepted tokens must
be bit-identical to non-speculative decode on every layout (contiguous,
ring, paged, int8 KV, 2-dev mesh), sampled streams included, and the
accept/rollback bookkeeping must leave the page allocator balanced
through mid-page rejections, ring rotation-boundary rewinds, COW forks
under verify chunks, and preemption mid-speculation.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve as serve_mod
from repro.models import model as M
from repro.launch.mesh import make_mesh


def _cfg():
    return get_config("stablelm-1.6b").reduced()


def _trace(vocab, *, n=4, prompt_range=(12, 24), max_new=16, seed=3,
           shared=0, duplicate=False):
    rng = np.random.default_rng(seed)
    pre = rng.integers(0, vocab, shared).astype(np.int32)
    out = []
    base_tail = rng.integers(0, vocab, prompt_range[0]).astype(np.int32)
    for rid in range(n):
        if duplicate:
            tail = base_tail
        else:
            tail = rng.integers(0, vocab, int(rng.integers(
                prompt_range[0], prompt_range[1] + 1))).astype(np.int32)
        out.append(serve_mod.Request(
            rid=rid, prompt=np.concatenate([pre, tail]),
            max_new=max_new - (rid % 3) * 2, arrival=0.0))
    return out


def _drive(cfg, params, trace, *, spec, spec_k=4, n_slots=2,
           cache_len=64, chunk=16, sample=False, seed=0, **kw):
    """Run a trace through a fresh engine; returns (engine, tokens)."""
    eng = serve_mod.ServeEngine(
        cfg, params, n_slots=n_slots, cache_len=cache_len, chunk=chunk,
        sample=sample, seed=seed, spec=spec, spec_k=spec_k, **kw)
    serve_mod._warmup(eng, trace)
    done = []
    eng.start_clock()
    serve_mod._drain(eng, sorted(trace, key=lambda r: r.arrival), 0, done)
    assert len(done) == len(trace)
    return eng, {r.rid: list(r.tokens) for r in trace}


def _assert_books_balanced(eng):
    """Every request drained -> every page reference dropped: tables
    empty, refcounts zero, the whole pool (minus the sink) back on the
    free list.  A speculative pre-map that rollback misses shows up here
    as a leaked refcount."""
    assert eng.paged
    assert (eng.pt_host == -1).all(), eng.pt_host
    ref = np.asarray(eng.alloc.ref)
    assert (ref == 0).all(), f"leaked refcounts: {np.nonzero(ref)[0]}"
    assert sorted(eng.alloc.free) == list(range(1, eng.n_pages))


class _WrongDraft:
    """Draft source proposing deliberately wrong tokens (cycling the
    vocab away from the true continuation) — forces every verify round
    to reject the whole draft tail, the regime that exercises mid-page
    rollback hardest.  Greedy identity must survive total rejection."""

    kind = "wrong"

    def __init__(self, vocab):
        self.vocab = vocab

    def propose_one(self, history, k):
        last = int(history[-1])
        return [(last + 7 * (i + 1)) % self.vocab for i in range(k - 1)]

    def admit(self, req, j):
        pass

    def reset(self):
        pass


# ---------------------------------------------------------------------------
# token identity across layouts
# ---------------------------------------------------------------------------

def test_spec_identity_contiguous():
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    run = lambda spec: _drive(cfg, params,
                              _trace(cfg.vocab_size, n=4), spec=spec)[1]
    base = run("off")
    assert run("ngram") == base


def test_spec_identity_ring_rotation_boundary():
    """Sliding-window arch: the ring cache rotates every ``window``
    positions, so spec_k=4 chunks from generation-length 20 requests
    straddle rotation boundaries repeatedly.  Verify never writes the
    ring (commit scatters only accepted rows), so a rejected tail needs
    no un-rotation — identity is the proof."""
    cfg = dataclasses.replace(_cfg(), block_cycle=("attn_local",),
                              sliding_window=8)
    params = M.init_params(cfg, jax.random.key(0))
    run = lambda spec: _drive(
        cfg, params, _trace(cfg.vocab_size, n=3, max_new=20),
        spec=spec, chunk=8)[1]
    base = run("off")
    assert run("ngram") == base


def test_spec_identity_paged_and_books():
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    run = lambda spec: _drive(
        cfg, params, _trace(cfg.vocab_size, n=4, shared=32),
        spec=spec, cache_len=128, chunk=32, page_size=32,
        prefix_cache=True)
    _, base = run("off")
    eng, toks = run("ngram")
    assert toks == base
    assert eng.paged and eng.spec_rounds > 0
    _assert_books_balanced(eng)


def test_spec_identity_paged_int8():
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    run = lambda spec: _drive(
        cfg, params, _trace(cfg.vocab_size, n=3, shared=32),
        spec=spec, cache_len=128, chunk=32, page_size=32,
        prefix_cache=True, kv_dtype="int8")
    eng_off, base = run("off")
    eng, toks = run("ngram")
    assert eng.kv_dtype_name == "int8"
    assert toks == base
    _assert_books_balanced(eng)


def test_spec_identity_draft_model():
    """The tiny-config draft model source: acceptance is near zero (the
    draft net is independently initialised) but accepted tokens — i.e.
    the per-round bonus token — must still replay plain decode
    exactly, and the draft's own KV bookkeeping must not desync across
    partial accepts."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    run = lambda spec: _drive(
        cfg, params, _trace(cfg.vocab_size, n=3, max_new=10),
        spec=spec, spec_k=3)[1]
    base = run("off")
    assert run("draft") == base


def test_spec_sampled_streams_invariant():
    """Sampled decode: per-token keys derive from (request id, logical
    position), so a run that commits 3 tokens per verify round and a
    plain run that takes 3 steps draw the same stream — sampled outputs
    must be bit-identical, not just statistically alike."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    run = lambda spec: _drive(
        cfg, params, _trace(cfg.vocab_size, n=4), spec=spec,
        sample=True, seed=11)[1]
    base = run("off")
    assert run("ngram") == base


# ---------------------------------------------------------------------------
# rollback edge cases
# ---------------------------------------------------------------------------

def test_spec_midpage_rejection_rewinds_pages():
    """All-wrong drafts + 8-token pages: verify rounds pre-map pages the
    accept decision then wholly rejects; optimistic admission must
    decref-and-unmap them (counter proves it ran) and the drained books
    must balance — while greedy tokens stay identical to plain decode."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    mk = lambda: _trace(cfg.vocab_size, n=3, max_new=14)
    _, base = _drive(cfg, params, mk(), spec="off", cache_len=64,
                     chunk=16, page_size=8, admission="optimistic")
    eng = serve_mod.ServeEngine(
        cfg, params, n_slots=2, cache_len=64, chunk=16, sample=False,
        seed=0, spec="ngram", spec_k=6, page_size=8,
        admission="optimistic")
    eng.draft_src = _WrongDraft(cfg.vocab_size)
    trace = mk()
    serve_mod._warmup(eng, trace)
    done = []
    eng.start_clock()
    serve_mod._drain(eng, sorted(trace, key=lambda r: r.arrival), 0, done)
    assert {r.rid: list(r.tokens) for r in trace} == base
    assert eng.spec_pages_rewound >= 1, \
        "no page was ever rewound — the rollback arm went unexercised"
    # total rejection: acceptance collapses to the bonus token
    assert eng.spec_drafts_accepted < eng.spec_drafted
    _assert_books_balanced(eng)


def test_spec_reserve_admission_keeps_rejected_pages():
    """Under ``reserve`` admission a wholly-rejected page stays mapped
    (the reservation already paid for it; kpos masks its rows), so the
    rewind counter must stay zero and the books still balance."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    mk = lambda: _trace(cfg.vocab_size, n=3, max_new=14)
    _, base = _drive(cfg, params, mk(), spec="off", cache_len=64,
                     chunk=16, page_size=8, admission="reserve")
    eng = serve_mod.ServeEngine(
        cfg, params, n_slots=2, cache_len=64, chunk=16, sample=False,
        seed=0, spec="ngram", spec_k=6, page_size=8, admission="reserve")
    eng.draft_src = _WrongDraft(cfg.vocab_size)
    trace = mk()
    serve_mod._warmup(eng, trace)
    done = []
    eng.start_clock()
    serve_mod._drain(eng, sorted(trace, key=lambda r: r.arrival), 0, done)
    assert {r.rid: list(r.tokens) for r in trace} == base
    assert eng.spec_pages_rewound == 0
    _assert_books_balanced(eng)


def test_spec_cow_fork_during_verify():
    """Duplicate prompts share their partial prompt page; the first
    verify round's pre-map COW-forks it (the accept rule commits >= 1
    token, so the fork never rolls back).  Tokens must match plain
    decode, the fork must actually happen, and the books balance."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    mk = lambda: _trace(cfg.vocab_size, n=3, shared=32, duplicate=True)
    _, base = _drive(cfg, params, mk(), spec="off", cache_len=128,
                     chunk=32, page_size=32, prefix_cache=True)
    eng, toks = _drive(cfg, params, mk(), spec="ngram", cache_len=128,
                       chunk=32, page_size=32, prefix_cache=True)
    assert toks == base
    assert eng.cow_events >= 1, \
        "shared partial page never forked under speculation"
    _assert_books_balanced(eng)


def test_spec_preemption_mid_speculation():
    """Undersized pool under optimistic admission: speculative pre-maps
    hit exhaustion mid-round, the engine preempts a victim (dropping its
    speculative state with its pages), re-admits it later and must still
    reproduce plain decode exactly, with balanced books after drain."""
    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    mk = lambda: _trace(cfg.vocab_size, n=4, prompt_range=(10, 14),
                        max_new=14, shared=8)
    _, base = _drive(cfg, params, mk(), spec="off", n_slots=3,
                     cache_len=64, chunk=16, page_size=8,
                     admission="optimistic")
    tight = 11                       # 3 slots x 8 pages worst -> starved
    eng, toks = _drive(cfg, params, mk(), spec="ngram", spec_k=6,
                       n_slots=3, cache_len=64, chunk=16, page_size=8,
                       n_pages=tight, admission="optimistic")
    assert toks == base
    assert eng.preemptions >= 1, \
        "pool was never exhausted mid-speculation — tighten n_pages"
    _assert_books_balanced(eng)


# ---------------------------------------------------------------------------
# distributed leg
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs >= 2 devices "
                    "(XLA_FLAGS=--xla_force_host_platform_device_count=2)")
def test_spec_identity_2dev_mesh():
    """Speculative decode on the 2-dev host mesh (model-sharded decode
    layout): verify + commit ride the same sharded cache, tokens match
    the single-host plain run."""
    from repro.distributed import ctx, sharding

    cfg = _cfg()
    params = M.init_params(cfg, jax.random.key(0))
    mk = lambda: _trace(cfg.vocab_size, n=3, prompt_range=(4, 12),
                        max_new=6, seed=2)
    _, base = _drive(cfg, params, mk(), spec="off", cache_len=256,
                     chunk=8)
    mesh = make_mesh((1, 2), ("data", "model"))
    rules = sharding.decode_rules(cfg, mesh, batch_size=2)
    with jax.set_mesh(mesh), ctx.use_mesh(mesh), \
            ctx.sharding_rules(rules):
        _, toks = _drive(cfg, params, mk(), spec="ngram", cache_len=256,
                         chunk=8)
    assert toks == base
