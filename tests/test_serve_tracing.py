"""The program's own spans, counters and scopes (``repro.launch.tracing``).

* a CPU profiler trace holds the engine's host spans, nested as named, the
  admission spans carrying the admitted request ids;
* ``t_sched``, ``prefill_token_rows`` and the ``admit_blocked_*`` counts
  on hand-built schedules;
* the compile counter counts a fresh program once and a cached one never;
* ``jax.named_scope`` changes metadata only: the serve, prefill and train
  steps compile to the same HLO with and without their scopes.
"""
import contextlib
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.core import llm_a3c
from repro.launch import serve as serve_mod
from repro.launch import tracing
from repro.models import model as M
from repro.optim import optimizers


def _cfg():
    return get_config("stablelm-1.6b").reduced()


def _engine(cfg, params, **kw):
    opts = dict(n_slots=2, cache_len=512, chunk=128, sample=False,
                page_size=128, paged=True, kv_dtype="f32")
    opts.update(kw)
    return serve_mod.ServeEngine(cfg, params, **opts)


def _req(rid, plen, max_new=3, arrival=0.0, seed=0):
    rng = np.random.default_rng(seed + rid)
    return serve_mod.Request(rid=rid, prompt=rng.integers(
        0, 64, plen).astype(np.int32), max_new=max_new, arrival=arrival)


def _drain(eng, now=0.0):
    while eng.queue or any(r is not None for r in eng.req_of):
        eng.admit(eng.schedule_admissions(now), now)
        if any(r is not None for r in eng.req_of):
            eng.decode_step_all()


@pytest.fixture(scope="module")
def model():
    cfg = _cfg()
    return cfg, M.init_params(cfg, jax.random.key(0))


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, float(e.start_ns),
                                float(e.start_ns + e.duration_ns),
                                {k: str(v) for k, v in e.stats}))
    return out


def test_profiler_trace_holds_nested_engine_spans(model, tmp_path):
    cfg, params = model
    eng = _engine(cfg, params)
    for r in (_req(5, 100), _req(9, 200)):
        eng.enqueue(r)
    jax.profiler.start_trace(str(tmp_path))
    try:
        _drain(eng)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = {s[0] for s in spans}
    children = {"serve.admit": ("serve.admit.map_pages",
                                "serve.admit.prefill",
                                "serve.admit.write_rows",
                                "serve.admit.first_tokens"),
                "serve.decode": ("serve.decode.pages",
                                 "serve.decode.dispatch",
                                 "serve.decode.wait",
                                 "serve.decode.retire")}
    assert names == {"serve.schedule", *children,
                     *(c for cs in children.values() for c in cs)}
    for parent, kids in children.items():
        outer = [s for s in spans if s[0] == parent]
        for s in spans:
            if s[0] in kids:
                assert any(o[1] <= s[1] and s[2] <= o[2] for o in outer), s
    # one admission of both requests: each admission span names them
    for s in spans:
        if s[0].startswith("serve.admit"):
            assert s[3]["rids"] == "5 9", s
    assert sum(s[0] == "serve.decode" for s in spans) == eng.step_count


def test_t_sched_and_blocked_counts(model):
    """Two slots and a pool of four pages: the third due request waits on
    a slot, then on its page reservation; each pick stamps the engine
    clock."""
    cfg, params = model
    t = [0.0]
    eng = _engine(cfg, params, n_pages=5, clock=lambda: t[0])
    eng.start_clock()
    # page demand (prompt + max_new) / 128: 1, 2 and 3 pages
    reqs = [_req(0, 100, max_new=20), _req(1, 100, max_new=60),
            _req(2, 300, max_new=20)]
    for r in reqs:
        eng.enqueue(r)
    t[0] = 1.5
    picked = eng.schedule_admissions(eng.now())
    assert [r.rid for r, _ in picked] == [0, 1]
    assert (eng.admit_blocked_slots, eng.admit_blocked_pages) == (1, 0)
    assert [r.t_sched for r in reqs] == [1.5, 1.5, -1.0]
    eng.admit(picked, eng.now())
    # request 0 finishes and frees its slot; request 1 still holds two of
    # the four pages, so request 2's three do not fit
    while eng.req_of[0] is not None:
        eng.decode_step_all()
    assert eng.req_of[1] is reqs[1]
    t[0] = 2.0
    assert eng.schedule_admissions(eng.now()) == []
    assert (eng.admit_blocked_slots, eng.admit_blocked_pages) == (1, 1)
    assert reqs[2].t_sched == -1.0
    while eng.req_of[1] is not None:
        eng.decode_step_all()
    t[0] = 3.25
    picked = eng.schedule_admissions(eng.now())
    assert [r.rid for r, _ in picked] == [2]
    assert reqs[2].t_sched == 3.25
    assert (eng.admit_blocked_slots, eng.admit_blocked_pages) == (1, 1)
    eng.reset()
    assert (eng.admit_blocked_slots, eng.admit_blocked_pages,
            eng.prefill_token_rows) == (0, 0, 0)


def test_t_sched_takes_the_last_pick_after_a_requeue(model):
    """A page allocation that fails while the admission maps its prompt
    requeues the request; its next pick restamps ``t_sched``."""
    cfg, params = model
    t = [0.0]
    eng = _engine(cfg, params, clock=lambda: t[0],
                  fault_plan=serve_mod.FaultPlan(fail_alloc_at=frozenset(
                      {0})))
    eng.start_clock()
    r = _req(1, 100)
    eng.enqueue(r)
    t[0] = 0.5
    assert eng.admit(eng.schedule_admissions(eng.now()), eng.now()) == []
    assert eng.admission_alloc_failures == 1 and r.t_sched == 0.5
    assert list(eng.queue) == [r]
    t[0] = 0.75
    eng.admit(eng.schedule_admissions(eng.now()), eng.now())
    assert r.t_sched == 0.75 and eng.req_of[0] is r


def test_prefill_token_rows(model):
    """Each request's chunk grid tokens on its one row, padding in,
    skipped chunks out; beside them the prompt tokens the prefill
    computed, so the padding share holds on prefix hits."""
    cfg, params = model
    eng = _engine(cfg, params)
    a, b = _req(1, 100, max_new=1), _req(2, 200, max_new=1)
    for r in (a, b):
        eng.enqueue(r)
    eng.admit(eng.schedule_admissions(0.0), 0.0)
    # one group, one row a request: 100 -> chunk (0, 128), 200 -> chunks
    # (0, 128), (128, 128)
    assert eng.prefill_tokens == 300
    assert eng.prefill_token_rows == 128 + 256
    assert eng.prefill_tokens_computed == 300
    # a 300-token prompt held in a slot, then the same prompt again: the
    # second admission maps its pages from the prefix index and skips the
    # two chunks they cover whole
    p = _req(3, 300).prompt
    for rid, max_new in ((3, 5), (4, 1)):
        eng.enqueue(serve_mod.Request(rid=rid, prompt=p, max_new=max_new,
                                      arrival=0.0))
        eng.admit(eng.schedule_admissions(0.0), 0.0)
    assert eng.prefill_chunks_skipped == 2
    assert eng.prefill_tokens == 300 + 600
    assert eng.prefill_token_rows == 128 + 256 + 384 + 128
    # the second copy computes only its last chunk's 300 - 256 tokens
    assert eng.prefill_tokens_computed == 300 + 300 + 44
    from benchmarks.chip import program_trace
    assert program_trace.prefill_pad_share(eng) == pytest.approx(
        100 * (1 - 644 / 896))
    eng.reset()
    assert eng.prefill_tokens_computed == 0


def test_one_request_warm_leaves_no_compile(model):
    """Warmed as the chip benchmark warms (one request as long as the
    longest prompt, then ``reset``), the engine compiles nothing when it
    then admits a pair and a lone request whose longest prompt ends in
    each chunk: every admission prefills one row a request."""
    cfg, params = model
    eng = _engine(cfg, params)
    eng.enqueue(_req(0, 500, max_new=2))
    _drain(eng)
    eng.reset()
    rid = 1
    with tracing.CompileCounter() as cc:
        for plen in (100, 200, 300, 500):
            for group in ((plen, plen // 2), (plen,)):
                for n in group:
                    eng.enqueue(_req(rid, n))
                    rid += 1
                _drain(eng)
    assert cc.n == 0, dict(cc.programs)
    assert eng.prefill_one_row_admissions == 12
    assert eng.prefill_token_rows == (128 + 128) + (256 + 128) + (
        384 + 256) + (512 + 256) + 1280


def test_compile_counter_counts_fresh_programs_once():
    # a constant drawn here keeps the program out of any compile cache
    c = float(np.random.default_rng().random())
    f = jax.jit(lambda x: x * c + 1.0)
    x3, x4, x5 = (jax.block_until_ready(jnp.ones(n)) for n in (3, 4, 5))
    with tracing.CompileCounter() as cc:
        f(x3).block_until_ready()
        assert cc.n == 1 and cc.seconds > 0
        f(x3).block_until_ready()
        assert cc.n == 1
        f(x4).block_until_ready()
        assert cc.n == 2
    assert sum(cc.programs.values()) == 2
    f(x5).block_until_ready()
    assert cc.n == 2            # closed: no longer listening


# -- scopes change metadata only ---------------------------------------------

def _strip(hlo: str) -> str:
    """Compiled HLO text without op metadata and the source tables."""
    hlo = re.sub(r",? metadata=\{[^}]*\}", "", hlo)
    return "\n".join(line for line in hlo.splitlines()
                     if not re.match(r"\s*(FileNames|FunctionNames|"
                                     r"FileLocations|StackFrames)\b", line)
                     and not re.match(r"\s*\d+ ", line))


def _programs(cfg, params):
    """Compiled text of the decode, prefill and train steps, each traced
    afresh."""
    eng = _engine(cfg, params)
    serve_step = jax.jit(llm_a3c.make_serve_step(cfg, sample=False))
    prefill_step = llm_a3c.make_prefill_step(cfg)
    toks = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.asarray([130, 7], jnp.int32)
    sids = jnp.asarray([1, 2], jnp.int32)
    opt = optimizers.shared_rmsprop()
    train = jax.jit(llm_a3c.make_train_step(cfg, opt))
    batch = {"tokens": jnp.zeros((2, 16), jnp.int32),
             "rewards": jnp.zeros((2, 16)), "discounts": jnp.ones((2, 16))}
    return [
        serve_step.lower(params, eng.cache, {"tokens": toks}, pos,
                         eng.base_key, sids),
        prefill_step.lower(params, eng.cache,
                           {"tokens": jnp.zeros((2, 128), jnp.int32)},
                           pos0=128, true_len=jnp.asarray([200, 150])),
        train.lower(params, opt.init(params), batch,
                    jnp.asarray(0, jnp.int32)),
    ]


def test_scopes_change_no_program(model, monkeypatch):
    cfg, params = model
    with_scopes = [lo.compile().as_text() for lo in _programs(cfg, params)]
    scoped = r'op_name="[^"]*[/(]({})[)/]'.format(
        "paged_gather|lm_head|optimizer")
    assert {m.group(1) for m in re.finditer(scoped, "\n".join(
        with_scopes))} == {"paged_gather", "lm_head", "optimizer"}
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    without = [lo.compile().as_text() for lo in _programs(cfg, params)]
    assert not re.search(scoped, "\n".join(without))
    for a, b in zip(with_scopes, without):
        assert _strip(a) == _strip(b)

