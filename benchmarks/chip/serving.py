"""Serving cells: the window drives ``launch/serve.ServeEngine``.

The engine is driven through the four methods ``serve._drain`` uses and no
others: ``enqueue``, ``schedule_admissions``, ``admit`` and
``decode_step_all`` (plus ``start_clock``/``now``/``reset``).  This loop is
the harness's own deadline-bounded copy of ``_drain``:

* open loop: each request is enqueued when its scheduled arrival has passed
  and is timed from that arrival, however late the loop got to it;
* a request's first token is stamped when ``admit`` returns, each later one
  when ``decode_step_all`` returns; both calls have pulled the tokens to
  the host by then, so the stamps are what a client would see;
* arrivals stop at the window's close; the loop then drains what is due,
  for at most ``drain_s``.

Set-up makes the weights from the seed, builds the engine, and warms the
shapes the mix will use by admitting one request as long as the mix's
longest prompt and taking one decode step, through the same methods.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import math
import time
from typing import Dict, List, Optional

import numpy as np

from . import common, generate, stats, weights


@dataclasses.dataclass
class Call:
    kind: str                 # "admit" or "decode"
    t0: float
    t1: float
    prompt_tokens: int = 0    # admit: prompt tokens prefilled
    prompts: tuple = ()       # admit: each admitted prompt's length
    cached_tokens: int = 0    # admit: prompt tokens mapped from the index
    pages_requested: int = 0  # admit: prompt pages asked for
    pages_alloced: int = 0    # admit: of those, freshly allocated
    held: tuple = ()          # decode: tokens each active slot attends to


@dataclasses.dataclass
class Stretch:
    """The traced part of a window: its bounds on the engine clock and the
    calls and occupancy samples inside it."""
    t0: float
    t1: float = math.nan
    calls: List[Call] = dataclasses.field(default_factory=list)
    occupancy: List[float] = dataclasses.field(default_factory=list)


class Recorder:
    """Stamps and calls of one window, times on the engine clock.  Its
    ``bench.*`` spans are made while ``spans`` is set: they mark the traced
    stretch."""

    def __init__(self, eng):
        self.eng = eng
        self.spans = False
        self.stamps: Dict[int, List[float]] = {}
        self.calls: List[Call] = []
        self.late: List[float] = []
        self.end = math.nan       # when the loop stopped, window time

    def span(self, name: str):
        if not self.spans:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation("bench." + name)

    def _stamp(self, reqs, before, t):
        for r, b in zip(reqs, before):
            self.stamps.setdefault(r.rid, []).extend(
                [t] * (len(r.tokens) - b))

    def admit(self, pairs) -> None:
        eng = self.eng
        reqs = [r for r, _ in pairs]
        before = [len(r.tokens) for r in reqs]
        req0, alloc0 = eng.pages_requested, eng.pages_alloced
        t0 = eng.now()
        with self.span("admit"):
            eng.admit(pairs, t0)
        t1 = eng.now()
        self._stamp(reqs, before, t1)
        served = [(r, b) for r, b in zip(reqs, before) if len(r.tokens) > b]
        preq = eng.pages_requested - req0
        palloc = eng.pages_alloced - alloc0
        self.calls.append(Call(
            "admit", t0, t1,
            prompt_tokens=sum(len(r.prompt) + b for r, b in served),
            prompts=tuple(len(r.prompt) + b for r, b in served),
            cached_tokens=(preq - palloc) * eng.page_size,
            pages_requested=preq, pages_alloced=palloc))

    def decode(self) -> None:
        eng = self.eng
        active = [(j, r) for j, r in enumerate(eng.req_of) if r is not None]
        reqs = [r for _, r in active]
        before = [len(r.tokens) for r in reqs]
        held = tuple(int(eng.pos[j]) + 1 for j, _ in active)
        t0 = eng.now()
        with self.span("decode"):
            eng.decode_step_all()
        t1 = eng.now()
        self._stamp(reqs, before, t1)
        self.calls.append(Call("decode", t0, t1, held=held))


class Tracer:
    """The profiler runs from before the window opens until after it has
    closed, so that neither its start nor its stop stalls the loop; the
    stretch it is read over runs from ``start`` to ``stop`` (engine clock),
    always between two engine calls, and is marked by the recorder's
    spans."""

    def __init__(self, rec: Recorder, start: float, stop: float):
        self.rec = rec
        self.start, self.stop = start, stop
        self.stretch: Optional[Stretch] = None
        self.done = False

    def poll(self, now: float) -> None:
        eng = self.rec.eng
        if self.stretch is None and now >= self.start:
            self.stretch = Stretch(eng.now())
            self._n_calls, self._n_occ = len(self.rec.calls), \
                len(eng.occupancy)
            self.rec.spans = True
        elif self.stretch is not None and not self.done and now >= self.stop:
            self.end_stretch()

    def end_stretch(self) -> None:
        if self.stretch is None or self.done:
            return
        eng = self.rec.eng
        self.rec.spans = False
        self.stretch.t1 = eng.now()
        self.stretch.calls = self.rec.calls[self._n_calls:]
        self.stretch.occupancy = list(eng.occupancy[self._n_occ:])
        self.done = True

    def finish(self) -> None:
        import jax
        self.end_stretch()
        jax.profiler.stop_trace()


def warm(eng, longest_prompt: int) -> None:
    """Compile what the window will run: the prefill step at every chunk
    offset the longest prompt reaches (admission prefills always run
    ``n_slots`` rows), the admission scatter and sampler, and one decode
    step.  The request goes through the engine's own methods, so the pool
    it warms is the engine's; ``reset`` then clears every counter."""
    from repro.launch import serve
    warm_req = serve.Request(rid=0, prompt=np.zeros(longest_prompt,
                                                    np.int32),
                             max_new=2, arrival=0.0)
    eng.enqueue(warm_req)
    eng.admit(eng.schedule_admissions(0.0), 0.0)
    while any(r is not None for r in eng.req_of):
        eng.decode_step_all()
    eng.reset()


@dataclasses.dataclass
class Cell:
    cfgj: dict
    traffic: dict
    named: dict
    eng: object
    reqs: list


def setup(cfgj: dict, tj: dict, seed: int, seconds: float) -> Cell:
    from repro.launch import serve
    cfg = common.model_config(cfgj)
    s = cfgj["serving"]
    key = common.seed32(seed)
    named, params = weights.make(cfg, key, serve=True)
    eng = serve.ServeEngine(
        cfg, params, n_slots=s["slots"], cache_len=s["cache_len"],
        chunk=s["chunk"], sample=not s["greedy"], seed=key,
        page_size=s["page_size"], n_pages=s["pages"], prefix_cache=True,
        paged=True, kv_dtype=s["kv_dtype"])
    items = generate.requests(tj, cfg.vocab_size, seed, seconds,
                              s["cache_len"])
    for it in items:
        need = -(-(len(it.prompt) + it.max_new) // s["page_size"])
        if len(it.prompt) + it.max_new - 1 > s["cache_len"] or \
                need > eng.usable_pages:
            raise ValueError(f"request {it.rid} ({len(it.prompt)} + "
                             f"{it.max_new}) does not fit the cache")
    reqs = [serve.Request(rid=it.rid, prompt=it.prompt, max_new=it.max_new,
                          arrival=it.arrival) for it in items]
    warm(eng, max(len(r.prompt) for r in reqs))
    if tj["kind"] == "closed_backlog":
        # the backlog is admitted, and its first decode step taken, as
        # part of set-up: the window opens on full slots
        eng.start_clock()
        for r in reqs:
            eng.enqueue(r)
        eng.admit(eng.schedule_admissions(eng.now()), eng.now())
        eng.decode_step_all()
    return Cell(cfgj, tj, named, eng, reqs)


# the traced stretch: from this share of the window, for at most so long
TRACE_FROM, TRACE_SECONDS = 0.25, 8.0


def run_window(cell: Cell, seconds: float, trace_dir: Optional[str] = None):
    """Drive the engine for ``seconds`` (plus the drain); returns
    (Recorder, window start, Tracer or None).  Times in the recorder are
    relative to the window's start."""
    eng, tj = cell.eng, cell.traffic
    rec = Recorder(eng)
    closed = tj["kind"] == "closed_backlog"
    pending = sorted(cell.reqs, key=lambda r: r.arrival)
    if trace_dir is not None:
        import jax
        jax.profiler.start_trace(trace_dir)
    if closed:              # enqueued and admitted in set-up
        qi, origin = len(pending), eng.now()
    else:
        eng.start_clock()
        qi, origin = 0, 0.0
    tracer = None
    if trace_dir is not None:
        a = origin + TRACE_FROM * seconds
        tracer = Tracer(rec, a, a + min(TRACE_SECONDS, 0.5 * seconds))
    end = origin + seconds
    limit = end if closed else end + tj["drain_s"]
    while True:
        now = eng.now()
        if tracer is not None:
            tracer.poll(now)
        while qi < len(pending) and pending[qi].arrival <= now:
            with rec.span("enqueue"):
                eng.enqueue(pending[qi])
            rec.late.append(now - pending[qi].arrival)
            qi += 1
        with rec.span("schedule"):
            pairs = eng.schedule_admissions(now)
        if pairs:
            rec.admit(pairs)
        if any(r is not None for r in eng.req_of):
            rec.decode()
        elif qi < len(pending):
            wait = pending[qi].arrival - eng.now()
            if tracer is not None and not tracer.done:
                nxt = tracer.start if tracer.stretch is None else tracer.stop
                wait = min(wait, max(nxt - eng.now(), 0.0))
            if wait > 0:
                with rec.span("wait"):
                    time.sleep(wait)
        elif not eng.queue:
            break
        if eng.now() >= limit:
            break
    rec.end = eng.now() - origin
    if tracer is not None:
        tracer.finish()
    # times relative to the window's open
    for rid in rec.stamps:
        rec.stamps[rid] = [t - origin for t in rec.stamps[rid]]
    return rec, origin, tracer


def end_to_end(cell: Cell, rec: Recorder, seconds: float,
               origin: float) -> dict:
    """Every end-to-end number a serving cell can report, plus what the
    earlier lines of output show about them."""
    closed = cell.traffic["kind"] == "closed_backlog"
    in_win = [t for ts in rec.stamps.values() for t in ts
              if 0.0 <= t <= seconds]
    due = [r for r in cell.reqs if closed or r.arrival < seconds]
    ttft, failed = [], 0
    for r in due:
        if closed:
            continue
        ts = rec.stamps.get(r.rid)
        if len(r.tokens) < r.max_new:
            failed += 1
        # a request never served counts at the loop's end: a lower bound
        ttft.append((ts[0] if ts else rec.end) - r.arrival)
    gaps, admits = [], [c.t1 - origin for c in rec.calls if c.kind == "admit"]
    with_admit = 0
    adm = np.asarray(sorted(admits))
    for ts in rec.stamps.values():
        for a, b in zip(ts, ts[1:]):
            if 0.0 <= b <= seconds:
                gaps.append(b - a)
                i = np.searchsorted(adm, a, side="right")
                with_admit += int(i < len(adm) and adm[i] <= b)
    out = {
        "output_tokens_per_s": len(in_win) / seconds,
        "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95) if ttft else None,
        "itl_p95_ms": 1e3 * stats.percentile(gaps, 95) if gaps else None,
    }
    info = {
        "requests_due": len(due), "requests_failed": failed,
        "tokens_in_window": len(in_win), "itl_gaps": len(gaps),
        "ttft_p50_ms": 1e3 * stats.percentile(ttft, 50) if ttft else None,
        "itl_p50_ms": 1e3 * stats.percentile(gaps, 50) if gaps else None,
        "itl_share_with_admission": with_admit / len(gaps) if gaps else None,
        "generator_late_p50_ms": 1e3 * stats.percentile(rec.late, 50)
        if rec.late else None,
        "generator_late_max_ms": 1e3 * max(rec.late) if rec.late else None,
        "decode_steps": sum(c.kind == "decode" for c in rec.calls),
        "admissions": sum(c.kind == "admit" for c in rec.calls),
    }
    attempted = len(due) if not closed else sum(
        1 for ts in rec.stamps.values() if any(0 <= t <= seconds
                                                for t in ts))
    return {"values": out, "info": info, "attempted": attempted,
            "failed": failed}


def check_sample(cell: Cell, seed: int) -> list:
    """The requests the check compares: the longest served one and others
    drawn from the seed, as many as the mix's ``check_requests``."""
    served = [r for r in cell.reqs if r.tokens]
    if cell.traffic["kind"] != "closed_backlog":
        served = [r for r in served if len(r.tokens) >= r.max_new]
    if not served:
        return []
    served.sort(key=lambda r: r.rid)
    longest = max(served, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in served if r is not longest]
    rng = np.random.default_rng([seed, 0xC4EC])
    k = min(cell.traffic["check_requests"] - 1, len(rest))
    pick = rng.choice(len(rest), size=k, replace=False) if k else []
    return [longest] + [rest[i] for i in sorted(pick)]


def free_engine(cell: Cell) -> None:
    """Drop the program's state (engine caches and pools) before the
    reference runs; the benchmark's weights stay."""
    cell.eng = None
    gc.collect()


def check(cell: Cell, sample: list, *, control: bool = False) -> dict:
    """Run the reference once over each sampled prompt with its served
    tokens.  ``served_logit_gap`` is the widest gap by which a served
    token's logit lies below the reference's best at its position, and
    ``served_logit_gap_mean`` that gap's mean over every served token (the
    widest gap is an extreme of a few near-ties and swings from seed to
    seed; the mean is steady).  With ``control`` the same are read for the
    int8 reference's own first choice at each position (it is not
    decoded)."""
    import jax
    import jax.numpy as jnp
    from .reference import model as R

    a = R.Arch.of(cell.cfgj)
    total = max(len(r.prompt) + len(r.tokens) for r in sample)
    lpad = -(-total // 512) * 512
    nmax = max(len(r.tokens) for r in sample)

    def gaps_of(logits, at, toks, valid):
        rows = logits[at]                                 # (N, V)
        best = jnp.max(rows, -1)
        got = jnp.take_along_axis(rows, toks[:, None], -1)[:, 0]
        return jnp.where(valid, best - got, 0.0)

    @jax.jit
    def served(named, seq, at, toks, valid):
        return gaps_of(R.forward(a, named, seq), at, toks, valid)

    @jax.jit
    def ctrl(named, seq, at, valid):
        ref = R.forward(a, named, seq)
        low = R.forward(a, named, seq, quant=True)
        first = jnp.argmax(low[at], -1)
        return gaps_of(ref, at, first, valid)

    worst, worst_ctrl, total, total_ctrl, n_tok = 0.0, 0.0, 0.0, 0.0, 0
    for r in sample:
        p, toks = len(r.prompt), np.asarray(r.tokens, np.int32)
        seq = np.zeros(lpad, np.int32)
        seq[:p] = r.prompt
        seq[p:p + len(toks) - 1] = toks[:-1]
        at = np.clip(p - 1 + np.arange(nmax), 0, lpad - 1).astype(np.int32)
        valid = np.arange(nmax) < len(toks)
        tk = np.zeros(nmax, np.int32)
        tk[:len(toks)] = toks
        g = served(cell.named, jnp.asarray(seq), jnp.asarray(at),
                   jnp.asarray(tk), jnp.asarray(valid))
        worst = max(worst, float(jnp.max(g)))
        total += float(jnp.sum(g))
        n_tok += len(toks)
        if control:
            gc_ = ctrl(cell.named, jnp.asarray(seq), jnp.asarray(at),
                       jnp.asarray(valid))
            worst_ctrl = max(worst_ctrl, float(jnp.max(gc_)))
            total_ctrl += float(jnp.sum(gc_))
    out = {"served_logit_gap": worst,
           "served_logit_gap_mean": total / n_tok,
           "tokens_compared": n_tok, "requests_compared": len(sample)}
    if control:
        out["control_logit_gap"] = worst_ctrl
        out["control_logit_gap_mean"] = total_ctrl / n_tok
    return out
