"""Continuous-batching serve engine (the actor/serving path).

A slot table of ``--slots`` concurrent sequences, fed by a queue of
requests with Poisson (or trace-driven) arrivals and heterogeneous
prompt/generation lengths:

  * **admission** — a finished sequence frees its slot; the oldest arrived
    request is admitted, its prompt runs through *chunked flash prefill*
    (``llm_a3c.make_prefill_step``: whole prompt chunks through the flash
    forward kernel, KV caches written in blocks) and its per-slot decode
    position starts at its true prompt length.  Architectures with
    recurrent caches (SSM / xLSTM / enc-dec) fall back to a token-by-token
    prefill loop through ``serve_step``.
  * **decode** — all slots step together through one jitted ``serve_step``
    with per-slot positions ``pos (B,)`` (the per-slot decode-attention
    kernel masks each row at its own depth) and per-slot sampling keys
    (``fold_in`` per step and per slot).

Reports aggregate tokens/s, per-request latency percentiles (TTFT and
end-to-end), slot-occupancy utilization, and the kernel dispatch summary.

  PYTHONPATH=src python -m repro.launch.serve --arch stablelm-1.6b \
      --slots 4 --requests 16 --prompt-range 16,64 --gen-range 8,32

``--mode lockstep`` keeps the old wave-batched driver (every slot the same
position; waves admit ``--slots`` requests at once and wait for the
slowest) — the baseline the engine is measured against in
``benchmarks/bench_serve.py``.  ``--decode-cp`` installs the
context-parallel serving layout on the local devices (seq-sharded KV cache
-> ``pallas_cp`` dispatch) under either mode.
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import itertools
import json
import logging
import time
from typing import List, Optional

import numpy as np

from repro.launch import tracing


# ---------------------------------------------------------------------------
# request trace
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new: int
    arrival: float                # seconds after engine start
    # robustness knobs (None = unbounded):
    deadline_ttft: Optional[float] = None    # max wait for the FIRST token,
    #                                          measured from the current
    #                                          (retry-adjusted) arrival
    deadline_total: Optional[float] = None   # max end-to-end, from the
    #                                          ORIGINAL arrival
    max_retries: int = 0                     # re-enqueues after an
    #                                          admission shed (client-retry
    #                                          semantics: the TTFT clock
    #                                          restarts at each retry)
    # filled by the engine:
    tokens: list = dataclasses.field(default_factory=list)
    t_sched: float = -1.0         # last pick by schedule_admissions
    t_admit: float = -1.0
    t_first: float = -1.0
    t_done: float = -1.0
    eff_arrival: float = -1.0     # current arrival (updated by retries)
    preemptions: int = 0
    retry_count: int = 0
    shed_reason: Optional[str] = None


def _eff_prompt(req: Request) -> np.ndarray:
    """The prompt a (re-)admission must prefill: a preempted request's
    generated-so-far tokens fold into the re-prefill prompt, so greedy
    decoding resumes with exactly the logits the uncontended run saw at
    that position (token-identity under preemption)."""
    if req.tokens:
        return np.concatenate([np.asarray(req.prompt, np.int32),
                               np.asarray(req.tokens, np.int32)])
    return np.asarray(req.prompt, np.int32)


def gen_trace(n_requests: int, *, vocab: int, prompt_range, gen_range,
              arrival_rate: float, seed: int) -> List[Request]:
    """Poisson arrivals (exponential interarrival at ``arrival_rate`` req/s;
    rate <= 0 = all at t=0) with uniform prompt/gen lengths — the same
    trace drives both the engine and the lockstep baseline."""
    if prompt_range[0] < 1 or gen_range[0] < 1:
        raise ValueError("prompt and generation lengths must be >= 1 "
                         f"(got ranges {prompt_range}, {gen_range})")
    rng = np.random.default_rng(seed)
    t = 0.0
    out = []
    for i in range(n_requests):
        if arrival_rate > 0:
            t += rng.exponential(1.0 / arrival_rate)
        plen = int(rng.integers(prompt_range[0], prompt_range[1] + 1))
        glen = int(rng.integers(gen_range[0], gen_range[1] + 1))
        out.append(Request(
            rid=i, prompt=rng.integers(0, vocab, plen).astype(np.int32),
            max_new=glen, arrival=t))
    return out


def min_accept_margin(cfg, params, trace: List[Request],
                      cache_len: int) -> float:
    """Smallest top-2 logit gap along completed requests' greedy streams
    (single-slot decode chain — the non-speculative reference path).

    The speculative identity contract ("accepted greedy tokens
    bit-identical to plain decode") holds up to floating point: verify
    scores a K-token chunk while decode scores one token, and the two
    lowerings' logits differ by reduction-order noise (~1e-6).  That
    noise can only flip an argmax at a near-tie, so identity tests and
    the speculative bench pin traces whose streams keep every margin
    orders of magnitude above it — this is the checker for that
    precondition (and the diagnostic that separates a near-tie flip
    from a real logic bug: a flip at a healthy margin is never noise —
    historically an async-dispatch aliasing race, since designed out by
    fusing accept + commit into the verify launch, see
    ``_spec_step_all``).
    Returns 0.0 when a stream's recorded token is not even the chain's
    argmax (the margin is inside the noise band by construction)."""
    import jax
    import jax.numpy as jnp_mod

    from repro.models import model as M

    def _step(c, t, p):
        return M.decode_step(cfg, params, c, {"tokens": t}, p)
    step = jax.jit(_step)
    worst = float("inf")
    for r in trace:
        if not r.tokens:
            continue
        seq = [int(t) for t in r.prompt] + [int(t) for t in r.tokens]
        cache = M.init_cache(cfg, 1, cache_len, dtype=jnp_mod.float32)
        p0 = len(r.prompt)
        for i, t in enumerate(seq[:-1]):
            out, cache = step(cache,
                              jnp_mod.asarray([[t]], jnp_mod.int32),
                              jnp_mod.asarray(i))
            if i >= p0 - 1:
                row = np.asarray(out["logits"][0, -1], np.float64)
                top2 = np.argpartition(row, -2)[-2:]
                top1 = top2[np.argmax(row[top2])]
                if int(top1) != seq[i + 1]:
                    return 0.0
                worst = min(worst,
                            float(row[top1] - row[top2[top2 != top1][0]]))
    return worst


def _percentiles(xs) -> dict:
    if not xs:
        return {}
    return {p: round(float(np.percentile(xs, q)), 4)
            for p, q in (("p50", 50), ("p90", 90), ("p99", 99))}


def _validate_trace(trace: List[Request], cache_len: int, *,
                    page_size: Optional[int] = None,
                    usable_pages: Optional[int] = None,
                    spec_k: int = 1) -> None:
    """A full KV cache has no wrap semantics: ``slot = pos % cache_len``
    silently clobbers row 0 onward if decode runs past the end, while kpos
    keeps attributing the old positions — so reject traces that could
    reach it (decode writes up to position prompt + max_new - 2).

    Paged engines additionally reject any request whose worst-case page
    demand exceeds the pool: such a request can never be served even
    alone, so preempt-and-requeue would thrash forever — fail clearly at
    startup instead of mid-run.  ``spec_k`` > 1 widens the worst case by
    the speculative in-flight tail: a verify round maps pages covering up
    to ``spec_k - 1`` tokens past the committed frontier (clamped to the
    cache), so the same request demands more pages mid-round than its
    final footprint — the demand ``--admission reserve`` must hold back
    for its never-preempts guarantee to survive speculation."""
    for r in trace:
        if len(r.prompt) < 1:
            raise ValueError(f"request {r.rid}: empty prompt")
        if len(r.prompt) + r.max_new - 1 > cache_len:
            raise ValueError(
                f"request {r.rid}: prompt {len(r.prompt)} + max_new "
                f"{r.max_new} overruns cache_len {cache_len}; raise "
                "--cache-len (a full cache would wrap and clobber "
                "prompt rows silently)")
        if page_size:
            need = -(-min(len(r.prompt) + r.max_new + spec_k - 1,
                          cache_len) // page_size)
            if need > usable_pages:
                raise ValueError(
                    f"request {r.rid}: worst-case page demand {need} "
                    f"(ceil((prompt {len(r.prompt)} + max_new {r.max_new}"
                    f" + spec_k {spec_k} - 1) / page_size {page_size})) "
                    f"exceeds the pool's "
                    f"{usable_pages} usable pages — it can never be "
                    "served even alone; raise --pages or shorten the "
                    "request")


# ---------------------------------------------------------------------------
# deterministic fault injection
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class FaultPlan:
    """A seeded, replayable overload scenario for the serve engine.

    Every field indexes deterministic engine counters — the global
    ``try_alloc`` call number and the decode step number — so the same
    plan against the same trace replays the same faults bit-for-bit:

      * ``fail_alloc_at``  — global ``try_alloc`` call indices that return
                             None regardless of pool state (the allocator
                             itself is untouched, so reservations survive
                             an injected failure)
      * ``preempt_at``     — decode step indices that force-preempt the
                             victim-policy choice before the step runs
                             (repeated indices preempt several slots)
      * ``latency_at``     — (step, seconds) artificial per-step latency,
                             applied to the engine's virtual clock — with
                             ``clock=lambda: 0.0`` time is FULLY virtual
                             and deadline behavior is deterministic
      * ``hold_pages``     — pages seized from the pool at engine init
                             (standing pressure; released only by reset)
    """

    fail_alloc_at: frozenset = frozenset()
    preempt_at: tuple = ()
    latency_at: tuple = ()
    hold_pages: int = 0

    def alloc_fails(self, call: int) -> bool:
        return call in self.fail_alloc_at

    def forced_preempts(self, step: int) -> int:
        return sum(1 for s in self.preempt_at if s == step)

    def step_latency(self, step: int) -> float:
        return sum(lat for s, lat in self.latency_at if s == step)

    @classmethod
    def random(cls, seed: int, *, n_steps: int = 64,
               n_alloc_calls: int = 64, alloc_fail_p: float = 0.1,
               preempt_p: float = 0.05, latency_p: float = 0.1,
               max_latency: float = 0.01,
               hold_pages: int = 0) -> "FaultPlan":
        rng = np.random.default_rng(seed)
        return cls(
            fail_alloc_at=frozenset(
                int(i) for i in range(n_alloc_calls)
                if rng.random() < alloc_fail_p),
            preempt_at=tuple(int(s) for s in range(n_steps)
                             if rng.random() < preempt_p),
            latency_at=tuple(
                (int(s), float(round(rng.uniform(0.0, max_latency), 6)))
                for s in range(n_steps) if rng.random() < latency_p),
            hold_pages=hold_pages)

    def to_json(self) -> str:
        return json.dumps({
            "fail_alloc_at": sorted(self.fail_alloc_at),
            "preempt_at": list(self.preempt_at),
            "latency_at": [list(x) for x in self.latency_at],
            "hold_pages": self.hold_pages})

    @classmethod
    def from_json(cls, s: str) -> "FaultPlan":
        d = json.loads(s)
        return cls(fail_alloc_at=frozenset(d.get("fail_alloc_at", ())),
                   preempt_at=tuple(d.get("preempt_at", ())),
                   latency_at=tuple((int(a), float(b))
                                    for a, b in d.get("latency_at", ())),
                   hold_pages=int(d.get("hold_pages", 0)))


# ---------------------------------------------------------------------------
# chunked prefill plumbing (shared by the engine, the lockstep baseline and
# both warmups — one place to get the grid and the logit gather right)
# ---------------------------------------------------------------------------

def _chunk_grid(pmax: int, chunk: int, cache_len: int) -> List[tuple]:
    """(offset, length) chunks covering the padded prompt grid.

    The padded length rounds ``pmax`` up to the chunk grid but is clamped
    to ``cache_len``: a full cache has no wrap semantics and
    ``attend_prefill`` rejects writes past its end (window layers clamp
    their own ring length and wrap), so the last chunk shrinks instead of
    overflowing."""
    if pmax > cache_len:
        raise ValueError(f"prompt length {pmax} exceeds cache_len "
                         f"{cache_len}")
    padded = min(-(-pmax // chunk) * chunk, cache_len)
    grid = []
    p0 = 0
    while p0 < padded:
        grid.append((p0, min(chunk, padded - p0)))
        p0 += grid[-1][1]
    return grid


def _pad_prompt(prompt: np.ndarray, chunk: int, cache_len: int):
    """Right-pad one prompt array onto its chunk grid.  Returns (toks
    (1, padded) int32, plens [len(prompt)], grid).  (Takes a raw token
    array, not a Request: a requeued request prefills its EFFECTIVE
    prompt — original plus generated-so-far — via ``_eff_prompt``.)"""
    grid = _chunk_grid(len(prompt), chunk, cache_len)
    toks = np.zeros((1, grid[-1][0] + grid[-1][1]), np.int32)
    toks[0, :len(prompt)] = prompt
    return toks, [len(prompt)], grid


def _chunked_prefill(prefill_step, params, cache, toks, plens, grid,
                     skip=()):
    """Run one right-padded (B, padded) token block through the chunk
    chain.  Returns (last_logits (B, V) np.float32 — each row's true
    last-prompt-position logits — and the final cache).  The gather
    accumulates on device so the chunk chain is dispatched without a
    host sync per chunk; only the final (B, V) block crosses to host.
    Rows with plen 0 (dummy padding rows) keep zeros.

    ``skip`` — chunk offsets the prefix cache already covers for EVERY
    row (and that contain no row's last prompt token, whose logits feed
    the first sample): those chunks are not launched at all — the shared
    pages already hold their KV."""
    import jax.numpy as jnp

    last = None
    plens = np.asarray(plens)
    # per-row true lengths: ring (sliding-window) caches mask writes past
    # them, which is what makes right-padded admission chunks safe there
    true_len = jnp.asarray(plens, jnp.int32)
    for p0, c in grid:
        if p0 in skip:
            continue
        logits, cache = prefill_step(
            params, cache, {"tokens": jnp.asarray(toks[:, p0:p0 + c])},
            pos0=p0, true_len=true_len)
        if last is None:
            last = jnp.zeros((toks.shape[0], logits.shape[-1]),
                             jnp.float32)
        rel = plens - 1 - p0
        hit = (rel >= 0) & (rel < c)
        if hit.any():
            idx = jnp.asarray(np.clip(rel, 0, c - 1))
            rows = jnp.take_along_axis(logits, idx[:, None, None],
                                       axis=1)[:, 0]
            last = jnp.where(jnp.asarray(hit)[:, None], rows, last)
    return np.asarray(last), cache


# ---------------------------------------------------------------------------
# page allocator + prefix index (paged KV layout, host side)
# ---------------------------------------------------------------------------

class PageAllocator:
    """Host-side free-list allocator over the shared page pool.

    Page 0 is the reserved garbage sink (writes through unmapped page-table
    rows land there; reads mask it via kpos) and is never handed out.
    Pages are refcounted — prefix sharing maps one physical page into many
    slots' tables read-only — and ``version`` bumps every time a page's
    refcount returns to zero, so prefix-index entries naming a
    freed-and-reissued page fail validation instead of aliasing.

    Exhaustion is a scheduling event, not a crash: ``try_alloc`` returns
    None when the pool can't serve the request and the engine recovers
    (admission backpressure, preempt-and-requeue).  ``reserve``/
    ``unreserve`` track admission-time worst-case demand: reserved units
    are held back from UNRESERVED allocations (``free - reserved`` is the
    optimistic headroom), so a reserved allocation can never fail — the
    invariant ``reserved <= len(free)`` is what admission control buys."""

    def __init__(self, n_pages: int):
        if n_pages < 2:
            raise ValueError(f"need >= 2 pages (sink + 1), got {n_pages}")
        self.n_pages = n_pages
        self.free = list(range(n_pages - 1, 0, -1))      # LIFO, 0 reserved
        self.ref = np.zeros(n_pages, np.int32)
        self.version = np.zeros(n_pages, np.int64)
        self.reserved = 0        # admission units not yet materialized
        self.high_water = 0      # max used_pages ever (report counter)

    def try_alloc(self, *, reserved: bool = False) -> Optional[int]:
        """Allocate a page or return None (recoverable exhaustion).

        ``reserved=True`` consumes one outstanding reservation unit —
        admission already set the page aside, so this cannot fail while
        the reservation invariant holds.  Unreserved allocation fails as
        soon as the free list is down to the reserved units (they belong
        to admitted requests' worst-case tails, not to optimists)."""
        if reserved:
            if self.reserved <= 0:
                raise RuntimeError(
                    "reserved alloc without an outstanding reservation "
                    "(engine reservation accounting is out of sync)")
            if not self.free:       # invariant breach — recoverable anyway
                return None
            self.reserved -= 1
        elif len(self.free) <= self.reserved:
            return None
        p = self.free.pop()
        self.ref[p] = 1
        if self.used_pages > self.high_water:
            self.high_water = self.used_pages
        return p

    def alloc(self) -> int:
        p = self.try_alloc()
        if p is None:
            raise RuntimeError("page pool exhausted; raise --pages")
        return p

    def reserve(self, n: int) -> bool:
        """Set aside ``n`` pages of future demand; False (and no change)
        if the unreserved pool can't cover them — admission backpressure."""
        if n < 0:
            raise ValueError(f"reserve({n})")
        if len(self.free) - self.reserved < n:
            return False
        self.reserved += n
        return True

    def unreserve(self, n: int) -> None:
        if n > self.reserved:
            raise RuntimeError(
                f"unreserve({n}) exceeds outstanding {self.reserved}")
        self.reserved -= n

    def incref(self, p: int) -> None:
        self.ref[p] += 1

    def decref(self, p: int) -> None:
        self.ref[p] -= 1
        if self.ref[p] == 0:
            self.version[p] += 1
            self.free.append(p)

    @property
    def used_pages(self) -> int:
        return (self.n_pages - 1) - len(self.free)

    @property
    def free_unreserved(self) -> int:
        return len(self.free) - self.reserved


class PrefixIndex:
    """Prompt-prefix dedup: hash chains over page-sized token blocks.

    Block i of a prompt keys on ``hash((key_{i-1}, block_tokens))`` so a
    match at block i implies the whole prefix matched; lookup walks blocks
    in order and stops at the first miss.  Values carry the page, the
    allocator version at registration, and the exact token tuple — a hit
    must pass refcount > 0, version equality AND token equality, which
    makes recycled pages and hash collisions both non-events (stale
    entries are pruned lazily).  The final partial block registers too;
    its token tuple is part of the key, so it only ever matches an
    identical-length identical-content tail (i.e. identical prompts) —
    divergent continuations fork it via copy-on-write at decode time."""

    def __init__(self, page_size: int):
        self.ps = page_size
        self.entries: dict = {}          # chain hash -> (page, ver, toks)

    def _blocks(self, prompt):
        h = 0x9E3779B9
        for i in range(0, len(prompt), self.ps):
            blk = tuple(int(t) for t in prompt[i:i + self.ps])
            h = hash((h, blk))
            yield h, blk

    def lookup(self, prompt, alloc: PageAllocator) -> List[tuple]:
        """Longest valid shared-page chain covering the prompt's leading
        blocks: [(page, n_tokens), ...]."""
        out = []
        for h, blk in self._blocks(prompt):
            e = self.entries.get(h)
            if e is None:
                break
            page, ver, toks = e
            if alloc.ref[page] <= 0 or alloc.version[page] != ver \
                    or toks != blk:
                del self.entries[h]      # page recycled since registration
                break
            out.append((page, len(blk)))
        return out

    def register(self, prompt, pages, alloc: PageAllocator) -> None:
        """Record block -> page for every prompt block (first writer
        wins; re-registering a shared page is a no-op)."""
        for (h, blk), page in zip(self._blocks(prompt), pages):
            if h not in self.entries:
                self.entries[h] = (int(page), int(alloc.version[page]), blk)

    def clear(self) -> None:
        self.entries.clear()


class AllocatorModel:
    """The engine's allocator discipline as a checkable transition system.

    ``tools/audit``'s small-scope interleaving check drives REAL
    ``PageAllocator`` instances through every op sequence up to a bounded
    depth; this class is the single authority on which ops exist and what
    each does, mirroring the engine's exact allocator interactions:

      * ``alloc``      — unreserved allocation (optimistic admission,
                         decode growth past a consumed reservation, COW):
                         guarded by ``free > reserved`` — the protection
                         that keeps admitted requests' reservations honored
      * ``reserve``    — admission sets one page of worst-case demand
                         aside (``PageAllocator.reserve``)
      * ``alloc_r``    — a reserved allocation consuming one unit
                         (``try_alloc(reserved=True)``; cannot fail while
                         the reservation invariant holds)
      * ``unreserve``  — a finishing / unwinding / preempted slot releases
                         an unmaterialized unit
      * ``incref(h)``  — a prefix-cache hit maps a held page into another
                         slot's table read-only
      * ``release(h)`` — a finished slot drops one table reference
                         (``_free_slot_pages``)
      * ``cow(h)``     — first divergent write to a still-shared page:
                         allocate a private copy, drop the shared
                         reference (``ServeEngine._cow_into``)
      * ``preempt(h)`` — preempt-and-requeue: atomically drop hold ``h``
                         AND every outstanding reservation unit (the
                         victim's tail demand), the decode-time exhaustion
                         recovery path
      * ``spec``       — speculative pre-allocation: a verify round maps
                         pages covering drafted-but-unverified positions
                         BEFORE the accept decision
                         (``ServeEngine._spec_step_all``)
      * ``rewind(h)``  — rollback of a speculative hold whose page turned
                         out wholly rejected: decref-and-unmap (the
                         optimistic-admission rollback arm)
      * ``commit(h)``  — the accept decision lands at least one token in
                         a speculative page: it becomes an ordinary
                         committed hold (released later by
                         ``_free_slot_pages``, never by rewind)

    State is ``(allocator, holds)`` where ``holds`` is the tuple of
    outstanding page-table references as ``(page, version-at-acquire,
    kind)`` triples — kind ``"c"`` for committed references, ``"s"`` for
    speculative ones still awaiting their verify verdict.  The checker
    asserts, at every reachable state: refcounts equal
    outstanding holds and never go negative, free pages are never held,
    ``0 <= reserved <= len(free)`` (reserved allocs can never fail), and
    any page recycled after an index entry was recorded carries a bumped
    version (so stale prefix-index entries always fail validation)."""

    def __init__(self, n_pages: int = 4, allocator_cls=None):
        self.n_pages = n_pages
        self.allocator_cls = allocator_cls or PageAllocator

    def initial(self):
        return self.allocator_cls(self.n_pages), ()

    def enabled_ops(self, alloc, holds):
        """Op labels legal in this state (guards mirror engine call
        sites, which only ever decref pages they hold)."""
        ops = []
        reserved = int(getattr(alloc, "reserved", 0))
        if len(alloc.free) > reserved:
            ops.append(("alloc",))
            ops.append(("spec",))
        # reserve is always attemptable — the ALLOCATOR's capacity check
        # is the contract under test (a refused reserve is backpressure,
        # i.e. a no-op state)
        ops.append(("reserve",))
        if reserved > 0:
            ops.append(("alloc_r",))
            ops.append(("unreserve",))
        for i, h in enumerate(holds):
            p, kind = h[0], h[2]
            ops.append(("incref", i))
            ops.append(("release", i))
            ops.append(("preempt", i))
            if kind == "s":
                # a speculative hold resolves exactly one way per round:
                # wholly rejected (rewind) or touched by an accepted
                # token (commit) — never released while still pending
                ops.append(("rewind", i))
                ops.append(("commit", i))
            if alloc.ref[p] > 1 and len(alloc.free) > reserved:
                ops.append(("cow", i))
        return ops

    def apply(self, alloc, holds, op):
        """Apply ``op`` to copies of (alloc, holds); returns the new pair."""
        import copy
        alloc = copy.deepcopy(alloc)
        holds = list(holds)
        kind = op[0]
        if kind == "alloc":
            p = alloc.try_alloc()
            if p is None:
                raise RuntimeError("enabled unreserved alloc failed")
            holds.append((p, int(alloc.version[p]), "c"))
        elif kind == "spec":
            p = alloc.try_alloc()               # _spec_step_all pre-alloc
            if p is None:
                raise RuntimeError("enabled speculative alloc failed")
            holds.append((p, int(alloc.version[p]), "s"))
        elif kind == "reserve":
            alloc.reserve(1)    # False = backpressure (state unchanged)
        elif kind == "alloc_r":
            p = alloc.try_alloc(reserved=True)
            if p is None:
                raise RuntimeError("reserved alloc failed — the "
                                   "reservation invariant is broken")
            holds.append((p, int(alloc.version[p]), "c"))
        elif kind == "unreserve":
            alloc.unreserve(1)
        elif kind == "incref":
            p = holds[op[1]][0]
            alloc.incref(p)
            holds.append((p, int(alloc.version[p]), "c"))
        elif kind == "release":
            p = holds.pop(op[1])[0]
            alloc.decref(p)
        elif kind == "rewind":
            p, _, hk = holds.pop(op[1])         # rollback: decref-unmap
            if hk != "s":
                raise ValueError("rewind of a non-speculative hold")
            alloc.decref(p)
        elif kind == "commit":
            p, ver, hk = holds[op[1]]           # accepted token landed
            if hk != "s":
                raise ValueError("commit of a non-speculative hold")
            holds[op[1]] = (p, ver, "c")
        elif kind == "cow":
            src = holds[op[1]][0]
            hk = holds[op[1]][2]
            dst = alloc.try_alloc()             # ServeEngine._cow_into
            if dst is None:                     # order: copy rows, then
                raise RuntimeError("enabled cow failed")  # drop the
            alloc.decref(src)                   # shared ref
            holds[op[1]] = (dst, int(alloc.version[dst]), hk)
        elif kind == "preempt":
            p = holds.pop(op[1])[0]
            alloc.decref(p)
            reserved = int(getattr(alloc, "reserved", 0))
            if reserved:
                alloc.unreserve(reserved)
        else:
            raise ValueError(f"unknown op {op!r}")
        return alloc, tuple(sorted(holds))


# ---------------------------------------------------------------------------
# speculative draft sources
# ---------------------------------------------------------------------------

class NgramDraft:
    """Self-drafting n-gram lookup over each slot's prompt + generated
    tokens (zero model cost — "prompt lookup" drafting).

    ``propose_one(history, k)`` matches the longest suffix of ``history``
    (up to ``n`` tokens) against an earlier occurrence in the same
    history and proposes the up-to-``k - 1`` tokens that followed the
    most recent match.  No match -> no drafts: the slot rides the verify
    batch with an effective k of 1, which is exactly one plain decode
    step.  Repetitive generations (the regime greedy low-entropy decode
    falls into) hit near-perfect acceptance."""

    kind = "ngram"

    def __init__(self, n: int = 3):
        self.n = n

    def propose_one(self, hist: List[int], k: int) -> List[int]:
        m = len(hist)
        for n in range(min(self.n, m - 1), 0, -1):
            pat = hist[m - n:]
            best: List[int] = []
            for s in range(m - n - 1, -1, -1):
                if hist[s:s + n] == pat:
                    cont = hist[s + n:s + n + k - 1]
                    if len(cont) == k - 1:
                        # most recent match with a FULL continuation —
                        # near the tail of a periodic stream the newest
                        # match is truncated by the history end, so keep
                        # scanning older occurrences for full length
                        return [int(t) for t in cont]
                    if cont and not best:
                        best = [int(t) for t in cont]
            if best:
                return best
        return []

    def admit(self, req: "Request", j: int) -> None:
        pass

    def observe(self, js, new_pos) -> None:
        pass

    def reset(self) -> None:
        pass


class DraftModel:
    """Tiny-config greedy draft model sharing the engine's dispatch mesh.

    Keeps its own batched contiguous cache (one row per engine slot) and
    a host ``dpos[j]`` high-water mark: the draft cache holds KV for
    positions ``[0, dpos[j])`` of slot ``j``'s accepted token stream.
    Drafting runs ``k - 1`` batched greedy ``serve_step`` calls (the
    same jitted decode the target uses, under whatever mesh context the
    engine runs in); after the engine's accept decision ``observe`` drops
    ``dpos`` to the new committed frontier, and the next round's
    catch-up loop re-feeds accepted tokens from the request's own token
    history — stale rows past ``dpos`` written for rejected drafts are
    invisible (the decode kpos mask hides positions past ``pos``) and
    get overwritten in place.

    The draft config is ``get_config(arch).reduced()`` with the TARGET's
    vocab size, so draft tokens index the same logit space the verify
    step scores."""

    kind = "draft"

    def __init__(self, target_cfg, n_slots: int, cache_len: int,
                 chunk: int, *, arch: Optional[str] = None, seed: int = 0):
        import jax
        import jax.numpy as jnp

        from repro.configs import get_config
        from repro.core import llm_a3c
        from repro.models import model as M

        dcfg = get_config(arch or "stablelm-1.6b").reduced()
        dcfg = dataclasses.replace(dcfg, vocab_size=target_cfg.vocab_size)
        if not M.supports_chunked_prefill(dcfg):
            raise ValueError(
                f"draft arch {dcfg.name}: no chunked-prefill path — the "
                "draft cache can't admit prompts in blocks")
        self.cfg, self.jax, self.jnp, self.M = dcfg, jax, jnp, M
        self.n_slots, self.cache_len, self.chunk = n_slots, cache_len, \
            chunk
        self.params = M.init_params(dcfg, jax.random.key(seed + 9173))
        self.step = jax.jit(llm_a3c.make_serve_step(dcfg, sample=False))
        self.prefill = llm_a3c.make_prefill_step(dcfg)
        self.key = jax.random.key(seed)          # greedy: never consumed
        self.cache = M.init_cache(dcfg, n_slots, cache_len,
                                  dtype=jnp.float32)
        self.dpos = np.zeros(n_slots, np.int32)
        s1 = jax.eval_shape(lambda: M.init_cache(dcfg, 1, cache_len))
        s2 = jax.eval_shape(lambda: M.init_cache(dcfg, 2, cache_len))
        self._bdim = jax.tree.map(
            lambda a, b: next((d for d in range(a.ndim)
                               if a.shape[d] != b.shape[d]), -1), s1, s2)
        bdims = self._bdim

        def write_row(big, small, j):
            def one(bd, b, s):
                if bd < 0:
                    return b
                row = jnp.take(s, 0, axis=bd).astype(b.dtype)
                return jax.lax.dynamic_update_index_in_dim(b, row, j, bd)
            return jax.tree.map(one, bdims, big, small)

        self._write_row = jax.jit(write_row, static_argnames=("j",))

    def warm_prefill(self, plen: int) -> None:
        """Compile every chunk offset a ``plen``-token admission can
        reach (called from the engine's warmup, outside timed regions)."""
        toks, plens, grid = _pad_prompt(np.zeros(plen, np.int32),
                                        self.chunk, self.cache_len)
        cache = self.M.init_cache(self.cfg, 1, self.cache_len,
                                  dtype=self.jnp.float32)
        _chunked_prefill(self.prefill, self.params, cache, toks, plens,
                         grid)

    def admit(self, req: "Request", j: int) -> None:
        """Chunk-prefill the slot's effective prompt into draft row ``j``
        (generated tokens fold in on a preempted restore, so the draft
        frontier re-syncs to the committed token stream)."""
        prompt = _eff_prompt(req)
        toks, plens, grid = _pad_prompt(prompt, self.chunk,
                                        self.cache_len)
        cache = self.M.init_cache(self.cfg, 1, self.cache_len,
                                  dtype=self.jnp.float32)
        _, cache = _chunked_prefill(self.prefill, self.params, cache,
                                    toks, plens, grid)
        self.cache = self._write_row(self.cache, cache, j)
        self.dpos[j] = len(prompt)

    def propose(self, active: np.ndarray, hist: List[Optional[List[int]]],
                pos: np.ndarray, tok: np.ndarray,
                kmax: int) -> np.ndarray:
        """Return an (n_slots, kmax - 1) int32 draft matrix.  First the
        catch-up loop replays accepted tokens the draft cache hasn't
        consumed (at most one in steady state: the full-accept bonus
        token); rows already synced idempotently re-feed their last token
        — rewriting identical KV at the same position is a no-op.  Then
        ``kmax - 1`` greedy steps draft the continuation for every row at
        once; rows speculating with a smaller per-slot k just ignore the
        tail columns."""
        jnp = self.jnp
        n = self.n_slots
        while True:
            gap = np.where(active, pos - self.dpos, 0)
            if gap.max() <= 0:
                break
            feed_pos = np.where(gap > 0, self.dpos,
                                np.maximum(self.dpos - 1, 0))
            feed_tok = np.array(
                [hist[j][feed_pos[j]] if active[j] else 0
                 for j in range(n)], np.int32)
            _, _, self.cache = self.step(
                self.params, self.cache,
                {"tokens": jnp.asarray(feed_tok[:, None])},
                jnp.asarray(feed_pos), self.key)
            self.dpos = np.where(gap > 0, self.dpos + 1, self.dpos)
        drafts = np.zeros((n, max(kmax - 1, 1)), np.int32)
        cur = np.where(active, tok, 0).astype(np.int32)
        dp = np.where(active, pos, 0).astype(np.int32)
        for i in range(kmax - 1):
            out, _, self.cache = self.step(
                self.params, self.cache,
                {"tokens": jnp.asarray(cur[:, None])},
                jnp.asarray(dp), self.key)
            cur = np.asarray(out, np.int32)
            drafts[:, i] = cur
            dp = dp + 1
        self._drafted = kmax - 1
        return drafts

    def observe(self, js, new_pos) -> None:
        """Accept verdict: slot ``j``'s committed frontier moved to
        ``new_pos``.  Drafting wrote rows up to ``dpos + drafted - 1``
        with tokens that match the accepted stream exactly as far as the
        accepted prefix reaches, so the new draft frontier is
        ``min(new_pos, dpos + drafted)`` — a full accept leaves a gap of
        one (the bonus token's KV) for next round's catch-up loop."""
        drafted = getattr(self, "_drafted", 0)
        for j, p in zip(js, new_pos):
            self.dpos[j] = min(int(p), int(self.dpos[j]) + drafted)

    def reset(self) -> None:
        self.dpos[:] = 0
        self.cache = self.M.init_cache(self.cfg, self.n_slots,
                                       self.cache_len,
                                       dtype=self.jnp.float32)


# ---------------------------------------------------------------------------
# engine
# ---------------------------------------------------------------------------

class ServeEngine:
    """Slot table + schedulers around one jitted per-slot ``serve_step``.

    The model cache is one batched pytree of ``n_slots`` rows; admission
    prefills each request of the arrived group on one row, in a chunk
    chain of its own (recurrent archs: a token loop), and lands the row in
    its freed slot via a single jitted masked write — generic over every
    cache kind, KV and recurrent alike (the batch dim per leaf is found
    once by diffing eval_shapes).
    """

    def __init__(self, cfg, params, *, n_slots: int, cache_len: int,
                 chunk: int = 128, sample: bool = True, seed: int = 0,
                 page_size: int = 128, n_pages: int = 0,
                 prefix_cache: bool = True, paged: Optional[bool] = None,
                 kv_dtype="f32", admission: str = "reserve",
                 fault_plan: Optional[FaultPlan] = None, clock=None,
                 retry_backoff: float = 0.05, spec: str = "off",
                 spec_k: int = 4, draft_arch: Optional[str] = None,
                 draft_ngram: int = 3):
        import jax
        import jax.numpy as jnp

        from repro.core import llm_a3c
        from repro.kernels import kv_quant
        from repro.models import attention as attn_mod
        from repro.models import model as M

        self.cfg, self.params = cfg, params
        self.n_slots, self.cache_len, self.chunk = n_slots, cache_len, chunk
        self.sample = sample
        if admission not in ("reserve", "optimistic"):
            raise ValueError(f"admission policy {admission!r} (want "
                             "'reserve' or 'optimistic')")
        self.admission = admission
        if spec not in ("off", "ngram", "draft"):
            raise ValueError(f"spec mode {spec!r} (want 'off', 'ngram' "
                             "or 'draft')")
        self.spec = spec
        self.fault_plan = fault_plan
        # time authority: a custom clock makes time (and thus deadlines)
        # fully virtual — FaultPlan latencies advance it deterministically
        self.clock = clock if clock is not None else time.perf_counter
        self.virtual_time = clock is not None
        self.retry_backoff = retry_backoff
        self._t0: Optional[float] = None
        self._virtual = 0.0
        self.jnp, self.jax, self.M = jnp, jax, M
        self.serve_step = jax.jit(llm_a3c.make_serve_step(cfg,
                                                          sample=sample))
        self.prefill_step = llm_a3c.make_prefill_step(cfg)

        # paged layout: global-attention layers move to a shared page pool
        # + per-slot page tables.  Auto mode needs the chunked-prefill path
        # (recurrent archs keep per-request prefill loops on contiguous
        # state) and whole-page slots; ring layers stay contiguous inside
        # a paged cache either way.
        kinds = cfg.layer_kinds()

        # KV-cache storage dtype (f32/bf16/int8).  int8 only applies to
        # attention KV rows, so an arch with no attention layers has
        # nothing to quantize — logged fallback to f32, not a crash (the
        # dispatch arms themselves all consume quantized caches)
        kvd = kv_quant.resolve_kv_dtype(kv_dtype)
        if kv_quant.is_quantized(kvd) and \
                not any(k in ("attn", "attn_local") for k in kinds):
            logging.warning(
                "--kv-dtype int8 requested but arch %s has no attention "
                "layers (kinds=%s); recurrent state does not quantize — "
                "falling back to f32 cache storage", cfg.name, kinds)
            kvd = jnp.float32
        self.kv_dtype = kvd
        self.kv_dtype_name = {"float32": "f32", "bfloat16": "bf16",
                              "int8": "int8"}[jnp.dtype(kvd).name]
        if paged is None:
            paged = (self.prefill_step is not None
                     and "attn" in kinds
                     and cache_len % page_size == 0)
        self.paged = bool(paged)
        self.page_size = page_size
        self.max_pages = cache_len // page_size if self.paged else 0
        if self.paged:
            # worst case (no sharing): every slot fills its table, +1 sink
            self.n_pages = n_pages or n_slots * self.max_pages + 1
            self.paged_layout = attn_mod.PagedLayout(page_size, self.n_pages)
            self.alloc = PageAllocator(self.n_pages)
            self.prefix_cache = bool(prefix_cache)
            self.prefix_index = PrefixIndex(page_size)
            self.pt_host = np.full((n_slots, self.max_pages), -1, np.int32)
        else:
            self.n_pages = 0
            self.paged_layout = None
            self.prefix_cache = False
        self.cache = M.init_cache(cfg, n_slots, cache_len,
                                  dtype=jnp.float32,
                                  paged=self.paged_layout,
                                  kv_dtype=self.kv_dtype)
        # sampling keys are (request id, logical position) streams off the
        # session key — NOT the engine step count — so a slot that commits
        # three verified tokens in one speculative round and a slot that
        # takes three plain decode steps draw identical streams
        self.sample_first = jax.jit(
            lambda lg, key, sids, pos: llm_a3c.sample_slot_tokens(
                lg, key, sample=sample, sids=sids, pos=pos))
        self.base_key = jax.random.key(seed)
        # speculative decode: jitted verify (one fused k-position append
        # chunk per round, no cache writes) + deferred commit (scatter of
        # the accepted prefix), a draft source, per-slot adaptive k
        if spec != "off" and self.prefill_step is None:
            raise ValueError(
                f"--spec {spec}: {cfg.name} has no chunked-append path — "
                "recurrent caches can't score a k-token chunk in one "
                "call, so speculation has nothing to verify against")
        self.spec_k = max(2, int(spec_k)) if spec != "off" else 1
        if spec == "ngram":
            self.draft_src = NgramDraft(n=draft_ngram)
        elif spec == "draft":
            self.draft_src = DraftModel(cfg, n_slots, cache_len, chunk,
                                        arch=draft_arch, seed=seed)
        else:
            self.draft_src = None
        if spec != "off":
            # fused verify + accept + commit: one launch per round
            self.verify_step = jax.jit(
                llm_a3c.make_verify_step(cfg, cache_len, sample=sample))
        self.k_of = np.full(n_slots, self.spec_k, np.int32)
        self.accept_ema = np.full(n_slots, 1.0)
        self.spec_rounds = self.spec_drafted = 0
        self.spec_drafts_accepted = self.spec_wasted_tokens = 0
        self.spec_pages_rewound = 0
        self.accepted_k: List[int] = []
        # slot state (host side; shapes are static so no retraces)
        self.pos = np.zeros(n_slots, np.int32)
        self.tok = np.zeros(n_slots, np.int32)
        self.active = np.zeros(n_slots, bool)
        self.req_of: List[Optional[Request]] = [None] * n_slots
        self.step_count = 0
        self.prefill_tokens = 0
        # token rows the admission prefill computed: each request's chunk
        # grid tokens (padding included, skipped chunks not), and the
        # prompt tokens among them
        self.prefill_token_rows = self.prefill_tokens_computed = 0
        self.decode_tokens = 0
        self.prefill_wall = 0.0
        self.occupancy: List[float] = []
        self.page_occupancy: List[float] = []
        self.pages_requested = self.pages_alloced = 0
        self.cow_events = self.prefill_chunks_skipped = 0
        # requests the chunked prefill admitted, each on one row
        self.prefill_one_row_admissions = 0
        # robustness state: arrival queue (backpressure holds requests
        # here instead of admitting them into doomed slots), per-slot
        # outstanding reservation units, terminal sheds, counters
        self.queue: collections.deque = collections.deque()
        self.shed_requests: List[Request] = []
        self.resv_of = np.zeros(n_slots, np.int32)
        self.preemptions = self.requeues = 0
        self.sheds_admission = self.sheds_decode = self.retries = 0
        self.admission_alloc_failures = 0
        self.injected_alloc_failures = self.forced_preemptions = 0
        # schedule_admissions calls that left a due request queued: its
        # page reservation failed / no slot was free
        self.admit_blocked_pages = self.admit_blocked_slots = 0
        self.queue_depths: List[int] = []
        self._alloc_calls = 0
        self._fault_held: List[int] = []
        self._apply_fault_pressure()
        # batch-dim index per cache leaf (-1 for per-layer scalars like
        # "index", which have no batch dim): found once by diffing two
        # eval_shape batch sizes, so the admission scatter needs no shape
        # guessing at runtime.  Paged leaves get path-based codes on top:
        # -2 = shared page pool (kp/vp — no batch dim; admission takes the
        # group's pools wholesale since prefill updated them in place),
        # -3 = page table (pt — batch dim known from rank).
        pl = self.paged_layout
        kvd = self.kv_dtype
        s1 = jax.eval_shape(lambda: M.init_cache(cfg, 1, cache_len,
                                                 paged=pl, kv_dtype=kvd))
        s2 = jax.eval_shape(lambda: M.init_cache(cfg, 2, cache_len,
                                                 paged=pl, kv_dtype=kvd))
        bdim = jax.tree.map(
            lambda a, b: next((d for d in range(a.ndim)
                               if a.shape[d] != b.shape[d]), -1), s1, s2)

        def kind_of(path, bd):
            name = str(getattr(path[-1], "key", ""))
            if name in ("kp", "vp", "kps", "vps"):
                return -2   # scale pools ride the page pool: same code
            if name == "pt":
                return -3
            return bd
        self._bdim = jax.tree_util.tree_map_with_path(kind_of, bdim)
        bdims = self._bdim

        def group_cache():
            """The admission prefill's one-row input cache.  Its pool
            leaves are empty (``build_group`` hands the prefill the
            engine's pool), so it never holds a second pool; the prefill's
            output is not kept, and stale rows would be hidden by the
            kpos/pos invariant anyway."""
            c = M.init_cache(cfg, 1, cache_len, dtype=jnp.float32,
                             paged=self.paged_layout,
                             kv_dtype=self.kv_dtype)
            return jax.tree.map(
                lambda bd, x: jnp.zeros((0,), x.dtype) if bd == -2 else x,
                bdims, c)

        self._group_cache = None if self.prefill_step is None \
            else jax.jit(group_cache)()

        def scatter(big, small, mask):
            """big[j] <- small[0] where mask[j], per cache leaf (``small``
            is one row) — landing an admitted row is one jitted call."""
            def one(bd, b, s):
                if bd == -2:
                    return s    # shared pool: the prefill's writes ARE the
                                # engine's (one physical pool)
                if bd == -3:
                    bd = b.ndim - 2   # page table (…, n_slots, max_pages)
                if bd < 0:
                    return b    # engine tracks per-slot pos itself
                shape = [1] * b.ndim
                shape[bd] = -1
                return jnp.where(mask.reshape(shape), s.astype(b.dtype), b)
            return jax.tree.map(one, bdims, big, small)

        self._scatter = jax.jit(scatter)

        def build_group(group, engine, pt_rows):
            """Assemble the admission-prefill input cache: shared pools
            from the ENGINE cache (decode wrote pages since the last
            admission), page tables from the admission mapping, and
            contiguous / recurrent leaves from the group cache."""
            def one(bd, g, e):
                if bd == -2:
                    return e
                if bd == -3:
                    return jnp.broadcast_to(pt_rows, g.shape)
                return g
            return jax.tree.map(one, bdims, group, engine)

        self._build_group = jax.jit(build_group)

        def set_pt(cache, pt):
            """Push the host page table into every pt leaf (decode-time
            incremental allocs / COW forks / completion frees)."""
            def one(bd, leaf):
                if bd == -3:
                    return jnp.broadcast_to(pt, leaf.shape)
                return leaf
            return jax.tree.map(one, bdims, cache)

        self._set_pt = jax.jit(set_pt)

        def copy_page(cache, src, dst):
            """Copy-on-write fork: pool row src -> dst in every layer's
            pools (scan-stacked pools carry a leading cycle dim)."""
            def one(bd, leaf):
                if bd != -2:
                    return leaf
                if leaf.ndim == 5:
                    return leaf.at[:, dst].set(leaf[:, src])
                return leaf.at[dst].set(leaf[src])
            return jax.tree.map(one, bdims, cache)

        self._copy_page = jax.jit(copy_page)

    # -- clock / fault plumbing --------------------------------------------

    def _apply_fault_pressure(self) -> None:
        """Seize ``FaultPlan.hold_pages`` from the pool at init/reset —
        standing pressure that shrinks the usable pool (never below one
        allocatable page)."""
        if self.paged and self.fault_plan and self.fault_plan.hold_pages:
            n = min(self.fault_plan.hold_pages, len(self.alloc.free) - 1)
            self._fault_held = [self.alloc.alloc() for _ in range(n)]
        else:
            self._fault_held = []

    @property
    def usable_pages(self) -> int:
        """Pages a request can actually get: pool minus sink minus any
        fault-plan standing pressure."""
        return self.n_pages - 1 - len(self._fault_held)

    def start_clock(self) -> None:
        self._t0 = self.clock()
        self._virtual = 0.0

    def now(self) -> float:
        """Seconds since ``start_clock`` plus injected virtual latency.
        Before the clock starts (direct ``admit``/``decode_step_all``
        driving in tests) time sits at the accumulated virtual offset."""
        if self._t0 is None:
            return self._virtual
        return self.clock() - self._t0 + self._virtual

    def advance(self, dt: float) -> None:
        """Wait ``dt`` seconds: a wall sleep on the real clock, a virtual
        jump under a test/fault clock (keeps idle waits deterministic)."""
        if dt <= 0:
            return
        if self.virtual_time:
            self._virtual += dt
        else:
            time.sleep(dt)

    def _try_alloc(self, *, reserved: bool = False) -> Optional[int]:
        """All engine page allocations funnel through here: numbers the
        global call sequence so a ``FaultPlan`` can fail chosen calls
        deterministically.  An injected failure never touches the
        allocator — reservations survive it and the caller recovers the
        same way it recovers real exhaustion."""
        i = self._alloc_calls
        self._alloc_calls += 1
        if self.fault_plan is not None and self.fault_plan.alloc_fails(i):
            self.injected_alloc_failures += 1
            return None
        return self.alloc.try_alloc(reserved=reserved)

    # -- scheduling: backpressure, deadlines, preemption --------------------

    def _need_pages(self, req: Request) -> int:
        """Pages to reserve at admission.  ``reserve`` policy: worst case,
        ceil((prompt + max_new)/page_size) clamped to the cache — decode
        can never exhaust.  ``optimistic``: just the effective prompt's
        pages — generation growth is overcommitted and recovered by
        preempt-and-requeue.

        Speculation widens the reserve worst case by ``spec_k - 1``: a
        verify round pre-maps pages covering up to that many tokens past
        the committed frontier, and under ``reserve`` those pages stay
        mapped through a rejection (the reservation already paid for
        them), so the never-preempts guarantee must cover the speculative
        in-flight tail too."""
        if not self.paged:
            return 0
        plen = len(req.prompt) + len(req.tokens)
        total = plen if self.admission == "optimistic" \
            else len(req.prompt) + req.max_new + self.spec_k - 1
        return -(-min(total, self.cache_len) // self.page_size)

    def enqueue(self, req: Request) -> None:
        if req.eff_arrival < 0:
            req.eff_arrival = req.arrival
        self.queue.append(req)

    def _shed_admission(self, req: Request, now: float) -> None:
        """TTFT deadline missed while queued: shed.  With retries left the
        request re-enqueues with exponential backoff (client-retry
        semantics — its TTFT clock restarts at the new effective
        arrival); otherwise it drops terminally."""
        self.sheds_admission += 1
        if req.retry_count < req.max_retries:
            req.retry_count += 1
            self.retries += 1
            req.eff_arrival = now + \
                self.retry_backoff * (2 ** (req.retry_count - 1))
            self.queue.append(req)
        else:
            req.shed_reason = "ttft-deadline"
            req.t_done = now
            self.shed_requests.append(req)

    def schedule_admissions(self, now: float) -> List[tuple]:
        """Pick queued requests for free slots, FIFO.  This is where
        backpressure lives: a paged admission must first ``reserve`` its
        page demand, and a head that doesn't fit blocks the line (no
        starvation — pool drain admits it first).  Retry-backoff entries
        whose effective arrival hasn't come are skipped, not blocking.
        TTFT-deadline misses shed here, before burning a prefill.

        A picked request's ``t_sched`` is set to ``now``.  A call that
        leaves a due request queued counts in ``admit_blocked_pages``
        (its reservation failed) or ``admit_blocked_slots`` (no slot was
        free)."""
        with tracing.span("serve.schedule"):
            return self._schedule(now)

    def _schedule(self, now: float) -> List[tuple]:
        self.queue_depths.append(len(self.queue))
        pairs: List[tuple] = []
        free_slots = [j for j in range(self.n_slots)
                      if self.req_of[j] is None]
        i = 0
        while i < len(self.queue) and free_slots:
            req = self.queue[i]
            if req.eff_arrival > now:
                i += 1          # backoff pending; later entries may be due
                continue
            if req.deadline_ttft is not None and req.t_first < 0 \
                    and now - req.eff_arrival > req.deadline_ttft:
                del self.queue[i]
                self._shed_admission(req, now)
                continue
            need = self._need_pages(req)
            if self.paged and not self.alloc.reserve(need):
                self.admit_blocked_pages += 1
                break           # head-of-line waits for pool drain
            j = free_slots.pop(0)
            self.resv_of[j] = need
            del self.queue[i]
            req.t_sched = now
            pairs.append((req, j))
        if not free_slots and any(
                r.eff_arrival <= now
                for r in itertools.islice(self.queue, i, None)):
            self.admit_blocked_slots += 1
        return pairs

    def _release_reservation(self, j: int) -> None:
        if self.paged and self.resv_of[j]:
            self.alloc.unreserve(int(self.resv_of[j]))
            self.resv_of[j] = 0

    def _slot_alloc(self, j: int) -> Optional[int]:
        """Allocate one page for slot ``j``, consuming its admission
        reservation while any remains (reserved allocs cannot fail short
        of an injected fault, which leaves the unit intact); past the
        reservation it falls through to optimistic unreserved allocation."""
        if self.resv_of[j] > 0:
            p = self._try_alloc(reserved=True)
            if p is not None:
                self.resv_of[j] -= 1
            return p
        return self._try_alloc()

    def _choose_victim(self) -> Optional[int]:
        """Preemption victim: least decode progress first (cheapest
        re-prefill on restore), then most private pages (frees the most),
        then the youngest request — the oldest, furthest-along request is
        always protected, which is the forward-progress argument."""
        best, best_key = None, None
        for v in range(self.n_slots):
            req = self.req_of[v]
            if req is None:
                continue
            private = sum(1 for p in self.pt_host[v]
                          if p >= 0 and self.alloc.ref[int(p)] == 1) \
                if self.paged else 0
            k = (len(req.tokens), -private, -req.rid)
            if best_key is None or k < best_key:
                best, best_key = v, k
        return best

    def _preempt(self, v: int) -> None:
        """Evict slot ``v`` and requeue its request at the queue FRONT.
        Private pages free (decref); shared prefix pages keep their other
        references and stay in the ``PrefixIndex``, so restore re-maps
        them and chunk skipping makes the re-prefill cheap.  Generated
        tokens stay on the request — ``_eff_prompt`` folds them into the
        re-prefill, preserving greedy token-identity."""
        req = self.req_of[v]
        if self.paged:
            self._free_slot_pages(v)
        self.req_of[v] = None
        self.active[v] = False
        self.pos[v] = 0
        self.tok[v] = 0
        req.preemptions += 1
        self.preemptions += 1
        self.requeues += 1
        self.queue.appendleft(req)

    def _alloc_with_preemption(self, j: int) -> Optional[int]:
        """Decode-time page grab for slot ``j``: on exhaustion, preempt
        victims until the allocation succeeds or ``j`` preempts itself
        (returns None; the caller skips the now-empty slot).  Terminates:
        every failed attempt evicts one active slot, and ``j`` is always
        a candidate."""
        while True:
            p = self._slot_alloc(j)
            if p is not None:
                return p
            v = self._choose_victim()
            if v is None:       # unreachable: j itself is active
                raise RuntimeError(
                    "page pool exhausted with no preemptible slot")
            self._preempt(v)
            if v == j:
                return None

    # -- admission ----------------------------------------------------------

    def _write_row(self, row_cache, j: int) -> None:
        """Land a one-row admission cache in engine slot ``j`` (one jitted
        masked write)."""
        mask = np.zeros(self.n_slots, bool)
        mask[j] = True
        self.cache = self._scatter(self.cache, row_cache,
                                   self.jnp.asarray(mask))

    def _map_prompt_pages(self, req: Request, j: int) -> Optional[int]:
        """Build one admitted request's page-table row: map matching
        cached prefix pages read-only (incref), allocate fresh pages for
        the rest, and register the prompt's blocks so LATER admissions —
        including requests in this same group — can share them.  Returns
        the shared coverage in tokens (drives chunk skipping), or None if
        the pool ran out mid-row — in which case every page already
        placed (incref'd prefix hits and fresh allocs alike) is unwound,
        so refcounts and ``used_pages`` return exactly to their
        pre-admission values (a partial row used to leak here).

        Prefix-hit increfs consume the slot's reservation units too: a
        shared page the request maps IS part of its materialized demand.

        Same-group sharing is safe because every non-skipped chunk's
        writes into a shared page replay the identical token values at the
        identical positions; first divergent DECODE writes fork the page
        via copy-on-write in ``decode_step_all``."""
        prompt = _eff_prompt(req)
        plen = len(prompt)
        n_p = -(-plen // self.page_size)
        self.pages_requested += n_p
        row = np.full(self.max_pages, -1, np.int32)
        matched = self.prefix_index.lookup(prompt, self.alloc) \
            if self.prefix_cache else []
        placed: List[int] = []
        cov = 0
        for idx, (page, ntok) in enumerate(matched):
            self.alloc.incref(page)
            if self.resv_of[j] > 0:
                self.alloc.unreserve(1)
                self.resv_of[j] -= 1
            row[idx] = page
            placed.append(page)
            cov += ntok
        for idx in range(len(matched), n_p):
            p = self._slot_alloc(j)
            if p is None:
                # unwind the partial row: the admission must be all or
                # nothing, else these pages leak unreferenced-but-held
                for q in placed:
                    self.alloc.decref(int(q))
                self._release_reservation(j)
                self.pages_requested -= n_p
                return None
            row[idx] = p
            placed.append(p)
            self.pages_alloced += 1
        if self.prefix_cache:
            self.prefix_index.register(prompt, row[:n_p], self.alloc)
        self.pt_host[j] = row
        return cov

    def _prefill_input(self, j: Optional[int] = None):
        """The one-row prefill's input cache: the group cache, plus (paged
        layout) the engine's pools and slot ``j``'s page-table row — all
        unmapped for None, so the writes land in the page-0 sink of a
        copy of the pool."""
        if not self.paged:
            return self._group_cache
        row = self.pt_host[j:j + 1] if j is not None else \
            np.full((1, self.max_pages), -1, np.int32)
        return self._build_group(self._group_cache, self.cache,
                                 self.jnp.asarray(row))

    def _prefill_one(self, req: Request, j: int, cov: int, rid_arg: str):
        """Chunked flash prefill of one admitted request on one row: its
        effective prompt right-padded to its own chunk grid.  Returns
        (first token, cache).  One row per request keeps the prefill
        programs to one per chunk offset, and a lone request (most
        admissions) pays for no padding rows.

        Paged layout: slot ``j``'s page table was mapped (with prefix
        reuse) by ``admit`` before this call; ``cov`` is the prompt's prefix
        coverage, and a chunk it spans whole — and that holds not the last
        prompt token — is skipped outright: its KV already sits in the
        shared pages (an earlier request of this group wrote them first).
        ``rid_arg`` names the group in its spans."""
        jnp = self.jnp
        prompt = _eff_prompt(req)
        plen = len(prompt)
        toks, plens, grid = _pad_prompt(prompt, self.chunk, self.cache_len)
        skip: set = set()
        if self.paged and self.prefix_cache and all(
                k == "attn" for k in self.cfg.layer_kinds()):
            # ring layers keep contiguous caches that need every chunk, so
            # skipping is global-attention-only
            skip = {p0 for p0, c in grid
                    if cov >= p0 + c and plen - 1 >= p0 + c}
            self.prefill_chunks_skipped += len(skip)
        chunks = [(p0, c) for p0, c in grid if p0 not in skip]
        self.prefill_one_row_admissions += 1
        self.prefill_token_rows += sum(c for _, c in chunks)
        self.prefill_tokens_computed += sum(min(plen, p0 + c) - p0
                                            for p0, c in chunks)
        with tracing.span("serve.admit.prefill", rids=rid_arg):
            last, cache = _chunked_prefill(self.prefill_step, self.params,
                                           self._prefill_input(j), toks,
                                           plens, grid, skip=skip)
        # first token at logical position plen draws from the (rid, plen)
        # stream — same derivation every later decode/verify sample uses
        with tracing.span("serve.admit.first_tokens", rids=rid_arg):
            first = self.sample_first(jnp.asarray(last), self.base_key,
                                      jnp.asarray([req.rid], np.int32),
                                      jnp.asarray(plens, np.int32))
            return int(np.asarray(first)[0]), cache

    def _prefill_loop(self, req: Request):
        """Recurrent caches: token-by-token loop on a single-row cache."""
        jnp = self.jnp
        key = self.jax.random.fold_in(self.base_key,
                                      np.uint32(2 ** 31 + req.rid))
        cache = self.M.init_cache(self.cfg, 1, self.cache_len,
                                  dtype=jnp.float32,
                                  kv_dtype=self.kv_dtype)
        prompt = _eff_prompt(req)
        self.prefill_token_rows += len(prompt)
        self.prefill_tokens_computed += len(prompt)
        for i in range(len(prompt)):
            tok, _, cache = self.serve_step(
                self.params, cache,
                {"tokens": jnp.asarray(prompt[None, i:i + 1])},
                jnp.asarray(i, jnp.int32),
                self.jax.random.fold_in(key, i))
        return int(tok[0]), cache

    def admit(self, pairs: List[tuple], now: float) -> List[Request]:
        """Admit ``pairs`` of (request, free slot) — one batched prefill
        for KV-cache archs, a per-request loop otherwise.  Returns the
        requests already satisfied by their prefill token (max_new == 1),
        which never occupy a slot.

        Paged page-table mapping happens first; a request whose mapping
        hits pool exhaustion is unwound (no leak) and requeued at the
        queue front — it drops out of this admission group instead of
        crashing it."""
        if not pairs:
            return []
        t0 = self.now()
        rid_arg = tracing.ids(r.rid for r, _ in pairs)
        try:
            with tracing.span("serve.admit", rids=rid_arg):
                return self._admit(pairs, now, rid_arg)
        finally:
            # admission wall (prompt prefill + mapping + bookkeeping)
            # accumulates separately so _report can expose a decode-only
            # token rate — short-generation benches would otherwise
            # dilute decode-path comparisons with identical prefill cost
            self.prefill_wall += self.now() - t0

    def _admit(self, pairs: List[tuple], now: float,
               rid_arg: str) -> List[Request]:
        shared = None
        if self.paged:
            kept, shared = [], []
            with tracing.span("serve.admit.map_pages", rids=rid_arg):
                for req, j in pairs:
                    cov = self._map_prompt_pages(req, j)
                    if cov is None:
                        self.admission_alloc_failures += 1
                        self.requeues += 1
                        req.eff_arrival = min(req.eff_arrival, now) \
                            if req.eff_arrival >= 0 else now
                        self.queue.appendleft(req)
                    else:
                        kept.append((req, j))
                        shared.append(cov)
            if not kept:
                return []
            if len(kept) < len(pairs):
                rid_arg = tracing.ids(r.rid for r, _ in kept)
            pairs = kept
        # one request at a time, each row landing before the next prefill
        # reads the pool: a later request may map pages an earlier one of
        # this group wrote
        firsts = []
        for i, (r, j) in enumerate(pairs):
            if self.prefill_step is not None:
                f, cache = self._prefill_one(r, j, shared[i] if shared
                                             else 0, rid_arg)
            else:
                f, cache = self._prefill_loop(r)
            with tracing.span("serve.admit.write_rows", rids=rid_arg):
                self._write_row(cache, j)
            firsts.append(f)
        finished = []
        freed = False
        for (req, j), f in zip(pairs, firsts):
            plen_eff = len(req.prompt) + len(req.tokens)
            self.prefill_tokens += plen_eff
            req.t_admit = now
            if req.t_first < 0:     # TTFT is first-ever token, so a
                req.t_first = now   # preempted restore keeps the original
            req.tokens.append(f)
            if len(req.tokens) >= req.max_new:
                req.t_done = now
                finished.append(req)    # slot stays free
                if self.paged:
                    self._free_slot_pages(j)
                    freed = True
                continue
            self.pos[j] = plen_eff
            self.tok[j] = f
            self.active[j] = True
            self.req_of[j] = req
            if self.draft_src is not None:
                # sync the draft source's frontier to the committed
                # stream (a preempted restore folds accepted tokens in)
                self.draft_src.admit(req, j)
        if freed:
            self._push_pt()
        return finished

    # -- decode -------------------------------------------------------------

    def _free_slot_pages(self, j: int) -> None:
        for p in self.pt_host[j]:
            if p >= 0:
                self.alloc.decref(int(p))
        self.pt_host[j] = -1
        # a finishing/preempted slot also drops its unmaterialized
        # worst-case tail — that headroom goes back to the queue
        self._release_reservation(j)

    def _push_pt(self) -> None:
        # snapshot: pt_host is mutated in place between pushes, and on
        # CPU both device_put and an identity-forwarding jit output can
        # alias an aligned numpy buffer — the device-side table must not
        # see later host edits
        self.cache = self._set_pt(self.cache,
                                  self.jnp.asarray(self.pt_host.copy()))

    def _cow_into(self, src: int, dst: int) -> int:
        """Fork a shared page before the first divergent write: copy the
        pool rows in every layer into the already-allocated private copy,
        drop our reference to the shared original."""
        jnp = self.jnp
        self.cache = self._copy_page(self.cache,
                                     jnp.asarray(src, jnp.int32),
                                     jnp.asarray(dst, jnp.int32))
        self.alloc.decref(src)
        self.cow_events += 1
        self.pages_alloced += 1
        return dst

    def _sids(self):
        """Per-slot sampling stream ids (request ids; idle rows draw
        from a garbage stream that is never consumed)."""
        return self.jnp.asarray(np.asarray(
            [r.rid if r is not None else 0 for r in self.req_of],
            np.int32))

    def _spec_step_all(self):
        """One speculative decode round over the slot table: draft up to
        ``k_j - 1`` tokens per slot, then score the whole (n_slots,
        spec_k) chunk, accept the longest matching draft prefix plus the
        bonus target token, and commit exactly the accepted rows' KV —
        all inside ONE fused jit launch — then roll back page-table
        state mapped for wholly-rejected positions on the host.

        Layout rules (DESIGN.md §spec-decode):

          * contiguous / ring: verify never writes, so KV rollback is a
            no-op by construction — ``pos`` simply doesn't advance past
            the accepted prefix and the kpos mask hides everything
            beyond it;
          * paged: pages covering ``[pos, pos + k_j)`` are pre-mapped
            before the verify (through ``_alloc_with_preemption``,
            consuming the slot's reservation first); a page whose every
            token was rejected is decref'd-and-unmapped under
            ``optimistic`` admission, or kept mapped under ``reserve``
            (the reservation already paid for it, and the kpos mask
            keeps its unwritten rows invisible until decode really
            reaches them — no churn, no new failure point);
          * a COW fork triggered for the round's first page (the only
            one that can be shared — shared pages hold prompt prefix)
            never rolls back: the accept rule commits at least one
            token, which is exactly the write the fork was for.

        Adaptive k: a per-slot EMA of the draft accept rate raises
        ``k_j`` back toward ``--spec-k`` on streaks of full accepts and
        drops it toward 2 when drafts keep missing, so a low-acceptance
        slot degenerates toward plain decode instead of burning verify
        positions.  Non-speculating and draft-less slots ride the same
        verify batch with an effective k of 1 (shape-stable: the batch
        is always (n_slots, spec_k))."""
        jnp = self.jnp
        step = self.step_count
        now = self.now()
        kk = self.spec_k
        if self.fault_plan is not None:
            lat = self.fault_plan.step_latency(step)
            if lat:
                self._virtual += lat
                now = self.now()
            forced = False
            for _ in range(self.fault_plan.forced_preempts(step)):
                v = self._choose_victim()
                if v is None:
                    break
                self._preempt(v)
                self.forced_preemptions += 1
                forced = True
            if forced and self.paged:
                self._push_pt()
        # -- per-slot draft chunks: row j = [tok_j, d_1 .. d_{k-1}] -----
        k_eff = np.ones(self.n_slots, np.int32)
        toks = np.zeros((self.n_slots, kk), np.int32)
        hist: List[Optional[List[int]]] = [None] * self.n_slots
        active = np.zeros(self.n_slots, bool)
        for j in range(self.n_slots):
            req = self.req_of[j]
            if req is None:
                continue
            active[j] = True
            toks[j, 0] = self.tok[j]
            # k_j clamps to the cache END only, never to the request's
            # remaining budget: verify may range past it (commit clamps
            # n_acc), which is the in-flight tail _need_pages and
            # _validate_trace charge for
            k_eff[j] = max(1, min(int(self.k_of[j]),
                                  self.cache_len - int(self.pos[j])))
            hist[j] = [int(t) for t in req.prompt] + req.tokens
        if self.spec == "draft":
            drafts = self.draft_src.propose(active, hist, self.pos,
                                            self.tok, kk)
            if kk > 1:
                toks[:, 1:] = drafts[:, :kk - 1]
        else:
            for j in range(self.n_slots):
                if active[j] and k_eff[j] >= 2:
                    props = self.draft_src.propose_one(hist[j],
                                                       int(k_eff[j]))
                    k_eff[j] = min(int(k_eff[j]), 1 + len(props))
                    if props:
                        toks[j, 1:k_eff[j]] = props[:int(k_eff[j]) - 1]
        # -- paged: pre-map every page the speculative span can touch --
        ps = self.page_size
        new_idx: dict = {}
        if self.paged:
            dirty = False
            for j in range(self.n_slots):
                if self.req_of[j] is None:
                    continue
                lo = int(self.pos[j]) // ps
                hi = (int(self.pos[j]) + int(k_eff[j]) - 1) // ps
                for idx in range(lo, hi + 1):
                    if self.req_of[j] is None:
                        break       # evicted as a victim mid-loop
                    page = int(self.pt_host[j, idx])
                    if page < 0:
                        p = self._alloc_with_preemption(j)
                        if p is None:
                            dirty = True     # j preempted itself
                            break
                        self.pt_host[j, idx] = p
                        self.pages_requested += 1
                        self.pages_alloced += 1
                        new_idx.setdefault(j, []).append(idx)
                        dirty = True
                    elif self.alloc.ref[page] > 1:
                        p = self._alloc_with_preemption(j)
                        if p is None:
                            dirty = True
                            break
                        # re-read: a preemption inside the alloc may
                        # have un-shared the page
                        page = int(self.pt_host[j, idx])
                        if page >= 0 and self.alloc.ref[page] > 1:
                            self.pt_host[j, idx] = self._cow_into(page, p)
                        else:
                            self.alloc.decref(p)  # fork no longer needed
                        dirty = True
            if dirty:
                self._push_pt()
        # preemptions above may have evicted slots already drafted
        for j in range(self.n_slots):
            if active[j] and self.req_of[j] is None:
                active[j] = False
                new_idx.pop(j, None)
        # remaining budget per slot: the fused accept clamps n_acc to it
        # (verify may range past it — the in-flight tail _need_pages and
        # _validate_trace charge for); 0 marks an idle row, which
        # accepts and commits nothing
        remaining = np.zeros(self.n_slots, np.int32)
        for j in range(self.n_slots):
            if active[j]:
                req = self.req_of[j]
                remaining[j] = req.max_new - len(req.tokens)
        # -- one fused verify + accept + commit over the whole table ---
        # single launch per round; syncing targets/n_acc below forces
        # the commit too, so every host-mutated buffer the call read
        # (pos, toks) is provably consumed before bookkeeping advances
        # it in place — the async zero-copy aliasing hazard a separate
        # commit launch had (see min_accept_margin's docstring) can't
        # recur by construction
        targets, n_acc, self.cache = self.verify_step(
            self.params, self.cache, {"tokens": jnp.asarray(toks)},
            jnp.asarray(self.pos), self.base_key, self._sids(),
            jnp.asarray(k_eff), jnp.asarray(remaining))
        targets = np.asarray(targets)
        n_acc = np.asarray(n_acc)
        # -- host bookkeeping of the device accept decision ------------
        for j in range(self.n_slots):
            if not active[j]:
                continue
            kj = int(k_eff[j])
            self.spec_drafted += kj - 1
            self.spec_drafts_accepted += int(n_acc[j]) - 1
            self.spec_wasted_tokens += kj - int(n_acc[j])
            self.accepted_k.append(int(n_acc[j]))
        self.spec_rounds += 1
        # -- paged rollback: unmap wholly-rejected pre-mapped pages ----
        if self.paged:
            dirty = False
            for j, idxs in new_idx.items():
                pos_new = int(self.pos[j]) + int(n_acc[j])
                for idx in idxs:
                    if idx * ps >= pos_new \
                            and self.admission == "optimistic":
                        self.alloc.decref(int(self.pt_host[j, idx]))
                        self.pt_host[j, idx] = -1
                        self.spec_pages_rewound += 1
                        dirty = True
            if dirty:
                self._push_pt()
        # -- bookkeeping: tokens, pos, adaptive k, finish/shed ---------
        finished = []
        freed_any = False
        obs_j, obs_pos = [], []
        for j in range(self.n_slots):
            if not active[j]:
                continue
            req = self.req_of[j]
            na = int(n_acc[j])
            req.tokens.extend(int(t) for t in targets[j, :na])
            self.decode_tokens += na
            self.pos[j] += na
            self.tok[j] = int(targets[j, na - 1])
            obs_j.append(j)
            obs_pos.append(int(self.pos[j]))
            if int(k_eff[j]) > 1:
                rate = (na - 1) / (int(k_eff[j]) - 1)
                self.accept_ema[j] = (0.7 * self.accept_ema[j]
                                      + 0.3 * rate)
                if self.accept_ema[j] > 0.75:
                    self.k_of[j] = min(int(self.k_of[j]) + 1,
                                       self.spec_k)
                elif self.accept_ema[j] < 0.35:
                    self.k_of[j] = max(int(self.k_of[j]) - 1, 2)
            if len(req.tokens) >= req.max_new:
                req.t_done = now
                self.active[j] = False
                self.req_of[j] = None
                self.pos[j] = 0
                self.tok[j] = 0
                self.k_of[j] = self.spec_k
                self.accept_ema[j] = 1.0
                finished.append(req)
                if self.paged:
                    self._free_slot_pages(j)
                    freed_any = True
            elif req.deadline_total is not None \
                    and now - req.arrival > req.deadline_total:
                req.t_done = now
                req.shed_reason = "total-deadline"
                self.sheds_decode += 1
                self.shed_requests.append(req)
                self.active[j] = False
                self.req_of[j] = None
                self.pos[j] = 0
                self.tok[j] = 0
                self.k_of[j] = self.spec_k
                self.accept_ema[j] = 1.0
                if self.paged:
                    self._free_slot_pages(j)
                    freed_any = True
        if self.spec == "draft":
            self.draft_src.observe(obs_j, obs_pos)
        self.step_count += 1
        if self.paged:
            if freed_any:
                self._push_pt()
            self.page_occupancy.append(
                self.alloc.used_pages / max(self.n_pages - 1, 1))
        self.occupancy.append(float(np.mean([r is not None
                                             for r in self.req_of])))
        return finished

    def decode_step_all(self):
        """One per-slot decode step over the whole slot table.

        Paged growth and COW forks go through ``_alloc_with_preemption``:
        pool exhaustion evicts a victim (requeued, not lost) instead of
        raising.  Total-deadline misses shed mid-decode.  FaultPlan hooks
        run first: injected latency advances the virtual clock, forced
        preemptions evict the victim-policy choice.

        With speculation on, every decode step is a speculative round
        (non-speculating slots ride the verify batch with an effective
        k of 1 — the shape-stable degenerate case)."""
        if self.spec != "off":
            return self._spec_step_all()
        with tracing.span("serve.decode"):
            return self._decode_step()

    def _decode_step(self):
        jnp = self.jnp
        step = self.step_count
        now = self.now()
        if self.fault_plan is not None:
            lat = self.fault_plan.step_latency(step)
            if lat:
                self._virtual += lat
                now = self.now()
            forced = False
            for _ in range(self.fault_plan.forced_preempts(step)):
                v = self._choose_victim()
                if v is None:
                    break
                self._preempt(v)
                self.forced_preemptions += 1
                forced = True
            if forced and self.paged:
                self._push_pt()
        if self.paged:
            with tracing.span("serve.decode.pages"):
                self._grow_pages()
        with tracing.span("serve.decode.dispatch"):
            tok, _, self.cache = self.serve_step(
                self.params, self.cache,
                {"tokens": jnp.asarray(self.tok[:, None])},
                jnp.asarray(self.pos), self.base_key, self._sids())
        self.step_count += 1
        with tracing.span("serve.decode.wait"):
            tok = np.asarray(tok)
        with tracing.span("serve.decode.retire"):
            return self._retire(tok, now)

    def _grow_pages(self) -> None:
        """The step writes row pos[j] of each active slot: grow the table
        a page at a time, fork (COW) any still-shared page the write would
        land in, and push the table if it changed."""
        dirty = False
        for j in range(self.n_slots):
            if self.req_of[j] is None:
                continue
            idx = int(self.pos[j]) // self.page_size
            page = int(self.pt_host[j, idx])
            if page < 0:
                p = self._alloc_with_preemption(j)
                if p is None:
                    dirty = True        # j preempted itself
                    continue
                self.pt_host[j, idx] = p
                self.pages_requested += 1
                self.pages_alloced += 1
                dirty = True
            elif self.alloc.ref[page] > 1:
                p = self._alloc_with_preemption(j)
                if p is None:
                    dirty = True
                    continue
                # re-read: a preemption inside the alloc may have dropped
                # other references and un-shared the page
                page = int(self.pt_host[j, idx])
                if page >= 0 and self.alloc.ref[page] > 1:
                    self.pt_host[j, idx] = self._cow_into(page, p)
                else:
                    self.alloc.decref(p)    # fork no longer needed
                dirty = True
        if dirty:
            self._push_pt()

    def _retire(self, tok: np.ndarray, now: float) -> List[Request]:
        """Append each active slot's new token; free the slots of finished
        and total-deadline-shed requests."""
        finished = []
        freed_any = False
        for j in range(self.n_slots):
            req = self.req_of[j]
            if req is None:
                continue
            req.tokens.append(int(tok[j]))
            self.decode_tokens += 1
            self.pos[j] += 1
            self.tok[j] = int(tok[j])
            if len(req.tokens) >= req.max_new:
                req.t_done = now
                self.active[j] = False
                self.req_of[j] = None
                self.pos[j] = 0
                self.tok[j] = 0
                finished.append(req)
                if self.paged:
                    # free before the next step: a stale table row would
                    # let the idle slot's pos-0 write land in a page the
                    # allocator may hand to someone else
                    self._free_slot_pages(j)
                    freed_any = True
            elif req.deadline_total is not None \
                    and now - req.arrival > req.deadline_total:
                # mid-decode shed: past its total deadline the tokens are
                # worthless to the client — free the slot for the queue
                req.t_done = now
                req.shed_reason = "total-deadline"
                self.sheds_decode += 1
                self.shed_requests.append(req)
                self.active[j] = False
                self.req_of[j] = None
                self.pos[j] = 0
                self.tok[j] = 0
                if self.paged:
                    self._free_slot_pages(j)
                    freed_any = True
        if self.paged:
            if freed_any:
                self._push_pt()
            self.page_occupancy.append(
                self.alloc.used_pages / max(self.n_pages - 1, 1))
        self.occupancy.append(float(np.mean([r is not None
                                             for r in self.req_of])))
        return finished

    def reset(self):
        """Clear slot state and counters (compiled steps and caches stay
        warm) — used after the warmup pass.  Paged state resets too: fresh
        allocator, cleared prefix index, unmapped tables (stale pool
        content is unreachable once no table row names it — the kpos
        invariant)."""
        self.pos[:] = 0
        self.tok[:] = 0
        self.active[:] = False
        self.req_of = [None] * self.n_slots
        self.step_count = 0
        self.prefill_tokens = self.decode_tokens = 0
        self.prefill_token_rows = self.prefill_tokens_computed = 0
        self.prefill_wall = 0.0
        self.occupancy = []
        if self.paged:
            self.alloc = PageAllocator(self.n_pages)
            self.prefix_index.clear()
            self.pt_host[:] = -1
            self._push_pt()
        self.page_occupancy = []
        self.pages_requested = self.pages_alloced = 0
        self.cow_events = self.prefill_chunks_skipped = 0
        self.prefill_one_row_admissions = 0
        # robustness state: clear queue/sheds/counters, restart the fault
        # injector's deterministic counters, re-seize standing pressure on
        # the fresh allocator
        self.queue.clear()
        self.shed_requests = []
        self.resv_of[:] = 0
        self.preemptions = self.requeues = 0
        self.sheds_admission = self.sheds_decode = self.retries = 0
        self.admission_alloc_failures = 0
        self.injected_alloc_failures = self.forced_preemptions = 0
        self.admit_blocked_pages = self.admit_blocked_slots = 0
        self.queue_depths = []
        self._alloc_calls = 0
        self._t0 = None
        self._virtual = 0.0
        # speculative state: adaptive k back to the CLI ceiling, EMA
        # optimistic (first rounds draft at full k), counters zeroed,
        # draft cache re-synced to the empty slot table
        self.k_of[:] = self.spec_k
        self.accept_ema[:] = 1.0
        self.spec_rounds = self.spec_drafted = 0
        self.spec_drafts_accepted = self.spec_wasted_tokens = 0
        self.spec_pages_rewound = 0
        self.accepted_k = []
        if self.draft_src is not None:
            self.draft_src.reset()
        self._apply_fault_pressure()


def _warmup(eng: ServeEngine, trace: List[Request]) -> float:
    """Compile everything the run can hit, outside the timed region: every
    prefill chunk offset the trace can reach (admission prefills always
    run one row a request, so these are exactly the run's shapes), the
    first-token sampler, the row scatter, and one decode step.

    Fault injection is suspended for the warmup pass (its deterministic
    call counters restart at reset anyway) so the warm request always
    completes its compile coverage."""
    t0 = time.perf_counter()
    plan, eng.fault_plan = eng.fault_plan, None
    if eng.prefill_step is not None:
        pmax = max((len(r.prompt) for r in trace), default=1)
        if eng.paged and (plan is not None
                          or eng.admission == "optimistic"
                          or eng.usable_pages <
                          eng.n_slots * eng.max_pages):
            # preemption is possible: a requeued request's re-prefill
            # folds generated tokens in, so chunk grids can reach
            # prompt + max_new - 1 — compile those offsets too
            pmax = min(eng.cache_len,
                       max((len(r.prompt) + r.max_new - 1 for r in trace),
                           default=1))
        # the engine's own pool, through unmapped page tables: the warm
        # writes land in the page-0 sink of a copy of it
        toks, plens, grid = _pad_prompt(np.zeros(pmax, np.int32), eng.chunk,
                                        eng.cache_len)
        _chunked_prefill(eng.prefill_step, eng.params, eng._prefill_input(),
                         toks, plens, grid)
        if eng.spec == "draft":
            # draft admissions are single-row prefills over the same
            # chunk grid — compile those offsets too
            eng.draft_src.warm_prefill(pmax)
    warm = Request(rid=-1, prompt=np.zeros(min(8, eng.cache_len - 1),
                                           np.int32),
                   max_new=2, arrival=0.0)
    eng.admit([(warm, 0)], 0.0)
    eng.decode_step_all()
    eng.fault_plan = plan      # before reset: it re-seizes hold_pages
    eng.reset()
    return time.perf_counter() - t0


def _report(mode: str, eng: ServeEngine, done: List[Request], wall: float,
            warmup_s: float) -> dict:
    lat = [r.t_done - r.arrival for r in done]
    ttft = [r.t_first - r.arrival for r in done]
    total_new = sum(len(r.tokens) for r in done)
    first_req = min(done, key=lambda r: r.rid) if done else None
    paged = {}
    if eng.paged:
        paged = {
            "page_size": eng.page_size,
            "n_pages": eng.n_pages,
            "usable_pages": eng.usable_pages,
            "page_occupancy": round(float(np.mean(eng.page_occupancy)), 3)
            if eng.page_occupancy else 0.0,
            "pages_requested": eng.pages_requested,
            "pages_alloced": eng.pages_alloced,
            "dedup_ratio": round(
                eng.pages_requested / max(eng.pages_alloced, 1), 3),
            "cow_events": eng.cow_events,
            "prefill_chunks_skipped": eng.prefill_chunks_skipped,
            "prefix_cache": eng.prefix_cache,
            "pool_high_water": int(eng.alloc.high_water),
        }
    robustness = {
        "admission_policy": eng.admission,
        "preemptions": eng.preemptions,
        "requeues": eng.requeues,
        "sheds": eng.sheds_admission + eng.sheds_decode,
        "sheds_admission": eng.sheds_admission,
        "sheds_decode": eng.sheds_decode,
        "shed_requests": len(eng.shed_requests),
        "retries": eng.retries,
        "admission_alloc_failures": eng.admission_alloc_failures,
        "queue_depth": _percentiles(eng.queue_depths),
        "fault_plan": eng.fault_plan is not None,
        "injected_alloc_failures": eng.injected_alloc_failures,
        "forced_preemptions": eng.forced_preemptions,
    }
    speculative = {"spec": eng.spec}
    if eng.spec != "off":
        from repro.launch import traffic
        drafted = eng.spec_drafted
        speculative.update({
            "spec_k": eng.spec_k,
            "draft_source": eng.draft_src.kind,
            "rounds": eng.spec_rounds,
            "drafted_tokens": drafted,
            "accepted_draft_tokens": eng.spec_drafts_accepted,
            "accept_rate": round(
                eng.spec_drafts_accepted / drafted, 3) if drafted else 0.0,
            "mean_accepted_k": round(
                float(np.mean(eng.accepted_k)), 3)
            if eng.accepted_k else 0.0,
            "wasted_tokens": eng.spec_wasted_tokens,
            "wasted_bytes": traffic.spec_wasted_bytes(
                eng.cfg, eng.spec_wasted_tokens),
            "pages_rewound": eng.spec_pages_rewound,
        })
    return {
        "paged": eng.paged, **paged,
        "kv_dtype": eng.kv_dtype_name,
        "mode": mode, "slots": eng.n_slots, "requests": len(done),
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(wall, 3),
        "prefill_tokens": eng.prefill_tokens,
        "prefill_one_row_admissions": eng.prefill_one_row_admissions,
        "generated_tokens": total_new,
        "tokens_per_s": round(total_new / wall, 1) if wall else 0.0,
        # decode-phase rate: admission (prefill) wall subtracted, so legs
        # differing only in decode strategy compare undiluted
        "prefill_wall_s": round(eng.prefill_wall, 3),
        "decode_tokens_per_s": round(
            total_new / max(wall - eng.prefill_wall, 1e-9), 1)
        if wall else 0.0,
        "latency_s": _percentiles(lat),
        "ttft_s": _percentiles(ttft),
        "occupancy": round(float(np.mean(eng.occupancy)), 3)
        if eng.occupancy else 0.0,
        "chunked_prefill": eng.prefill_step is not None,
        "robustness": robustness,
        "speculative": speculative,
        # the FIRST REQUEST's first generated tokens, not the first decode
        # step across the batch
        "sample_tokens": first_req.tokens[:4] if first_req else [],
    }


def _drain(eng: ServeEngine, pending: List[Request], qi: int,
           done: List[Request]) -> int:
    """The shared serve loop: feed arrivals into the engine queue, let the
    scheduler admit (backpressure, deadlines, retries), decode; when the
    engine idles, jump to the next event (arrival or retry-backoff
    expiry) instead of spinning.  Runs until ``pending[qi:]``, the queue
    and the slot table are all empty; returns the advanced ``qi``."""
    while qi < len(pending) or eng.queue \
            or any(r is not None for r in eng.req_of):
        now = eng.now()
        while qi < len(pending) and pending[qi].arrival <= now:
            eng.enqueue(pending[qi])
            qi += 1
        done.extend(eng.admit(eng.schedule_admissions(now), now))
        if not any(r is not None for r in eng.req_of):
            nxt = [r.eff_arrival for r in eng.queue]
            if qi < len(pending):
                nxt.append(pending[qi].arrival)
            if not nxt:
                break
            eng.advance(min(nxt) - eng.now())
            continue
        done.extend(eng.decode_step_all())
    return qi


def run_engine(cfg, params, trace: List[Request], *, n_slots: int,
               cache_len: int, chunk: int, sample: bool, seed: int,
               page_size: int = 128, n_pages: int = 0,
               prefix_cache: bool = True,
               paged: Optional[bool] = None, kv_dtype="f32",
               admission: str = "reserve",
               fault_plan: Optional[FaultPlan] = None, clock=None,
               retry_backoff: float = 0.05, spec: str = "off",
               spec_k: int = 4, draft_arch: Optional[str] = None) -> dict:
    """Continuous batching: arrivals feed the engine queue, the scheduler
    admits under reservation backpressure into freed slots, per-slot
    decode (with preempt-and-requeue on pool exhaustion)."""
    eng = ServeEngine(cfg, params, n_slots=n_slots, cache_len=cache_len,
                      chunk=chunk, sample=sample, seed=seed,
                      page_size=page_size, n_pages=n_pages,
                      prefix_cache=prefix_cache, paged=paged,
                      kv_dtype=kv_dtype, admission=admission,
                      fault_plan=fault_plan, clock=clock,
                      retry_backoff=retry_backoff, spec=spec,
                      spec_k=spec_k, draft_arch=draft_arch)
    _validate_trace(trace, cache_len,
                    page_size=eng.page_size if eng.paged else None,
                    usable_pages=eng.usable_pages if eng.paged else None,
                    spec_k=eng.spec_k)
    warmup_s = _warmup(eng, trace)

    pending = sorted(trace, key=lambda r: r.arrival)
    done: List[Request] = []
    eng.start_clock()
    _drain(eng, pending, 0, done)
    wall = eng.now()
    return _report("engine", eng, done, wall, warmup_s)


def run_lockstep(cfg, params, trace: List[Request], *, n_slots: int,
                 cache_len: int, chunk: int, sample: bool, seed: int,
                 chunked_prefill: bool = True, page_size: int = 128,
                 n_pages: int = 0, prefix_cache: bool = True,
                 paged: Optional[bool] = None, kv_dtype="f32") -> dict:
    """Wave-batched baseline: admit ``n_slots`` requests at once (waiting
    until the whole wave has arrived), then decode until the wave's
    *slowest* request finishes before admitting the next wave.

    Runs on the same ``ServeEngine`` machinery as ``run_engine`` — same
    kernels, same (correct, per-request) prefill paths for every cache
    kind — so the benchmark difference between the two runners is purely
    the batching discipline: freed slots idle until the wave drains
    instead of taking the next arrival."""
    if not chunked_prefill and paged is None:
        paged = False   # the token-loop prefill writes contiguous caches
    eng = ServeEngine(cfg, params, n_slots=n_slots, cache_len=cache_len,
                      chunk=chunk, sample=sample, seed=seed,
                      page_size=page_size, n_pages=n_pages,
                      prefix_cache=prefix_cache, paged=paged,
                      kv_dtype=kv_dtype)
    if not chunked_prefill:
        eng.prefill_step = None
    _validate_trace(trace, cache_len,
                    page_size=eng.page_size if eng.paged else None,
                    usable_pages=eng.usable_pages if eng.paged else None)
    warmup_s = _warmup(eng, trace)

    pending = sorted(trace, key=lambda r: r.arrival)
    waves = [pending[i:i + n_slots]
             for i in range(0, len(pending), n_slots)]
    done: List[Request] = []
    eng.start_clock()
    for wave in waves:
        now = eng.now()
        wait = max(r.arrival for r in wave) - now
        if wait > 0:       # whole wave must have arrived (lockstep admit)
            eng.advance(wait)
            now = eng.now()
        done.extend(eng.admit(list(zip(wave, range(len(wave)))), now))
        # finished slots keep burning their decode step until the whole
        # wave drains — the cost the continuous engine removes
        while any(r is not None for r in eng.req_of):
            done.extend(eng.decode_step_all())
        # an undersized pool can have preempted wave members into the
        # queue — drain them before the next wave so lockstep stays a
        # complete baseline
        if eng.queue:
            _drain(eng, [], 0, done)
    wall = eng.now()
    return _report("lockstep", eng, done, wall, warmup_s)


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def _range(s: str):
    lo, hi = s.split(",")
    return int(lo), int(hi)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's reduced smoke config instead of "
                    "its published widths")
    ap.add_argument("--mode", choices=("engine", "lockstep"),
                    default="engine")
    ap.add_argument("--slots", "--batch", dest="slots", type=int, default=4)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--prompt-range", type=_range, default=(16, 48),
                    help="uniform prompt-length range lo,hi")
    ap.add_argument("--gen-range", type=_range, default=(8, 32),
                    help="uniform generation-length range lo,hi")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="Poisson arrivals, requests/s (0 = all at t=0)")
    ap.add_argument("--chunk", type=int, default=128,
                    help="prefill chunk length (tokens per flash launch); "
                    "rounded to the nearest 128 multiple — the append "
                    "kernel's MXU alignment unit")
    ap.add_argument("--cache-len", type=int, default=0,
                    help="KV cache length (0 = max prompt + max gen)")
    ap.add_argument("--page-size", type=int, default=128,
                    help="paged-KV page size in tokens; rounded to the "
                    "nearest 128 multiple so page boundaries coincide "
                    "with the kernels' key-block tiles")
    ap.add_argument("--pages", type=int, default=0,
                    help="page-pool size (0 = worst case: slots * "
                    "pages-per-slot + 1 sink page)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix page reuse (isolates the "
                    "dedup win in benches; pages stay per-slot private)")
    ap.add_argument("--kv-dtype", default="f32",
                    choices=("f32", "bf16", "int8"),
                    help="KV cache storage dtype: f32, bf16 or int8 "
                    "(int8 stores per-(row, head) symmetric scales "
                    "alongside and dequantizes inside the kernels; archs "
                    "without attention layers log a fallback to f32)")
    ap.add_argument("--admission", choices=("reserve", "optimistic"),
                    default="reserve",
                    help="paged admission policy: 'reserve' holds back "
                    "worst-case ceil((prompt+max_new)/page_size) pages at "
                    "admission (decode can never exhaust); 'optimistic' "
                    "reserves only the prompt's pages and overcommits — "
                    "decode-time exhaustion preempts-and-requeues")
    ap.add_argument("--deadline-ttft", type=float, default=0.0,
                    help="per-request TTFT deadline in seconds (0 = none):"
                    " requests still queued past it are shed (with "
                    "--max-retries backoff re-enqueues)")
    ap.add_argument("--deadline-total", type=float, default=0.0,
                    help="per-request end-to-end deadline in seconds "
                    "(0 = none): decode past it sheds mid-flight")
    ap.add_argument("--max-retries", type=int, default=0,
                    help="re-enqueues (exponential backoff) granted to a "
                    "request shed at admission before it drops")
    ap.add_argument("--fault-plan", default="",
                    help="fault-injection plan: a JSON string or a path "
                    "to one (FaultPlan schema: fail_alloc_at, preempt_at, "
                    "latency_at, hold_pages) — deterministic overload "
                    "replay")
    ap.add_argument("--spec", choices=("off", "ngram", "draft"),
                    default="off",
                    help="speculative decoding: 'ngram' self-drafts from "
                    "each request's own history (prompt lookup, zero "
                    "model cost); 'draft' runs a tiny reduced-config "
                    "draft model on the same mesh; accepted tokens are "
                    "bit-identical to --spec off")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="max verify-chunk length per slot (1 current "
                    "token + up to k-1 drafts); per-slot adaptive k "
                    "throttles below this on low acceptance")
    ap.add_argument("--draft-arch", default=None,
                    help="--spec draft: architecture name for the "
                    "reduced draft config (default stablelm-1.6b "
                    "reduced, re-vocabed to the target)")
    ap.add_argument("--greedy", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace-seed", type=int, default=0)
    ap.add_argument("--decode-cp", action="store_true",
                    help="context-parallel serving: shard the KV cache's "
                    "sequence dim over the local devices (decode_cp rules "
                    "-> pallas_cp dispatch)")
    args = ap.parse_args()

    if args.chunk % 128 != 0:
        # a misaligned chunk size would push EVERY chunk of every prompt
        # off the fused append path (Sk = pos0 + C inherits the
        # misalignment) — round instead of silently serving on jnp
        rounded = max(128, round(args.chunk / 128) * 128)
        logging.warning(
            "--chunk %d is not a 128 multiple; rounding to %d so prefill "
            "chunks stay on the fused append kernel (misaligned chunks "
            "fall back to the jnp reference on every chunk)",
            args.chunk, rounded)
        args.chunk = rounded

    if args.page_size % 128 != 0:
        # a misaligned page size pushes the paged decode/append arms onto
        # the jnp oracle (page boundaries must coincide with key-block
        # tiles) — round instead of silently serving unfused
        rounded = max(128, round(args.page_size / 128) * 128)
        logging.warning(
            "--page-size %d is not a 128 multiple; rounding to %d so the "
            "paged dispatch arms stay on the fused kernels (misaligned "
            "pages fall back to the jnp reference)",
            args.page_size, rounded)
        args.page_size = rounded

    import jax

    from repro.configs import get_config
    from repro.distributed import ctx, sharding
    from repro.kernels import dispatch
    from repro.launch import compile_cache, hlo_analysis
    from repro.launch.mesh import make_mesh
    from repro.models import model as M

    compile_cache.enable()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if cfg.family == "vlm" or cfg.is_encdec:
        raise SystemExit(
            f"{cfg.name}: the serve engine drives token-in/token-out LMs; "
            "VLM embeds / encoder-decoder memories have no request-queue "
            "source here (the decode dry-run still lowers those shapes)")
    # serving holds the compute-dtype weights only: every step casts to
    # them anyway, and f32 masters would double the weights' HBM.  Jitted,
    # so the f32 draws never sit in HBM next to the bf16 result.
    params = jax.jit(lambda k: M.cast_params(cfg, M.init_params(cfg, k)))(
        jax.random.key(args.seed))
    cache_len = args.cache_len or (
        args.prompt_range[1] + args.gen_range[1])
    trace = gen_trace(args.requests, vocab=cfg.vocab_size,
                      prompt_range=args.prompt_range,
                      gen_range=args.gen_range,
                      arrival_rate=args.arrival_rate,
                      seed=args.trace_seed)
    for r in trace:
        r.deadline_ttft = args.deadline_ttft or None
        r.deadline_total = args.deadline_total or None
        r.max_retries = args.max_retries
    fault_plan = None
    if args.fault_plan:
        s = args.fault_plan
        if not s.lstrip().startswith("{"):
            with open(s) as f:
                s = f.read()
        fault_plan = FaultPlan.from_json(s)

    decode_layout = "replicated"
    combine_bytes = 0
    with contextlib.ExitStack() as stack:
        if args.decode_cp:
            n_dev = len(jax.devices())
            mesh = make_mesh((1, n_dev), ("data", "model"))
            rules = sharding.decode_rules(cfg, mesh, batch_size=args.slots)
            stack.enter_context(jax.set_mesh(mesh))
            stack.enter_context(ctx.use_mesh(mesh))
            stack.enter_context(ctx.sharding_rules(rules))
            n_shards = rules["decode_cp"]["n_shards"]
            decode_layout = f"decode_cp[{n_shards}]"
            from repro.launch import traffic
            combine_bytes = traffic.decode_cp_combine_bytes(
                cfg, args.slots, n_shards)
        dispatch.clear_decision_log()

        kw = dict(n_slots=args.slots, cache_len=cache_len,
                  chunk=args.chunk, sample=not args.greedy,
                  seed=args.seed, page_size=args.page_size,
                  n_pages=args.pages,
                  prefix_cache=not args.no_prefix_cache,
                  kv_dtype=args.kv_dtype)
        if args.mode == "engine":
            rec = run_engine(cfg, params, trace,
                             admission=args.admission,
                             fault_plan=fault_plan, spec=args.spec,
                             spec_k=args.spec_k,
                             draft_arch=args.draft_arch, **kw)
        else:
            if args.spec != "off":
                raise SystemExit("--spec needs --mode engine (lockstep "
                                 "is the non-speculative baseline)")
            rec = run_lockstep(cfg, params, trace, **kw)

    rec.update({
        "arch": cfg.name,
        "prompt_range": list(args.prompt_range),
        "gen_range": list(args.gen_range),
        "arrival_rate": args.arrival_rate,
        "decode_layout": decode_layout,
        "cp_combine_bytes_per_token": combine_bytes,
        "kernel_dispatch": [
            r for r in hlo_analysis.kernel_dispatch_summary()
            if r["op"] in ("decode_attention", "flash_attention",
                           "flash_append", "decode_paged",
                           "append_paged", "flash_verify",
                           "verify_paged")],
    })
    print(json.dumps(rec))


if __name__ == "__main__":
    main()
