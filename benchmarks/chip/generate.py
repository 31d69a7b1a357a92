"""The one traffic generator: every mix is a file of parameters in
``traffic/<name>.json`` that this module reads.

Kinds of mix:

* ``open_loop`` — independent users: ``round(rate * seconds)`` requests due
  inside the window, sent on schedule whether or not earlier ones finished.
* ``closed_backlog`` — a fixed backlog: ``initial`` requests are admitted
  during set-up and ``queued`` more wait, so a freed slot refills at once.
* ``train_batches`` — training steps back to back; the batches come from the
  program's own input pipeline, so the file holds only their shape.

Every seed gets the same work: lengths and inter-arrival gaps are the
quantiles ``(i + 0.5) / n`` of their distributions, in one fixed shuffled
order, and only the token ids come from the seed.  A 30 s window held a
few dozen requests, and shuffling the order by the seed moved the tokens
delivered inside it by 20% and the tails by 35% from seed to seed, while
two runs of one seed agreed within 1% (PERF.md); the seed now changes the
inputs, not the amount of work.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import List, Optional

import numpy as np


@dataclasses.dataclass
class Item:
    rid: int
    prompt: np.ndarray            # (P,) int32
    max_new: int
    arrival: float                # seconds after the window opens
    prefix: Optional[int] = None  # shared system prompt, if any


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The n stratified quantiles of a length distribution, as ints."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = spec["min"], spec["max"]
    if spec["dist"] == "lognormal":
        z = np.asarray([statistics.NormalDist().inv_cdf(x) for x in q])
        v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        v = lo + q * (hi - lo)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.round(v), lo, hi).astype(np.int64)


def zipf_counts(n: int, k: int, s: float) -> np.ndarray:
    """n draws over k items in Zipf(s) shares, by largest remainder."""
    w = 1.0 / np.arange(1, k + 1) ** s
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    for i in np.argsort(-(exact - counts))[: n - counts.sum()]:
        counts[i] += 1
    return counts


def _tokens(rng, vocab: int, n: int) -> np.ndarray:
    return rng.integers(0, vocab, n).astype(np.int32)


# the one order every seed's lengths and gaps come in
ORDER_KEY = 0x0DE5


def open_loop(t: dict, vocab: int, seed: int, seconds: float) -> List[Item]:
    rng = np.random.default_rng([seed, 0x5EED])
    order = np.random.default_rng(ORDER_KEY)
    n = max(1, int(round(t["rate_per_s"] * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = order.permutation(gaps)
    # the n-th gap ends exactly at the window's close
    arrivals = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
        * seconds / gaps.sum()
    outs = order.permutation(quantiles(t["output"], n))
    sp = t.get("shared_prefix")
    if sp:
        systems = [_tokens(rng, vocab, sp["tokens"])
                   for _ in range(sp["prompts"])]
        which = order.permutation(np.repeat(
            np.arange(sp["prompts"]),
            zipf_counts(n, sp["prompts"], sp["zipf_s"])))
        suffix = order.permutation(quantiles(t["suffix"], n))
    else:
        plens = order.permutation(quantiles(t["prompt"], n))
    items = []
    for i in range(n):
        if sp:
            prompt = np.concatenate([systems[which[i]],
                                     _tokens(rng, vocab, suffix[i])])
            prefix = int(which[i])
        else:
            prompt, prefix = _tokens(rng, vocab, plens[i]), None
        items.append(Item(i, prompt, int(outs[i]), float(arrivals[i]),
                          prefix))
    return items


def closed_backlog(t: dict, vocab: int, seed: int,
                   cache_len: int) -> List[Item]:
    rng = np.random.default_rng([seed, 0xB10C])
    order = np.random.default_rng(ORDER_KEY)
    n = t["initial"] + t["queued"]
    plens = order.permutation(quantiles(t["prompt"], n))
    q = order.permutation((np.arange(n) + 0.5) / n)
    items = []
    for i in range(n):
        hi = cache_len - int(plens[i])
        lo = min(t["output_min"], hi)
        items.append(Item(i, _tokens(rng, vocab, plens[i]),
                          int(round(lo + q[i] * (hi - lo))), 0.0))
    return items


def requests(t: dict, vocab: int, seed: int, seconds: float,
             cache_len: int) -> List[Item]:
    if t["kind"] == "open_loop":
        return open_loop(t, vocab, seed, seconds)
    if t["kind"] == "closed_backlog":
        return closed_backlog(t, vocab, seed, cache_len)
    raise ValueError(f"traffic kind {t['kind']!r} sends no requests")
