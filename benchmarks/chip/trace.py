"""From a JAX profiler trace to what the per-layer metrics read.

A run with ``--trace 1`` records one stretch of its window with
``jax.profiler`` and reduces the ``.xplane.pb`` to a flat list of events
(``Event``).  Everything after that works on the list, so the reduction is
tested on a small recorded list (``tests/bench_chip``) without a chip:

* the window is the stretch between the first and the last host span the
  harness put around its calls (``bench.*``);
* busy time is the union of the intervals of operations on the first
  device's op line, clipped to the window; idle is the rest;
* module and op times are summed by name, ops that hold others (a loop)
  left out; idle time is put down to the ``bench.*`` span it falls in.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, Iterable, List, Optional, Tuple

SPAN_PREFIX = "bench."
OP_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def to_json(self) -> list:
        return [self.plane, self.line, self.name, self.start_ns, self.dur_ns]

    @classmethod
    def from_json(cls, row) -> "Event":
        return cls(*row)


def _is_device(plane: str) -> bool:
    return plane.startswith("/device:") and "CPU" not in plane


def load(trace_dir: str) -> List[Event]:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``: every
    device event, and the host spans the harness made."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    out = []
    for plane in data.planes:
        dev = _is_device(plane.name)
        for line in plane.lines:
            for e in line.events:
                if dev or e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def op_name(e: Event) -> str:
    """An op's own name: a TPU trace names an op by its HLO instruction,
    ``%_decode_call.9 = bf16[...] custom-call(...)``; the kernels' calls
    are named after the jitted function that wraps them."""
    return e.name.split(" = ", 1)[0].lstrip("%")


def _leaves(ops: List[Event]) -> List[Event]:
    """Drop ops that hold others on the same line (a loop, a call): their
    time is their children's."""
    ops = sorted(ops, key=lambda e: (e.start_ns, -e.dur_ns))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt.start_ns >= e.end_ns
            or nxt.end_ns > e.end_ns]


def _union(intervals: Iterable[Tuple[float, float]]):
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    device: str
    ops: List[Event]              # on the first device, inside the window
    modules: List[Event]
    spans: List[Event]
    gaps: List[Tuple[float, float]]

    def module_time(self, pattern: str) -> Tuple[float, int]:
        """(seconds, calls) of device modules whose name holds
        ``pattern``."""
        hit = [m for m in self.modules if pattern in m.name]
        return sum(m.dur_ns for m in hit) * 1e-9, len(hit)

    def op_time(self, patterns: Iterable[str]) -> Tuple[float, int]:
        """(seconds, count) of device ops whose own name holds any of
        ``patterns``."""
        pats = tuple(patterns)
        hit = [o for o in self.ops if any(p in op_name(o) for p in pats)]
        return sum(o.dur_ns for o in hit) * 1e-9, len(hit)

    def top_ops(self, n: int = 10) -> List[list]:
        tot: Dict[str, float] = {}
        for o in self.ops:
            tot[op_name(o)] = tot.get(op_name(o), 0.0) + o.dur_ns * 1e-9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> List[list]:
        """Idle seconds by the host span they fall in (the harness's spans
        follow one another on one thread), the largest first."""
        tot: Dict[str, float] = {}
        for s, e in self.gaps:
            left = e - s
            for sp in self.spans:
                o = min(e, sp.end_ns) - max(s, sp.start_ns)
                if o > 0:
                    tot[sp.name] = tot.get(sp.name, 0.0) + o * 1e-9
                    left -= o
            if left > 0:
                tot["outside bench spans"] = \
                    tot.get("outside bench spans", 0.0) + left * 1e-9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]


def reduce(events: List[Event]) -> Optional[Reduced]:
    """None when the trace holds no device operation or no span."""
    spans = [e for e in events if e.name.startswith(SPAN_PREFIX)
             and not _is_device(e.plane)]
    devices = sorted({e.plane for e in events if _is_device(e.plane)})
    if not spans or not devices:
        return None
    lo = min(s.start_ns for s in spans)
    hi = max(s.end_ns for s in spans)
    dev = devices[0]

    def inside(e):
        return e.plane == dev and e.start_ns >= lo and e.end_ns <= hi

    ops = _leaves([e for e in events if e.line in OP_LINES and inside(e)])
    modules = [e for e in events if e.line in MODULE_LINES and inside(e)]
    busy_src = ops or modules
    if not busy_src:
        return None
    merged = _union((e.start_ns, e.end_ns) for e in busy_src)
    busy = sum(e - s for s, e in merged)
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy * 1e-9,
                   device=dev, ops=ops, modules=modules, spans=spans,
                   gaps=gaps)
