"""What the program records about itself, read from one run of a cell.

``trace.py`` keeps the harness's ``bench.*`` spans and each device op's
name, and that is all the per-layer readers of ``run.py`` see.  The program
records more: host spans inside the serve engine (``serve.*``), a scope
path on each device op (``jax.named_scope``: ``paged_gather``, ``lm_head``,
``optimizer``), the engine's admission counters, and the compile counter of
``repro.launch.tracing``.  This module reads them:

    python3 benchmarks/chip/program_trace.py --workload <name> \\
        --seed <n> --seconds <s> --trace <0|1>

runs a cell's set-up and window as ``run.py`` does, with no check of
``correct``, and prints one JSON line: the compiles in set-up and in the
window, the cell's end-to-end numbers, and with ``--trace 1`` the readings
below, each step's device time by scope path, and the device's idle time
put down to the innermost host span.  Like ``run.py`` it refuses (exit 2)
without a TPU or on a device kind missing from ``peaks.json``.

* ``queue_wait_p95_ms``: nearest-rank p95 of ``t_sched - arrival`` over
  the requests ``ttft_p95_ms`` counts (one never picked counts at the
  loop's end);
* ``prefill_pad_share``: 100 x (1 - prompt tokens the admission prefill
  computed / token rows it computed), over the window;
* ``paged_gather_ms``: device time of the ops scoped ``paged_gather``
  inside ``serve_step`` modules, per such module, in the traced stretch;
* ``decode_idle_ms``: device-idle time inside ``serve.decode`` spans, per
  such span;
* ``lm_head_ms``: device time of the ops whose scope path holds
  ``lm_head`` (forward and transpose), per train step in the stretch;
  ``optimizer_ms`` the same of ``optimizer``.

A TPU trace names each op by its HLO instruction and carries no scope:
an op's scope path is the ``op_name`` of that instruction in the compiled
text of the module it ran in.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import dataclasses  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from benchmarks.chip import trace  # noqa: E402
from benchmarks.chip.trace import Event  # noqa: E402

PROGRAM_PREFIX = "serve."
OUTSIDE = "outside spans"


def module_key(name: str) -> str:
    """A module event's name without its program id:
    ``jit_serve_step(12)`` -> ``jit_serve_step``."""
    return name.split("(", 1)[0].strip()


def in_scope(path: str, scope: str) -> bool:
    """Whether ``scope`` is one of the scopes of an op's path, as
    ``jax.named_scope`` writes them, under any transform:
    ``jit(step)/transpose(jvp(lm_head))/dot_general`` holds ``lm_head``,
    ``params['lm_head']`` does not."""
    return re.search(r"(^|/|\()" + re.escape(scope) + r"(\)|/|$)",
                     path) is not None


def _op_names(hlo_text: str) -> Dict[str, str]:
    """Instruction name -> ``op_name`` in one compiled module's text."""
    out = {}
    pattern = r'%([\w.\-]+) = [^\n]*?metadata=\{op_name="([^"]*)"'
    for m in re.finditer(pattern, hlo_text):
        out.setdefault(m.group(1), m.group(2))
    return out


def _innermost(spans: List[Event]) -> List[Tuple[float, float, str]]:
    """The timeline cut where any span starts or ends, each piece named
    after the covering span that started last (for nested spans, the
    innermost)."""
    pts = sorted({p for s in spans for p in (s.start_ns, s.end_ns)})
    by_start = sorted(spans, key=lambda s: (s.start_ns, -s.dur_ns))
    out, open_, k = [], [], 0
    for a, b in zip(pts, pts[1:]):
        while k < len(by_start) and by_start[k].start_ns <= a:
            open_.append(by_start[k])
            k += 1
        open_ = [s for s in open_ if s.end_ns > a]
        if open_:
            out.append((a, b, open_[-1].name))
    return out


def _module_of(mods: List[Event], starts: List[float],
               op: Event) -> Optional[Event]:
    """The module (sorted by start) an op ran in, if any."""
    i = bisect.bisect_right(starts, op.start_ns) - 1
    return mods[i] if i >= 0 and op.end_ns <= mods[i].end_ns else None


def scopes_of(red: trace.Reduced, hlo_texts: Dict[str, str]
              ) -> Dict[Tuple[float, str], str]:
    """Each device op's scope path: the ``op_name`` of its instruction in
    the compiled text of the module it ran in (``hlo_texts``, keyed by
    module name)."""
    names = {m: _op_names(t) for m, t in hlo_texts.items()}
    mods = sorted(red.modules, key=lambda m: m.start_ns)
    starts = [m.start_ns for m in mods]
    out = {}
    for o in red.ops:
        mod = _module_of(mods, starts, o)
        path = mod and names.get(module_key(mod.name), {}).get(
            trace.op_name(o))
        if path:
            out[(o.start_ns, trace.op_name(o))] = path
    return out


@dataclasses.dataclass
class Program:
    red: trace.Reduced
    spans: List[Event]              # every host span inside the window
    scopes: Dict[Tuple[float, str], str]   # (start_ns, op name) -> path

    def scope_of(self, op: Event) -> str:
        return self.scopes.get((op.start_ns, trace.op_name(op)), "")

    def ops_in(self, module: Optional[str]) -> List[Event]:
        """The device ops inside modules whose name holds ``module`` (all
        ops for None)."""
        if module is None:
            return self.red.ops
        mods = sorted((m for m in self.red.modules if module in m.name),
                      key=lambda m: m.start_ns)
        starts = [m.start_ns for m in mods]
        return [o for o in self.red.ops if _module_of(mods, starts, o)]

    def scope_time(self, scope: str,
                   module: Optional[str] = None) -> Tuple[float, int]:
        """(seconds, count) of device ops whose scope path holds
        ``scope``; with ``module``, only ops inside a module whose name
        holds it."""
        hit = [o for o in self.ops_in(module)
               if in_scope(self.scope_of(o), scope)]
        return sum(o.dur_ns for o in hit) * 1e-9, len(hit)

    def time_by_scope(self, module: str, n: int = 12) -> List[list]:
        """Device seconds of the ops inside ``module`` modules by scope
        path (the op's own kind left off), the largest first."""
        tot: Dict[str, float] = {}
        for o in self.ops_in(module):
            path = self.scope_of(o).rsplit("/", 1)[0] or "(no scope)"
            tot[path] = tot.get(path, 0.0) + o.dur_ns * 1e-9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def idle_by_span(self, n: int = 10) -> List[list]:
        """Idle seconds by the innermost host span they fall in, the
        largest first."""
        tot: Dict[str, float] = {}
        pieces = _innermost(self.spans)
        i = 0
        for s, e in self.red.gaps:
            left = e - s
            while i < len(pieces) and pieces[i][1] <= s:
                i += 1
            j = i
            while j < len(pieces) and pieces[j][0] < e:
                a, b, name = pieces[j]
                o = min(e, b) - max(s, a)
                if o > 0:
                    tot[name] = tot.get(name, 0.0) + o * 1e-9
                    left -= o
                j += 1
            if left > 0:
                tot[OUTSIDE] = tot.get(OUTSIDE, 0.0) + left * 1e-9
        return [[k, v] for k, v in sorted(tot.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def named(self, name: str) -> List[Event]:
        return [s for s in self.spans if s.name == name]

    def idle_in(self, name: str) -> float:
        """Idle seconds inside the spans named ``name`` (its children's
        included)."""
        spans = sorted(self.named(name), key=lambda s: s.start_ns)
        starts = [s.start_ns for s in spans]
        tot = 0.0
        for s, e in self.red.gaps:
            i = max(bisect.bisect_right(starts, s) - 1, 0)
            for sp in spans[i:]:
                if sp.start_ns >= e:
                    break
                tot += max(min(e, sp.end_ns) - max(s, sp.start_ns), 0.0)
        return tot * 1e-9


def load(trace_dir: str) -> List[Event]:
    """``trace.load``'s events of the newest trace under ``trace_dir`` and
    the program's host spans, in one pass."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    out = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        dev = trace._is_device(plane.name)
        for line in plane.lines:
            for e in line.events:
                if dev or e.name.startswith((trace.SPAN_PREFIX,
                                             PROGRAM_PREFIX)):
                    out.append(Event(plane.name, line.name, e.name,
                                     float(e.start_ns),
                                     float(e.duration_ns)))
    return out


def reduce(events: List[Event], hlo_texts: Dict[str, str]
           ) -> Optional[Program]:
    """``trace.reduce`` of the events (which passes the program's spans
    over), with the program's host spans inside its window and each op's
    scope path; None where ``trace.reduce`` reads nothing."""
    red = trace.reduce(events)
    if red is None:
        return None
    lo = min(s.start_ns for s in red.spans)
    hi = max(s.end_ns for s in red.spans)
    own = [e for e in events if e.name.startswith(PROGRAM_PREFIX)
           and not trace._is_device(e.plane)
           and e.start_ns >= lo and e.end_ns <= hi]
    return Program(red, red.spans + own, scopes_of(red, hlo_texts))


# -- readings ---------------------------------------------------------------

def queue_wait_p95_ms(reqs, seconds: float, end: float) -> Optional[float]:
    """Over the requests due in the window (arrival before its close)."""
    from benchmarks.chip import stats
    due = [r for r in reqs if r.arrival < seconds]
    if not due:
        return None
    return 1e3 * stats.percentile(
        [(r.t_sched if r.t_sched >= 0 else end) - r.arrival for r in due],
        95)


def prefill_pad_share(eng) -> Optional[float]:
    if not eng.prefill_token_rows:
        return None
    return 100.0 * (1.0 - eng.prefill_tokens_computed
                    / eng.prefill_token_rows)


def paged_gather_ms(prog: Program) -> Optional[float]:
    seconds, n = prog.scope_time("paged_gather", module="serve_step")
    _, steps = prog.red.module_time("serve_step")
    return 1e3 * seconds / steps if n and steps else None


def decode_idle_ms(prog: Program) -> Optional[float]:
    n = len(prog.named("serve.decode"))
    return 1e3 * prog.idle_in("serve.decode") / n if n else None


def scope_ms(prog: Program, scope: str, steps: int) -> Optional[float]:
    seconds, n = prog.scope_time(scope)
    return 1e3 * seconds / steps if n and steps else None


def lm_head_ms(prog: Program, steps: int) -> Optional[float]:
    return scope_ms(prog, "lm_head", steps)


# -- one run ----------------------------------------------------------------

def _hlo_serve(eng) -> Dict[str, str]:
    """The decode step's compiled text (the compile cache holds it), for op
    scopes where the device trace carries none."""
    import jax.numpy as jnp
    args = (eng.params, eng.cache, {"tokens": jnp.asarray(eng.tok[:, None])},
            jnp.asarray(eng.pos), eng.base_key, eng._sids())
    return {"jit_serve_step": eng.serve_step.lower(*args).compile().as_text()}


def _hlo_train(cell) -> Dict[str, str]:
    import jax.numpy as jnp
    args = (cell.params, cell.opt_state, cell.batch_fn(cell.data_key, 0),
            jnp.asarray(0, jnp.int32))
    return {"jit_train_step": cell.step_fn.lower(*args).compile().as_text()}


def _compiles(counter, at_open: Tuple[int, float]) -> dict:
    """Compiles before the window opened and inside it."""
    return {"compiles_setup": at_open[0],
            "compiles_in_window": counter.n - at_open[0],
            "compile_s_in_window": counter.seconds - at_open[1],
            "compiled": dict(counter.programs)}


def run_serve(cfgj, tj, args, trace_dir, counter) -> dict:
    from benchmarks.chip import serving
    c = serving.setup(cfgj, tj, args.seed, args.seconds)
    out = {"setup_s": time.perf_counter() - T_START}
    at_open = (counter.n, counter.seconds)
    rec, origin, _ = serving.run_window(c, args.seconds, trace_dir)
    out.update(_compiles(counter, at_open))
    e2e = serving.end_to_end(c, rec, args.seconds, origin)
    out.update(e2e["values"])
    out.update(e2e["info"])
    eng = c.eng
    out["queue_wait_p95_ms"] = queue_wait_p95_ms(c.reqs, args.seconds,
                                                 rec.end)
    out["prefill_pad_share"] = prefill_pad_share(eng)
    for k in ("prefill_tokens", "prefill_tokens_computed",
              "prefill_token_rows", "admit_blocked_pages",
              "admit_blocked_slots"):
        out[k] = getattr(eng, k)
    if trace_dir is not None:
        prog = reduce(load(trace_dir), _hlo_serve(eng))
        if prog is not None:
            _, steps = prog.red.module_time("serve_step")
            out["paged_gather_ms"] = paged_gather_ms(prog)
            out["decode_idle_ms"] = decode_idle_ms(prog)
            out["idle_gaps"] = prog.idle_by_span(20)
            out["serve_step_ms_by_scope"] = [
                [k, 1e3 * v / max(steps, 1)]
                for k, v in prog.time_by_scope("serve_step")]
            out["window_s"], out["busy_s"] = prog.red.window_s, \
                prog.red.busy_s
    return out


def run_train(cfgj, tj, args, trace_dir, counter) -> dict:
    from benchmarks.chip import training
    c = training.setup(cfgj, tj, args.seed)
    out = {"setup_s": time.perf_counter() - T_START - c.check_s}
    at_open = (counter.n, counter.seconds)
    steps, elapsed, stretch = training.run_window(c, args.seconds,
                                                  trace_dir)
    out.update(_compiles(counter, at_open))
    out["train_tokens_per_s"] = steps * tj["batch"] * tj["seq"] / elapsed
    out["steps"], out["window_s"] = steps, elapsed
    if trace_dir is not None and stretch is not None:
        prog = reduce(load(trace_dir), _hlo_train(c))
        if prog is not None:
            steps = stretch[2]
            out["lm_head_ms"] = lm_head_ms(prog, steps)
            out["optimizer_ms"] = scope_ms(prog, "optimizer", steps)
            out["idle_gaps"] = prog.idle_by_span(20)
            out["train_step_ms_by_scope"] = [
                [k, 1e3 * v / max(steps, 1)]
                for k, v in prog.time_by_scope("train_step")]
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    import jax

    from benchmarks.chip import common, run
    from repro.launch import compile_cache
    from repro.launch.tracing import CompileCounter
    cell = common.cell(args.workload)
    try:
        info = run.device_info(cell)
    except run.Refused as e:
        print(f"program_trace: {e}", file=sys.stderr)
        return 2
    compile_cache.enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfgj = common.config(cell["config"])
    tj = common.traffic(cell["traffic"])
    trace_dir = tempfile.mkdtemp(prefix="program_trace_") if args.trace \
        else None
    fn = run_train if tj["kind"] == "train_batches" else run_serve
    try:
        with CompileCounter() as counter:
            out = fn(cfgj, tj, args, trace_dir, counter)
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps({"run": cell["name"], "seed": args.seed,
                      "trace": args.trace, "device": info["kind"], **out}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
