"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``.  Set-up (weights
from the seed, the engine or the train step, compilation or the compile
cache, warm-up) is timed as ``setup_s``; then the window runs for
``--seconds``; then the program's state is freed and the plain reference
decides ``correct``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` records a stretch of the window with the profiler and reports
its per-layer metrics.  The last line of standard output is one JSON object;
the numbers that decided ``correct`` are the last lines of standard error.

The run refuses (exit 2, no result) without a TPU, with fewer chips than
the cell asks for, or on a device kind missing from ``peaks.json``.

``--readings 1`` also prints what the limits of ``correct`` were set from:
the control (the reference in int8) and, for training, the reference with
half of the batch left out, each put through the same comparison and
limits, with whether it came out correct.  The benchmark's own runs leave
it off.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class Refused(RuntimeError):
    """The run cannot measure on this machine."""


@dataclasses.dataclass
class Context:
    """What the per-layer readers (``metrics/<name>.py``) read."""
    kind: str                  # "serve" or "train"
    config: dict               # the configuration file
    traffic: dict              # the traffic mix file
    peaks: dict                # this device's row of peaks.json
    trace: object              # trace.Reduced, or None
    calls: list = dataclasses.field(default_factory=list)   # whole window
    stretch: object = None     # serving.Stretch, or train (t0, t1, steps)
    occupancy: list = dataclasses.field(default_factory=list)


def device_info(cell: dict) -> dict:
    """The devices as JAX reports them; raises ``Refused`` when they
    cannot carry the cell."""
    import jax
    from benchmarks.chip import common
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu":
        raise Refused(f"no TPU found (devices: {info}); nothing measured")
    if info["count"] < cell["chips"]:
        raise Refused(f"{cell['name']} needs {cell['chips']} chips, found "
                      f"{info['count']}")
    try:
        common.peaks(info["kind"])
    except common.UnknownDevice as e:
        raise Refused(str(e)) from e
    return info


def memory_peak() -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", 0))


def checks_of(values: dict, limits: dict) -> dict:
    """Each number ``correct`` compares, beside its limit."""
    return {k: {"value": values.get(k), "limit": lim}
            for k, lim in limits.items()}


def passes(checks: dict) -> bool:
    return all(v["value"] is not None and v["limit"] is not None
               and v["value"] <= v["limit"] for v in checks.values())


def _metric_line(entries, values: dict) -> dict:
    out = {}
    for m in entries:
        v = values.get(m["name"])
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run_serve(cfgj, tj, args, trace_dir, t_start):
    from benchmarks.chip import serving
    c = serving.setup(cfgj, tj, args.seed, args.seconds)
    setup_s = time.perf_counter() - t_start
    rec, origin, tracer = serving.run_window(c, args.seconds, trace_dir)
    e2e = serving.end_to_end(c, rec, args.seconds, origin)
    e2e["values"]["setup_s"] = setup_s
    peak = memory_peak()
    occupancy = list(c.eng.occupancy)
    serving.free_engine(c)
    sample = serving.check_sample(c, args.seed)
    t0 = time.perf_counter()
    chk = serving.check(c, sample, control=args.readings) if sample else {}
    chk["check_s"] = time.perf_counter() - t0
    checks = checks_of(chk, cfgj["correct"])
    if args.readings and sample:
        chk["control_correct"] = passes(checks_of(
            {"served_logit_gap": chk["control_logit_gap"],
             "served_logit_gap_mean": chk["control_logit_gap_mean"]},
            cfgj["correct"]))
    ctx = Context("serve", cfgj, tj, None, None, calls=rec.calls,
                  stretch=tracer.stretch if tracer else None,
                  occupancy=occupancy)
    return e2e, checks, chk, peak, ctx


def run_train(cfgj, tj, args, trace_dir, t_start):
    from benchmarks.chip import training
    c = training.setup(cfgj, tj, args.seed)
    setup_s = time.perf_counter() - t_start - c.check_s
    steps, elapsed, stretch = training.run_window(c, args.seconds, trace_dir)
    peak = memory_peak()
    training.free_program(c)
    t0 = time.perf_counter()
    ref = training.reference_run(c)
    chk = training.compare(c.got, ref)
    chk["losses"], chk["reference_losses"] = c.got["losses"], ref["losses"]
    limits = cfgj["correct"]
    if args.readings:
        half = list(range(tj["batch"] // 2))
        for name, kw in (("control", {"quant": True}),
                         ("half_batch", {"rows": half})):
            r = training.compare(training.reference_run(c, **kw), ref)
            r["correct"] = passes(checks_of(r, limits))
            chk[name] = r
    chk["check_s"] = time.perf_counter() - t0 + c.check_s
    checks = checks_of(chk, limits)
    tokens = tj["batch"] * tj["seq"]
    e2e = {"values": {"train_tokens_per_s": steps * tokens / elapsed,
                      "setup_s": setup_s},
           "info": {"steps": steps, "window_s": elapsed,
                    "step_s": elapsed / max(steps, 1)},
           "attempted": steps, "failed": 0}
    ctx = Context("train", cfgj, tj, None, None,
                  stretch=tuple(stretch) if stretch else None)
    return e2e, checks, chk, peak, ctx


def run(args, t_start: float = T_START) -> dict:
    from benchmarks.chip import common, trace
    tbl = common.table()
    cell = common.cell(args.workload, tbl)
    dev = device_info(cell)
    from repro.launch import compile_cache
    import jax
    compile_cache.enable()
    # every program goes to the cache, so only a checkout's first run
    # compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cfgj = common.config(cell["config"])
    tj = common.traffic(cell["traffic"])
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if args.trace \
        else None
    try:
        fn = run_train if tj["kind"] == "train_batches" else run_serve
        e2e, checks, chk, peak, ctx = fn(cfgj, tj, args, trace_dir,
                                         t_start)
        dev["memory_peak_bytes"] = peak
        result = {"correct": passes(checks),
                  "attempted": e2e["attempted"], "failed": e2e["failed"]}
        print(json.dumps({"run": cell["name"], "seed": args.seed,
                          **e2e["info"], "check": chk}), flush=True)
        if args.trace:
            red = trace.reduce(trace.load(trace_dir)) \
                if ctx.stretch is not None else None
            ctx.trace = red
            ctx.peaks = common.peaks(dev["kind"])
            values = {}
            for m in common.metrics_of(cell["name"], "per_layer", tbl):
                values[m["name"]] = common.reader(m["name"]).read(ctx)
            result["metrics"] = _metric_line(
                common.metrics_of(cell["name"], "per_layer", tbl), values)
            if red is not None:
                dev["busy_s"], dev["window_s"] = red.busy_s, red.window_s
                result["breakdown"] = {"device_ops": red.top_ops(10),
                                       "idle_gaps": red.idle_by_span(10)}
        else:
            result["metrics"] = _metric_line(
                common.metrics_of(cell["name"], "end_to_end", tbl),
                e2e["values"])
        result["device"] = dev
        result["checks"] = checks
        return result
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--readings", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args)
    except Refused as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for k, v in result["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
