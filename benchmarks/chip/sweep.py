"""Find the highest rate an open-loop serving mix sustains, on the chip.

    python3 benchmarks/chip/sweep.py --workload stablelm-1.6b.chat \\
        --seconds 51 --rates 0.8,1.0,1.2,1.4 [--seed 1]

One process builds the cell once, then runs the cell's window once per
rate, the mix's file with only ``rate_per_s`` changed, and prints one JSON
line per rate: requests due and unfinished, how long the drain took, TTFT
p50/p95 and the p50 of the first and last third of arrivals (a queue that
grows through the window shows as the last third waiting longer), ITL
p50/p95, the share of gaps that hold an admission, mean occupancy and
tokens per second.  The knee is the highest rate whose queue does not grow
through the window; a cell's ``rate_per_s`` is set below it from these
lines.  The benchmark's runs never call this.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (os.path.join(_ROOT, "src"), _ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# the sweep's drain: long enough to see a queue that does not clear
DRAIN_S = 20.0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    import numpy as np

    from benchmarks.chip import common, generate, serving, stats
    from repro.launch import compile_cache, serve
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import run as run_mod
    cell = common.cell(args.workload)
    run_mod.device_info(cell)
    compile_cache.enable()
    cfgj = common.config(cell["config"])
    base = common.traffic(cell["traffic"])
    rates = [float(r) for r in args.rates.split(",")]
    top = dict(copy.deepcopy(base), rate_per_s=max(rates), drain_s=DRAIN_S)
    c = serving.setup(cfgj, top, args.seed, args.seconds)
    cfg = common.model_config(cfgj)
    for rate in rates:
        tj = dict(copy.deepcopy(base), rate_per_s=rate, drain_s=DRAIN_S)
        items = generate.requests(tj, cfg.vocab_size, args.seed,
                                  args.seconds, cfgj["serving"]["cache_len"])
        c.traffic = tj
        c.reqs = [serve.Request(rid=it.rid, prompt=it.prompt,
                                max_new=it.max_new, arrival=it.arrival)
                  for it in items]
        rec, origin, _ = serving.run_window(c, args.seconds)
        e2e = serving.end_to_end(c, rec, args.seconds, origin)
        due = sorted((r for r in c.reqs if r.arrival < args.seconds),
                     key=lambda r: r.arrival)
        third = max(len(due) // 3, 1)

        def p50_ttft(reqs):
            v = [rec.stamps[r.rid][0] - r.arrival for r in reqs
                 if rec.stamps.get(r.rid)]
            return 1e3 * stats.percentile(v, 50) if v else None

        occ = c.eng.occupancy
        print(json.dumps({
            "rate_per_s": rate, **e2e["values"], **e2e["info"],
            "drain_s": max(rec.end - args.seconds, 0.0),
            "ttft_p50_first_third_ms": p50_ttft(due[:third]),
            "ttft_p50_last_third_ms": p50_ttft(due[-third:]),
            "occupancy": float(np.mean(occ)) if occ else None}),
            flush=True)
        c.eng.reset()
    return 0


if __name__ == "__main__":
    sys.exit(main())
