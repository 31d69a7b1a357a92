"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV per the scaffold contract; rich
records land in benchmarks/results/*.json.  Budgets here are CPU-smoke
sized; pass --full for paper-scale budgets (hours).
"""
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale budgets (hours on 1 CPU)")
    ap.add_argument("--quick", action="store_true",
                    help="CI perf-trajectory leg: the prefill and serve "
                    "benches, writing the root-level BENCH_prefill.json "
                    "and BENCH_serve.json artifacts")
    ap.add_argument("--chaos", action="store_true",
                    help="CI chaos-smoke leg: the serve overload bench "
                    "only (undersized page pool + fault injection); any "
                    "shed, crash, or greedy-token divergence raises")
    ap.add_argument("--spec", action="store_true",
                    help="CI speculative-decode smoke leg: the serve "
                    "spec bench only (off vs n-gram vs draft-model on "
                    "the probed high-acceptance trace); any greedy "
                    "divergence or a tok/s ratio <= 1.5x raises")
    ap.add_argument("--only", default=None,
                    help="comma-separated bench names")
    args = ap.parse_args()
    mult = 8 if args.full else 1

    from benchmarks import (bench_fig1_learning, bench_fig4_continuous,
                            bench_fig8_optimizers, bench_fig9_entropy,
                            bench_fig10_lr_robustness, bench_kernels,
                            bench_llm_train, bench_prefill,
                            bench_replay_ablation, bench_roofline,
                            bench_serve, bench_stability,
                            bench_table1_scores, bench_table2_scaling)

    benches = {
        "kernels": lambda: bench_kernels.run(),
        "serve": lambda: bench_serve.run(),
        "prefill": lambda: bench_prefill.run(),
        "llm_train": lambda: bench_llm_train.run(),
        "fig1": lambda: bench_fig1_learning.run(frames=120_000 * mult),
        "table1": lambda: bench_table1_scores.run(frames=100_000 * mult),
        "table2": lambda: bench_table2_scaling.run(
            max_frames=150_000 * mult),
        "fig8": lambda: bench_fig8_optimizers.run(
            n_trials=6 if not args.full else 18, frames=30_000 * mult),
        "fig9": lambda: bench_fig9_entropy.run(frames=60_000 * mult),
        "fig10": lambda: bench_fig10_lr_robustness.run(
            frames=60_000 * mult),
        "fig4": lambda: bench_fig4_continuous.run(frames=80_000 * mult),
        "replay": lambda: bench_replay_ablation.run(frames=40_000 * mult),
        "stability": lambda: bench_stability.run(frames=40_000 * mult),
        "roofline": lambda: bench_roofline.run(),
        "chaos": lambda: bench_serve.run_chaos(),
        "spec": lambda: bench_serve.run_spec(),
    }
    if args.chaos:
        only = ["chaos"]
    elif args.spec:
        only = ["spec"]
    elif args.quick:
        only = ["prefill", "serve"]
        # one-line invariant status next to the perf rows: the cheap
        # repro-audit families (AST lints + dispatch contracts), so a
        # perf run that rode on a contract violation is visible in the
        # same log (the full suite runs as its own CI job)
        import os
        sys.path.insert(0, os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        from tools.audit import quick_summary
        print(quick_summary(), flush=True)
    else:
        only = args.only.split(",") if args.only else list(benches)
    print("name,us_per_call,derived")
    for name in only:
        t0 = time.time()
        rows = benches[name]()
        wall = time.time() - t0
        for r in rows:
            if "us_per_call" in r:
                print(f"{r['name']},{r['us_per_call']:.1f},{r['derived']}")
        n = len(rows)
        print(f"bench_{name},{1e6 * wall / max(n,1):.0f},rows={n}",
              flush=True)


if __name__ == "__main__":
    main()
