"""Benchmark orchestrator: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--budget quick|full] \
        [--only table4,fig7]

quick (default): CI-sized budgets (Eps=600) -- every claim is exercised,
absolute values are noisier.  full: the paper's Eps=5000 (hours on CPU).
Each module writes results/<name>.json for EXPERIMENTS.md.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from benchmarks import common

BENCHES = [
    ("fig5_perlayer", "benchmarks.bench_fig5_perlayer"),
    ("table3_lp", "benchmarks.bench_table3_lp"),
    ("table4_methods", "benchmarks.bench_table4_methods"),
    ("table5_rl", "benchmarks.bench_table5_rl"),
    ("table6_mix", "benchmarks.bench_table6_mix"),
    ("table7_twostage", "benchmarks.bench_table7_twostage"),
    ("table9_policy", "benchmarks.bench_table9_policy"),
    ("fig6_critic", "benchmarks.bench_fig6_critic"),
    ("fig7_convergence", "benchmarks.bench_fig7_convergence"),
    ("relaxed_oneshot", "benchmarks.bench_relaxed_oneshot"),
    ("frontier", "benchmarks.bench_frontier"),
    ("costmodel_throughput", "benchmarks.bench_costmodel_throughput"),
    ("dist_search", "benchmarks.bench_dist_search"),
    ("fanout_backends", "benchmarks.bench_fanout_backends"),
    ("search_service", "benchmarks.bench_search_service"),
    ("obs_overhead", "benchmarks.bench_obs_overhead"),
    ("roofline", "benchmarks.bench_roofline"),
]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--budget", default="quick", choices=["quick", "full"])
    ap.add_argument("--only", default="",
                    help="comma-separated bench name substrings")
    args = ap.parse_args(argv)

    sel = [s for s in args.only.split(",") if s]
    failures = []
    t_all = time.time()
    for name, module in BENCHES:
        if sel and not any(s in name for s in sel):
            continue
        print(f"\n########## {name} (budget={args.budget}) ##########",
              flush=True)
        t0 = time.time()
        try:
            mod = __import__(module, fromlist=["run"])
            payload = mod.run(args.budget)
            payload["_budget"] = args.budget
            payload["_seconds"] = round(time.time() - t0, 1)
            path = common.save_json(name, payload)
            print(f"[{name}] done in {payload['_seconds']}s -> {path}",
                  flush=True)
        except Exception as e:  # noqa: BLE001
            failures.append((name, repr(e)))
            traceback.print_exc()
    print(f"\n===== benchmarks finished in {time.time()-t_all:.0f}s; "
          f"{len(failures)} failures =====")
    for name, err in failures:
        print(f"  FAILED {name}: {err}")
    return 1 if failures else 0


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    sys.exit(main())
