"""Benchmark entry point — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  Set BENCH_FULL=1 for the
paper-scale protocol (100 nodes, 100x50 preemptions).

  table4_*  — hit rate (paper Table 4)
  fig10_*   — per-workload sourcing overhead (paper Fig 10)
  fig9_*    — preemption timeline (paper Fig 9)
  fig8_*    — allocation snapshots (paper Fig 8)
  colocation_* — day-cycle co-location A/B (paper §1/§2.3, Fig 2 headline)
  elastic_*  — two-level request+instance backfill ladder A/B
  roofline_* — §Roofline terms per (arch x shape) from the dry-run
"""
from __future__ import annotations

import argparse
import time


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(
        prog="benchmarks.run",
        description="Run every benchmark; the co-location day cycle "
                    "accepts size/horizon/seed overrides")
    ap.add_argument("--nodes", type=int, default=None,
                    help="co-location cluster size override (forwarded to "
                         "bench_colocation; overridden runs don't rewrite "
                         "the committed BENCH JSON)")
    ap.add_argument("--hours", type=float, default=24.0,
                    help="co-location day-cycle horizon in simulated hours")
    ap.add_argument("--seed", type=int, default=0,
                    help="co-location arrival-stream / placement seed")
    ap.add_argument("--skip-scale", action="store_true",
                    help="skip the co-location O(delta) scale sweep")
    args = ap.parse_args(argv)
    overridden = (args.nodes is not None or args.hours != 24.0
                  or args.seed != 0)

    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    from . import (bench_allocation_snapshot, bench_colocation,
                   bench_elastic, bench_hit_rate, bench_instance_timeline,
                   bench_roofline, bench_scheduler_hillclimb,
                   bench_workload_overhead)

    print("name,us_per_call,derived")
    for mod in (bench_hit_rate, bench_workload_overhead,
                bench_instance_timeline,
                bench_allocation_snapshot, bench_colocation, bench_elastic,
                bench_scheduler_hillclimb, bench_roofline):
        t0 = time.time()
        if mod is bench_colocation:
            mod.run(num_nodes=args.nodes, horizon_hours=args.hours,
                    seed=args.seed, write=not overridden,
                    skip_scale=args.skip_scale)
        else:
            mod.run()
        print(f"# {mod.__name__} done in {time.time() - t0:.1f}s", flush=True)


if __name__ == "__main__":
    main()
