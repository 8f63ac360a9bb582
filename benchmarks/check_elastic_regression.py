"""CI gate for the two-level elastic co-location A/B.

Re-runs the committed ``BENCH_elastic.json`` protocol (same nodes, seed,
horizon, engine) and fails if

* the two-level ladder no longer strictly increases offline goodput over
  the instance-only baseline (``goodput_uplift <= 0``),
* online SLO attainment under the two-level ladder drops below the
  instance-only baseline (the admission guard stopped guarding),
* the two-level run no longer has strictly fewer instance preemptions
  (``preemption_delta >= 0`` — the reserve guard or the ramp-time
  demotion path stopped working),
* the elastic layer stopped being exercised (nothing admitted into
  request slots, or nothing completed there), or
* either mode's deterministic day metrics drift from the committed
  baseline (both runs are seeded end to end and must reproduce
  bit-for-bit on any machine).

Run: ``PYTHONPATH=src python -m benchmarks.check_elastic_regression``
"""
from __future__ import annotations

import json
import math
import sys

from .bench_elastic import BENCH_JSON, MODES, day_config, report_payload

REL_TOL = 1e-6

FLOAT_METRICS = ("scheduled_perf", "offline_goodput", "elastic_goodput",
                 "slo_attainment")
INT_METRICS = ("elastic_admitted", "elastic_ejected", "elastic_completed",
               "elastic_demoted", "preemptions", "requeued",
               "requeue_replanned", "placements", "failures",
               "slo_violations")


def main() -> int:
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    if not BENCH_JSON.exists():
        print(f"FAIL: no committed baseline at {BENCH_JSON}")
        return 1
    base = json.loads(BENCH_JSON.read_text())
    from repro.core.colocation import compare_two_level

    cfg = day_config(num_nodes=int(base["num_nodes"]),
                     horizon_hours=float(base["horizon_hours"]),
                     seed=int(base["seed"]))
    ab = compare_two_level(cfg)
    modes = {name: report_payload(rep) for name, rep in ab["reports"].items()}
    io, tl = (modes[m] for m in MODES)
    failures = 0

    uplift = ab["goodput_uplift"]
    status = "ok" if uplift > 0 else "REGRESSION"
    print(f"offline-goodput uplift two_level vs instance_only: "
          f"{uplift * 100:+.1f}% [{status}]")
    if uplift <= 0:
        failures += 1

    ok = tl["slo_attainment"] >= io["slo_attainment"]
    print(f"online SLO attainment: two_level {tl['slo_attainment']:.4f} vs "
          f"instance_only {io['slo_attainment']:.4f} "
          f"[{'ok' if ok else 'REGRESSION'}]")
    if not ok:
        failures += 1

    delta = ab["preemption_delta"]
    ok = delta < 0
    print(f"instance preemptions: two_level {tl['preemptions']} vs "
          f"instance_only {io['preemptions']} (delta {delta:+d}) "
          f"[{'ok' if ok else 'REGRESSION'}]")
    if not ok:
        failures += 1

    exercised = tl["elastic_admitted"] > 0 and tl["elastic_completed"] > 0
    print(f"elastic layer exercised: admitted={tl['elastic_admitted']} "
          f"completed={tl['elastic_completed']} "
          f"demoted={tl['elastic_demoted']} "
          f"[{'ok' if exercised else 'FAIL'}]")
    if not exercised:
        failures += 1

    for mode in MODES:
        committed = base["modes"][mode]
        for metric in FLOAT_METRICS:
            got, want = modes[mode][metric], committed[metric]
            ok = math.isclose(got, want, rel_tol=REL_TOL)
            print(f"{mode} {metric}: {got:.3f} vs committed {want:.3f} "
                  f"[{'ok' if ok else 'DRIFT'}]")
            if not ok:
                failures += 1
        for metric in INT_METRICS:
            got, want = modes[mode][metric], committed[metric]
            ok = got == want
            print(f"{mode} {metric}: {got} vs committed {want} "
                  f"[{'ok' if ok else 'DRIFT'}]")
            if not ok:
                failures += 1

    if failures:
        print(f"FAIL: {failures} elastic gate(s) tripped")
        return 1
    print("two-level elastic co-location within budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
