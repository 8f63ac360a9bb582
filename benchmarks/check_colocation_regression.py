"""CI gate for the co-location day-cycle A/B (next to the sourcing gate).

Re-runs the committed ``BENCH_colocation.json`` protocol (same nodes, seed,
horizon) and fails if

* the topology-aware engine no longer beats the topology-unaware baseline
  on the scheduled-performance integral (``uplift <= 0``),
* the victim requeue lifecycle stopped being exercised (no preempted
  offline job was requeued AND successfully replanned),
* the aware engine's deterministic day metrics drift from the committed
  baseline (the day cycle is seeded end to end: decisions, and therefore
  the integrals, must reproduce bit-for-bit on any machine),
* the per-hour P50 plan latency regresses more than ``MAX_REGRESSION``x
  over the committed run, machine-normed via the baseline engine's host
  sourcing latency (clamped >= 1 so a fast machine never tightens the
  gate).  Hours that paid XLA compile time (``compiled_per_hour`` from
  `simulator.CompileWatch`) are excluded on BOTH sides, so cold-jit noise
  no longer spends gate headroom,
* the committed ``scale`` block (the O(delta) event-loop sweep) is
  missing, ran the small protocol, lost bit-exact parity vs the legacy
  loop at any parity size, fell under ``MIN_EVPS_RATIO``x the legacy
  loop's events/sec, or blew the 10240-node wall-clock budget, or
* a LIVE legacy-vs-O(delta) day (small, host engine, in-process) stops
  being bit-exact — the committed parity flags prove the sweep machine
  saw exactness; this proves THIS checkout still has it.

Run: ``PYTHONPATH=src python -m benchmarks.check_colocation_regression``
"""
from __future__ import annotations

import json
import math
import statistics
import sys

from .bench_colocation import (BENCH_JSON, ENGINES, SCALE_BUDGET_S, SIZES,
                               day_config, report_payload)

MAX_REGRESSION = 2.0
REL_TOL = 1e-6
#: O(delta) events/sec over the legacy loop's (committed scale block)
MIN_EVPS_RATIO = 5.0
#: live legacy-vs-O(delta) parity re-check protocol (host engine: cheap)
LIVE_PARITY = dict(num_nodes=16, horizon_hours=8.0, seed=3, engine="imp")


def _clean_p50(payload: dict) -> float:
    """Median per-hour plan P50 over compile-free hours.

    Falls back to all nonzero hours (the pre-``compiled_per_hour``
    baseline shape), then to the whole-day ``plan_p50_us``."""
    per_hour = payload.get("plan_p50_us_per_hour", [])
    compiled = payload.get("compiled_per_hour") or [0] * len(per_hour)
    vals = [v for v, c in zip(per_hour, compiled) if v > 0 and not c]
    if not vals:
        vals = [v for v in per_hour if v > 0]
    return statistics.median(vals) if vals else payload.get("plan_p50_us",
                                                            0.0)


def _check_scale_block(base: dict) -> int:
    failures = 0
    scale = base.get("scale")
    if not scale:
        print("FAIL: no committed `scale` block in BENCH_colocation.json")
        return 1
    if scale.get("protocol") != "full":
        print(f"scale protocol: {scale.get('protocol')} [FAIL: the "
              f"committed sweep must be the full {list(SIZES)} protocol]")
        failures += 1
    rows = {(r["nodes"], r["loop"]): r for r in scale.get("rows", [])}

    for size in scale.get("parity_sizes", []):
        ok = scale.get("parity", {}).get(str(size), False)
        print(f"scale {size}-node day metrics odelta vs legacy: "
              f"[{'bit-exact' if ok else 'DIVERGED'}]")
        if not ok:
            failures += 1

    ratio = scale.get("evps_ratio", 0.0)
    od_n, lg_n = scale.get("evps_ratio_nodes", (0, 0))
    ok = ratio >= MIN_EVPS_RATIO
    print(f"scale events/sec odelta@{od_n} / legacy@{lg_n}: {ratio:.1f}x "
          f"(floor {MIN_EVPS_RATIO:.0f}x) [{'ok' if ok else 'REGRESSION'}]")
    if not ok:
        failures += 1

    big = rows.get((max(SIZES), "odelta"))
    if big is None:
        print(f"FAIL: no {max(SIZES)}-node odelta row in the scale block")
        failures += 1
    else:
        budget = scale.get("budget_s", SCALE_BUDGET_S)
        ok = big["wall_s"] <= budget
        print(f"scale {max(SIZES)}-node day: {big['wall_s']:.0f}s wall, "
              f"{big['events']} events, {big['events_per_sec']:.0f} ev/s "
              f"(budget {budget:.0f}s) [{'ok' if ok else 'OVER BUDGET'}]")
        if not ok:
            failures += 1
    return failures


def _check_live_parity() -> int:
    import dataclasses

    from repro.core.colocation import run_day_cycle

    cfg = day_config(**LIVE_PARITY)
    new = run_day_cycle(cfg)
    old = run_day_cycle(dataclasses.replace(cfg, legacy_loop=True))
    ok = new.key_metrics() == old.key_metrics()
    print(f"live O(delta) vs legacy loop ({LIVE_PARITY['num_nodes']} nodes, "
          f"{LIVE_PARITY['horizon_hours']:.0f}h, "
          f"engine={LIVE_PARITY['engine']}): "
          f"[{'bit-exact' if ok else 'DIVERGED'}]")
    return 0 if ok else 1


def main() -> int:
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    if not BENCH_JSON.exists():
        print(f"FAIL: no committed baseline at {BENCH_JSON}")
        return 1
    base = json.loads(BENCH_JSON.read_text())
    from repro.core.colocation import compare_day_cycle

    cfg = day_config(num_nodes=int(base["num_nodes"]),
                     horizon_hours=float(base["horizon_hours"]),
                     seed=int(base["seed"]))
    ab = compare_day_cycle(cfg, engines=ENGINES)
    aware_name, baseline_name = ENGINES
    aware = report_payload(ab["reports"][aware_name])
    failures = 0

    uplift = ab["uplift"]
    status = "ok" if uplift > 0 else "REGRESSION"
    print(f"scheduled-performance uplift {aware_name} vs {baseline_name}: "
          f"{uplift * 100:+.1f}% (preemptor slice "
          f"{ab['preemptor_uplift'] * 100:+.1f}%) [{status}]")
    if uplift <= 0:
        failures += 1

    rq, rp = aware["requeued"], aware["requeue_replanned"]
    status = "ok" if (rq > 0 and rp > 0) else "FAIL"
    print(f"requeue lifecycle: {rp}/{rq} victims replanned [{status}]")
    if not (rq > 0 and rp > 0):
        failures += 1

    committed = base["engines"][aware_name]
    for metric in ("scheduled_perf", "preemptor_perf", "offline_goodput"):
        got, want = aware[metric], committed[metric]
        ok = math.isclose(got, want, rel_tol=REL_TOL)
        print(f"{aware_name} {metric}: {got:.3f} vs committed {want:.3f} "
              f"[{'ok' if ok else 'DRIFT'}]")
        if not ok:
            failures += 1
    for metric in ("preemptions", "hits", "requeued", "requeue_replanned",
                   "placements", "failures"):
        got, want = aware[metric], committed[metric]
        ok = got == want
        print(f"{aware_name} {metric}: {got} vs committed {want} "
              f"[{'ok' if ok else 'DRIFT'}]")
        if not ok:
            failures += 1

    # latency: machine-normed via the host baseline engine, on
    # compile-free hours only (both sides of both ratios)
    base_ref = _clean_p50(base["engines"][baseline_name])
    base_now = _clean_p50(report_payload(ab["reports"][baseline_name]))
    ref = _clean_p50(committed)
    now = _clean_p50(aware)
    if ref and base_ref:
        norm = max(1.0, base_now / base_ref)
        ratio = now / (ref * norm)
        status = "ok" if ratio <= MAX_REGRESSION else "REGRESSION"
        print(f"{aware_name} clean plan p50 {now:.0f}us vs "
              f"committed {ref:.0f}us (machine norm {norm:.2f}, "
              f"{ratio:.2f}x) [{status}]")
        if ratio > MAX_REGRESSION:
            failures += 1

    failures += _check_scale_block(base)
    failures += _check_live_parity()

    if failures:
        print(f"FAIL: {failures} colocation gate(s) tripped")
        return 1
    print("co-location day cycle within budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
