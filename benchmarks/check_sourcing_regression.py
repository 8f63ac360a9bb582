"""CI smoke gate for the fused sourcing fast path.

Re-runs the small-protocol Table 5 latency experiment for the fused
``imp_batched`` engine (plus ``imp_batched_legacy`` for the hit-rate
identity check) and fails if

* the fused sourcing P50 — or the filtering-inclusive end-to-end ``plan()``
  P50, the normal-cycle ``plan_normal_e2e`` P50 (the chained
  normal+placement dispatch), or the persistent-session ``plan_batch8``
  P50 — regresses more than ``MAX_REGRESSION``x over the committed
  ``BENCH_sourcing.json`` baseline, or
* the fused hit rate diverges from the legacy engine at the same seed
  (the fused on-device Filtering + Eq. 2 selection must be
  decision-identical), or
* the committed ``scale`` block (written by ``benchmarks.
  bench_scale_sourcing``: plan P50s at 24..10k nodes) is missing, shows
  SUPER-``SUBLINEAR_FRACTION``-linear ``imp_sharded`` plan-P50 growth from
  the smallest to the largest size, or records an ``imp_sharded``-vs-``imp_batched``
  decision divergence at any size — plus a LIVE parity re-check at the two
  smallest sizes (single-process, degenerate one-device mesh: the sharded
  evaluators must stay bit-identical without the 8-device subprocess), or
* the committed scale block violates the shortlist front-end contract:
  any row timed while a jit bucket was still compiling
  (``compiled_n > 0`` — warmup was incomplete, the numbers are invalid),
  any ``shortlist_parity`` flag false (guaranteed mode must be
  bit-identical to the full sweep), the shortlisted ``plan_e2e`` P50 not
  beating its ``*_full`` full-sweep twin at the ``SHORTLIST_GATE_SIZES``
  (modulo the documented ``SHORTLIST_SPEEDUP_CAPS`` exception),
  or the shortlisted ``imp_sharded`` plan P50 at the largest size above
  the ``SHORTLIST_ABS_CAP_US`` absolute budget.

Baseline rows tagged ``"interpret": true`` (Mosaic-interpreter Pallas runs
on CPU) are placeholders, not wall-clock measurements — the gate skips
them.  CI machines are noisy, so the threshold is deliberately loose (2x):
the gate catches structural regressions (a lost jit cache, an accidental
per-k dispatch loop, a host re-upload of the resident state), not
scheduler jitter.

Run: ``PYTHONPATH=src python -m benchmarks.check_sourcing_regression``
"""
from __future__ import annotations

import json
import sys

from repro.core.simulator import (SimConfig, run_latency_experiment,
                                  run_plan_batch_latency,
                                  run_plan_latency_experiment,
                                  run_plan_normal_latency)

from .bench_sourcing_latency import BENCH_JSON
from .common import p

MAX_REGRESSION = 2.0

#: sub-linearity gate for the scale sweep: P50 growth from the smallest to
#: the largest committed size must stay under this fraction of the node
#: -count growth (0.5 = per-node cost at 10k nodes is at most HALF the
#: per-node cost at 24 — comfortably met by the measured ~0.1-0.3, loose
#: enough for machine noise)
SUBLINEAR_FRACTION = 0.5

#: metrics the sub-linearity gate covers (plan_batch8 is recorded in the
#: block but not growth-gated: per-request amortization already makes it
#: the cheapest path and its small per-size round counts are noisier)
SCALE_GATED_METRICS = ("plan_e2e", "plan_normal_e2e")

#: engines the sub-linearity gate covers.  The scaling claim is about the
#: mesh-sharded engine; ``imp_batched`` rows stay in the block as the
#: single-device reference (and are parity-gated at every size) but its
#: growth is printed without gating — its 24-node P50 is noise-dominated
#: (a few samples of ~1ms against a multi-second jit tail) and sits right
#: on the cap, which would make CI a coin flip.
SCALE_GATED_ENGINES = ("imp_sharded",)

#: sizes where the shortlisted plan_e2e P50 must beat the full sweep's
#: (below the default K=128 the prescreen is inactive, so only the two
#: largest committed sizes carry the speedup claim)
SHORTLIST_GATE_SIZES = (1024, 10240)

#: per-(size, engine) cap on shortlisted/full P50.  Strictly < 1.0
#: everywhere the sweep dominates; the one exception is ``imp_sharded``
#: at 1024 nodes, where BOTH paths are dispatch-overhead-dominated on
#: the CPU host-platform mesh (~24ms fixed multi-device dispatch vs a
#: ~4ms single-device sweep) so the prescreen has nothing to cut —
#: there the gate is non-inferiority (<= 1.15x, i.e. the front-end must
#: not cost anything real).  ``engine="auto"`` routes 1024-node
#: clusters to ``imp_batched`` anyway; the sharded speedup claim lives
#: at 10240 where it is gated strictly.
SHORTLIST_SPEEDUP_CAPS = {(1024, "imp_sharded"): 1.15}

#: absolute plan-P50 budget for the shortlisted ``imp_sharded`` engine at
#: the largest committed size — 0.5x the 190ms full-sweep P50 the previous
#: baseline committed at 10240 nodes
SHORTLIST_ABS_CAP_US = 95_000.0


def check_scale(baseline: dict) -> int:
    """Gate the committed scale block + live small-size sharded parity."""
    scale = baseline.get("scale")
    if not scale:
        print("FAIL: no scale block in BENCH_sourcing.json "
              "(run benchmarks.bench_scale_sourcing)")
        return 1
    failures = 0
    rows = {(r["nodes"], r["engine"], r["metric"]): r for r in scale["rows"]}
    sizes = sorted(scale["sizes"])
    n_min, n_max = sizes[0], sizes[-1]
    node_ratio = n_max / n_min
    for engine in ("imp_batched", "imp_sharded"):
        gated = engine in SCALE_GATED_ENGINES
        for metric in SCALE_GATED_METRICS:
            lo = rows.get((n_min, engine, metric))
            hi = rows.get((n_max, engine, metric))
            if not lo or not hi or not lo["p50_us"]:
                print(f"FAIL scale: missing {engine}/{metric} rows")
                failures += 1
                continue
            growth = hi["p50_us"] / lo["p50_us"]
            cap = SUBLINEAR_FRACTION * node_ratio
            if not gated:
                status = "reference, ungated"
            elif growth <= cap:
                status = "ok"
            else:
                status = "REGRESSION"
            print(f"scale {engine}/{metric}: p50 {lo['p50_us']:.0f}us@{n_min}"
                  f" -> {hi['p50_us']:.0f}us@{n_max} = {growth:.1f}x growth "
                  f"(cap {cap:.0f}x, nodes grew {node_ratio:.0f}x) [{status}]")
            if gated and growth > cap:
                failures += 1
    for size in scale["sizes"]:
        if not scale["parity"].get(str(size)):
            print(f"FAIL scale: imp_sharded decisions diverged from "
                  f"imp_batched at {size} nodes in the committed block")
            failures += 1
    # benchmark hygiene: a timed sample that paid a compile is not a
    # latency measurement — refuse the whole committed row
    for r in scale["rows"]:
        if r.get("compiled_n", 0) > 0:
            print(f"FAIL scale: row {r['nodes']}/{r['engine']}/{r['metric']} "
                  f"timed {r['compiled_n']} compiling sample(s) — rerun "
                  f"bench_scale_sourcing with full warmup before committing")
            failures += 1
    # shortlist contract: guaranteed mode is bit-identical to the sweep...
    slp = scale.get("shortlist_parity") or {}
    if not slp:
        print("FAIL scale: no shortlist_parity flags in the committed "
              "block (rerun benchmarks.bench_scale_sourcing)")
        failures += 1
    for key, ok in sorted(slp.items()):
        if not ok:
            print(f"FAIL scale: shortlisted decisions diverged from the "
                  f"full sweep at {key}")
            failures += 1
    # ...and the prescreen must actually pay for itself where it is active
    for size in SHORTLIST_GATE_SIZES:
        for engine in ("imp_batched", "imp_sharded"):
            sl = rows.get((size, engine, "plan_e2e"))
            fw = rows.get((size, engine + "_full", "plan_e2e"))
            if not sl or not fw or not sl["p50_us"] or not fw["p50_us"]:
                print(f"FAIL scale: missing shortlist/full plan_e2e rows "
                      f"for {engine} at {size} nodes")
                failures += 1
                continue
            speedup = fw["p50_us"] / sl["p50_us"]
            cap = SHORTLIST_SPEEDUP_CAPS.get((size, engine), 1.0)
            ok = sl["p50_us"] < fw["p50_us"] * cap
            kind = "beats sweep" if cap == 1.0 else f"non-inferior({cap}x)"
            print(f"scale shortlist {engine}@{size}: p50 "
                  f"{sl['p50_us']:.0f}us vs full sweep {fw['p50_us']:.0f}us "
                  f"({speedup:.2f}x, gate: {kind}) "
                  f"[{'ok' if ok else 'REGRESSION'}]")
            if not ok:
                failures += 1
    cap_row = rows.get((max(scale["sizes"]), "imp_sharded", "plan_e2e"))
    if not cap_row or not cap_row["p50_us"]:
        print("FAIL scale: missing shortlisted imp_sharded plan_e2e row "
              "at the largest size")
        failures += 1
    elif cap_row["p50_us"] > SHORTLIST_ABS_CAP_US:
        print(f"FAIL scale: shortlisted imp_sharded plan_e2e p50 "
              f"{cap_row['p50_us']:.0f}us at {max(scale['sizes'])} nodes "
              f"exceeds the {SHORTLIST_ABS_CAP_US:.0f}us budget")
        failures += 1
    else:
        print(f"scale shortlist abs cap: imp_sharded plan_e2e p50 "
              f"{cap_row['p50_us']:.0f}us @ {max(scale['sizes'])} nodes "
              f"<= {SHORTLIST_ABS_CAP_US:.0f}us [ok]")
    # live parity: rerun the decision sequence at the two smallest sizes
    from repro.core import TopoScheduler, table3_workloads

    from .bench_scale_sourcing import _parity_sequence, build_scaled_cluster

    wl = {w.name: w for w in table3_workloads()}
    for n in sizes[:2]:
        keys = {}
        for engine in ("imp_batched", "imp_sharded"):
            sched = TopoScheduler(build_scaled_cluster(n, seed=0),
                                  engine=engine, alpha=0.5)
            keys[engine] = _parity_sequence(sched, wl, batch=8)
        same = keys["imp_batched"] == keys["imp_sharded"]
        print(f"scale live parity @{n} nodes: "
              f"{'identical' if same else 'DIVERGED'}")
        if not same:
            failures += 1
    return failures


def main() -> int:
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    if not BENCH_JSON.exists():
        print(f"FAIL: no committed baseline at {BENCH_JSON}")
        return 1
    baseline = json.loads(BENCH_JSON.read_text())
    base_rows = {(r["workload"], r["engine"], r.get("metric", "sourcing")): r
                 for r in baseline["rows"]}
    skipped = [k for k, r in base_rows.items() if r.get("interpret")]
    for k in skipped:
        print(f"SKIP {k}: interpret-mode placeholder, not gated")
    cfg = SimConfig(num_nodes=int(baseline.get("num_nodes", 50)),
                    seed=int(baseline.get("seed", 0)))
    samples = int(baseline.get("samples", 20))
    failures = 0
    for wl, label in (("B", "high-p-1000-4-card"), ("C", "low-p-500-2-card")):
        ref = base_rows.get((label, "imp_batched", "sourcing"))
        ref_e2e = base_rows.get((label, "imp_batched", "plan_e2e"))
        ref_legacy = base_rows.get((label, "imp_batched_legacy", "sourcing"))
        if ref is None or not ref["p50_us"] or ref.get("interpret"):
            print(f"SKIP {label}: no gateable fused baseline row")
            continue
        fused = run_latency_experiment(cfg, "imp_batched", wl, samples=samples)
        legacy = run_latency_experiment(cfg, "imp_batched_legacy", wl,
                                        samples=samples)
        p50 = p(fused.sourcing_us, 50)
        # normalize away machine speed: when the legacy engine runs slower
        # on THIS machine than in the committed run, relax the baseline by
        # the same factor (clamped to >= 1 so noise never tightens the gate)
        norm = 1.0
        if ref_legacy and ref_legacy["p50_us"]:
            norm = max(1.0, p(legacy.sourcing_us, 50) / ref_legacy["p50_us"])
        ratio = p50 / (ref["p50_us"] * norm)
        status = "ok" if ratio <= MAX_REGRESSION else "REGRESSION"
        print(f"{label}: fused p50 {p50:.0f}us vs baseline "
              f"{ref['p50_us']:.0f}us (machine norm {norm:.2f}, "
              f"{ratio:.2f}x) [{status}]")
        if ratio > MAX_REGRESSION:
            failures += 1
        if ref_e2e and ref_e2e["p50_us"]:
            e2e = run_plan_latency_experiment(cfg, "imp_batched", wl,
                                              samples=samples)
            e2e_p50 = p(e2e.sourcing_us, 50)
            ratio = e2e_p50 / (ref_e2e["p50_us"] * norm)
            status = "ok" if ratio <= MAX_REGRESSION else "REGRESSION"
            print(f"{label}: fused plan_e2e p50 {e2e_p50:.0f}us vs baseline "
                  f"{ref_e2e['p50_us']:.0f}us ({ratio:.2f}x) [{status}]")
            if ratio > MAX_REGRESSION:
                failures += 1
        ref_normal = base_rows.get((label, "imp_batched", "plan_normal_e2e"))
        if ref_normal and ref_normal["p50_us"]:
            rep = run_plan_normal_latency(cfg, "imp_batched", wl,
                                          samples=samples)
            n_p50 = p(rep.sourcing_us, 50)
            ratio = n_p50 / (ref_normal["p50_us"] * norm)
            status = "ok" if ratio <= MAX_REGRESSION else "REGRESSION"
            print(f"{label}: fused plan_normal_e2e p50 {n_p50:.0f}us vs "
                  f"baseline {ref_normal['p50_us']:.0f}us "
                  f"({ratio:.2f}x) [{status}]")
            if ratio > MAX_REGRESSION:
                failures += 1
        ref_batch = base_rows.get((label, "imp_batched", "plan_batch8"))
        if ref_batch and ref_batch["p50_us"]:
            rep = run_plan_batch_latency(cfg, "imp_batched", wl, batch=8)
            b_p50 = p(rep.sourcing_us, 50)
            ratio = b_p50 / (ref_batch["p50_us"] * norm)
            status = "ok" if ratio <= MAX_REGRESSION else "REGRESSION"
            print(f"{label}: persistent plan_batch8 p50 {b_p50:.0f}us vs "
                  f"baseline {ref_batch['p50_us']:.0f}us "
                  f"({ratio:.2f}x) [{status}]")
            if ratio > MAX_REGRESSION:
                failures += 1
        if (fused.preemptions, fused.hits) != (legacy.preemptions, legacy.hits):
            print(f"FAIL {label}: fused hits {fused.hits}/{fused.preemptions} "
                  f"!= legacy {legacy.hits}/{legacy.preemptions}")
            failures += 1
        else:
            print(f"{label}: hit-rate identical to legacy "
                  f"({fused.hits}/{fused.preemptions})")
    failures += check_scale(baseline)
    if failures:
        print(f"FAIL: {failures} sourcing-latency gate(s) tripped")
        return 1
    print("sourcing fast path within budget")
    return 0


if __name__ == "__main__":
    sys.exit(main())
