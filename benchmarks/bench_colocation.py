"""Co-location day-cycle A/B (paper §1/§2.3, Fig. 2 headline) + scale sweep.

Runs one full simulated day on the Table 3 mix through the event-driven
co-location engine twice — the topology-aware fused ``imp_batched`` engine
vs the topology-unaware ``godel`` baseline, SAME seeded arrival stream —
and writes ``BENCH_colocation.json`` at the repo root:

* ``uplift``            — scheduled-performance-integral uplift of the
  aware engine over the baseline (the paper reports +55% for the
  preemption-scheduled slice; ``preemptor_uplift`` is that slice here);
* per-engine day totals (hit rate, preemption/requeue counts,
  requeue-success rate, offline goodput);
* ``plan_p50_us_per_hour`` / ``compiled_per_hour`` — the per-hour P50 plan
  dispatch latency of each engine plus the `CompileWatch` compile count
  per hour (the CI latency gate skips compile-polluted hours);
* ``scale`` — the O(delta) host-loop sweep: one 24-hour day per size in
  `SIZES` on ``engine="auto"`` (``imp_batched`` below 4096 nodes,
  ``imp_sharded`` above), recording events/sec and wall clock, with the
  pre-O(delta) ``legacy_loop`` run at `PARITY_SIZES` for the bit-exact
  day-metric parity flags and the events/sec ratio baseline.  On a TPU
  each day runs in-process on the real devices; on the CPU each runs in a
  subprocess with an 8-device host platform so the sharded engine gets a
  real mesh.

``benchmarks.check_colocation_regression`` gates CI on this file.

Run: ``PYTHONPATH=src python -m benchmarks.bench_colocation``
(``--nodes/--hours/--seed`` override the A/B protocol — overridden runs
print but do NOT rewrite the committed JSON; ``--skip-scale`` omits the
sweep).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from pathlib import Path

from .common import FULL, emit, on_tpu

BENCH_JSON = Path(__file__).parent.parent / "BENCH_colocation.json"

ENGINES = ("imp_batched", "godel")

# ---- O(delta) event-loop scale sweep -------------------------------------
#: day-cycle sizes for the O(delta) loop on ``engine="auto"``
SIZES = (24, 128, 1024, 10240)
#: non-BENCH_FULL protocol: one small size, short horizon (CI smoke only
#: proves the subprocess path + parity; the committed block is full)
SMALL_SIZES = (24,)
SMALL_HOURS = 6.0
#: sizes where the legacy O(N)-per-event loop ALSO runs a full day — the
#: bit-exact parity check and the events/sec denominator
PARITY_SIZES = (24, 128)
#: the acceptance ratio compares the O(delta) loop at this size...
ODELTA_REF_NODES = 1024
#: ...against the legacy loop at this size (where it still terminates in
#: reasonable wall clock)
LEGACY_REF_NODES = 128
#: committed-run wall-clock budget for the 10240-node day (seconds) —
#: the committed run took ~97 min on a single-core host (the 1M-event
#: stream is host-loop-cheap; the wall is ~450k sharded plan dispatches)
SCALE_BUDGET_S = 7200.0
#: forced host-device count of the CPU child
DEVICES = 8
_CHILD_FLAG = "--scale-child"
_MARK = "COLOCATION_SCALE_JSON:"


def day_config(full: bool = FULL, num_nodes: int | None = None,
               horizon_hours: float = 24.0, seed: int = 0,
               engine: str | None = None, legacy_loop: bool = False):
    from repro.core.colocation import ColocationConfig

    kwargs = {} if engine is None else {"engine": engine}
    return ColocationConfig(
        num_nodes=num_nodes if num_nodes is not None else (41 if full else 24),
        seed=seed, horizon_hours=horizon_hours, warmup=True,
        legacy_loop=legacy_loop, **kwargs)


def report_payload(rep) -> dict:
    return {
        "scheduled_perf": rep.scheduled_perf,
        "preemptor_perf": rep.preemptor_perf,
        "offline_goodput": rep.offline_goodput,
        "hit_rate": rep.hit_rate,
        "hits": rep.hits,
        "preemptions": rep.preemptions,
        "placements": rep.placements,
        "failures": rep.failures,
        "requeued": rep.requeued,
        "requeue_replanned": rep.requeue_replanned,
        "requeue_success_rate": rep.requeue_success_rate,
        "plan_p50_us": rep.plan_p50_us,
        "plan_p50_us_per_hour": [r.plan_p50_us for r in rep.hours],
        # hours whose plan latencies paid cold-jit compile time
        # (`simulator.CompileWatch`); the CI latency gate excludes them
        "compiled_per_hour": [r.compiled_n for r in rep.hours],
    }


# ---------------------------------------------------------------------------
# scale sweep: ONE day cycle per size (a forced 8-device child on the CPU)
# ---------------------------------------------------------------------------

def _scale_day(nodes: int, hours: float, seed: int, legacy: bool):
    from repro.core.colocation import ColocationSim, default_policies

    cfg = day_config(num_nodes=nodes, horizon_hours=hours, seed=seed,
                     engine="auto", legacy_loop=legacy)
    sim = ColocationSim(cfg, policies=default_policies(cfg))
    t0 = time.perf_counter()
    rep = sim.run()
    wall = time.perf_counter() - t0
    return sim, rep, wall


def _day_row(nodes: int, hours: float, seed: int, legacy: bool) -> dict:
    """One sweep day in this process, as its JSON row."""
    import jax

    sim, rep, wall = _scale_day(nodes, hours, seed, legacy)
    dev = jax.devices()[0]
    return json.loads(json.dumps({
        "nodes": nodes,
        "loop": "legacy" if legacy else "odelta",
        "engine": sim.sched.engine,
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "devices": len(jax.devices()),
        "horizon_hours": hours,
        "seed": seed,
        "events": sim.events_processed,
        "wall_s": wall,
        "events_per_sec": sim.events_processed / wall if wall else 0.0,
        # full day metrics only at the (small) parity sizes — the caller
        # compares legacy vs O(delta) dicts whole; both sides go through
        # one json round-trip, so float equality is preserved exactly
        "key_metrics": (rep.key_metrics()
                        if nodes in PARITY_SIZES else None),
    }))


def _child_main(args: argparse.Namespace) -> None:
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    print(_MARK + json.dumps(_day_row(args.nodes, args.hours, args.seed,
                                      args.legacy)))


def _sweep_day(nodes: int, hours: float, seed: int, legacy: bool) -> dict:
    if on_tpu():
        return _day_row(nodes, hours, seed, legacy)
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={DEVICES}"
                        ).strip()
    env["PYTHONPATH"] = "src" + (os.pathsep + env["PYTHONPATH"]
                                 if env.get("PYTHONPATH") else "")
    cmd = [sys.executable, "-m", "benchmarks.bench_colocation", _CHILD_FLAG,
           "--nodes", str(nodes), "--hours", str(hours), "--seed", str(seed)]
    if legacy:
        cmd.append("--legacy")
    proc = subprocess.run(cmd, cwd=BENCH_JSON.parent, env=env,
                          capture_output=True, text=True,
                          timeout=SCALE_BUDGET_S * 1.5)
    if proc.returncode != 0:
        raise RuntimeError(f"scale child failed ({proc.returncode}) at "
                           f"n={nodes} legacy={legacy}:\n"
                           f"{proc.stderr[-4000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            return json.loads(line[len(_MARK):])
    raise RuntimeError(f"no scale result in child output:\n"
                       f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")


def scale_sweep(full: bool = FULL, hours: float | None = None,
                seed: int = 0) -> dict:
    """One day per size on the O(delta) loop (+ legacy at `PARITY_SIZES`);
    returns the ``scale`` block for ``BENCH_colocation.json``."""
    sizes = SIZES if full else SMALL_SIZES
    if hours is None:
        hours = 24.0 if full else SMALL_HOURS
    rows: list[dict] = []
    parity: dict[str, bool] = {}
    km: dict[tuple[int, str], dict | None] = {}
    for n in sizes:
        for legacy in ((False, True) if n in PARITY_SIZES else (False,)):
            row = _sweep_day(n, hours, seed, legacy)
            km[(n, row["loop"])] = row.pop("key_metrics")
            rows.append(row)
            emit(f"colocation_scale_{n}_{row['loop']}", 0.0,
                 f"engine={row['engine']} events={row['events']} "
                 f"wall={row['wall_s']:.1f}s "
                 f"ev/s={row['events_per_sec']:.0f}")
    for n in sizes:
        if n in PARITY_SIZES:
            parity[str(n)] = km[(n, "odelta")] == km[(n, "legacy")]
            emit(f"colocation_scale_{n}_parity", 0.0,
                 "bit-exact" if parity[str(n)] else "DIVERGED")

    def _evps(nodes: int, loop: str) -> float:
        for row in rows:
            if row["nodes"] == nodes and row["loop"] == loop:
                return row["events_per_sec"]
        return 0.0

    od_ref_n = ODELTA_REF_NODES if full else sizes[-1]
    lg_ref_n = LEGACY_REF_NODES if full else sizes[-1]
    legacy_ref = _evps(lg_ref_n, "legacy")
    odelta_ref = _evps(od_ref_n, "odelta")
    ratio = odelta_ref / legacy_ref if legacy_ref else 0.0
    emit("colocation_scale_evps_ratio", 0.0,
         f"odelta@{od_ref_n}/legacy@{lg_ref_n}={ratio:.1f}x")
    return {
        "protocol": "full" if full else "small",
        "engine": "auto",
        "devices": rows[0]["devices"],
        "horizon_hours": hours,
        "seed": seed,
        "sizes": list(sizes),
        "parity_sizes": [n for n in sizes if n in PARITY_SIZES],
        "rows": rows,
        "parity": parity,
        "evps_ratio": ratio,
        "evps_ratio_nodes": [od_ref_n, lg_ref_n],
        "budget_s": SCALE_BUDGET_S,
    }


# ---------------------------------------------------------------------------
# the A/B + sweep driver
# ---------------------------------------------------------------------------

def run(full: bool = FULL, write: bool = True,
        num_nodes: int | None = None, horizon_hours: float = 24.0,
        seed: int = 0, skip_scale: bool = False) -> dict:
    from repro.core.colocation import compare_day_cycle

    cfg = day_config(full, num_nodes=num_nodes,
                     horizon_hours=horizon_hours, seed=seed)
    ab = compare_day_cycle(cfg, engines=ENGINES)
    payload = {
        "num_nodes": cfg.num_nodes,
        "seed": cfg.seed,
        "horizon_hours": cfg.horizon_hours,
        "uplift": ab["uplift"],
        "preemptor_uplift": ab["preemptor_uplift"],
        "goodput_uplift": ab["goodput_uplift"],
        "engines": {name: report_payload(rep)
                    for name, rep in ab["reports"].items()},
    }
    if not skip_scale:
        payload["scale"] = scale_sweep(full)
    if write:
        doc = json.loads(BENCH_JSON.read_text()) if BENCH_JSON.exists() else {}
        if skip_scale and "scale" in doc:
            payload["scale"] = doc["scale"]   # keep the committed sweep
        BENCH_JSON.write_text(json.dumps(payload, indent=2) + "\n")
    aware, base = (payload["engines"][e] for e in ENGINES)
    emit("colocation_uplift", 0.0,
         f"scheduled_perf +{payload['uplift'] * 100:.1f}% "
         f"preemptor +{payload['preemptor_uplift'] * 100:.1f}%")
    emit("colocation_aware", aware["plan_p50_us"],
         f"perf={aware['scheduled_perf']:.0f} hit={aware['hit_rate']:.2f} "
         f"requeue={aware['requeue_replanned']}/{aware['requeued']}")
    emit("colocation_baseline", base["plan_p50_us"],
         f"perf={base['scheduled_perf']:.0f} hit={base['hit_rate']:.2f} "
         f"requeue={base['requeue_replanned']}/{base['requeued']}")
    return payload


def _parse_args(argv: list[str]) -> argparse.Namespace:
    ap = argparse.ArgumentParser(
        prog="benchmarks.bench_colocation",
        description="Co-location day-cycle A/B + O(delta) scale sweep")
    ap.add_argument("--nodes", type=int, default=None,
                    help="cluster size override (default 24, BENCH_FULL=1: "
                         "41); overridden runs don't rewrite BENCH JSON")
    ap.add_argument("--hours", type=float, default=24.0,
                    help="day-cycle horizon in simulated hours")
    ap.add_argument("--seed", type=int, default=0,
                    help="arrival-stream / placement seed")
    ap.add_argument("--skip-scale", action="store_true",
                    help="skip the O(delta) scale sweep")
    ap.add_argument(_CHILD_FLAG, action="store_true",
                    help=argparse.SUPPRESS)   # internal: one sweep day
    ap.add_argument("--legacy", action="store_true",
                    help=argparse.SUPPRESS)   # child-only: legacy_loop day
    return ap.parse_args(argv)


def main(argv: list[str] | None = None) -> None:
    args = _parse_args(sys.argv[1:] if argv is None else argv)
    if args.scale_child:
        if args.nodes is None:
            raise SystemExit(f"{_CHILD_FLAG} requires --nodes")
        _child_main(args)
        return
    overridden = (args.nodes is not None or args.hours != 24.0
                  or args.seed != 0)
    run(num_nodes=args.nodes, horizon_hours=args.hours, seed=args.seed,
        write=not overridden, skip_scale=args.skip_scale)


if __name__ == "__main__":
    main()
