"""Smoke test of the scheduler's main path on a TPU.

Run from the repository root on a machine with a TPU:

    python chip_smoke.py             # one chip: phases 1-5
    python chip_smoke.py --chips 4   # four chips: the imp_sharded phase only

Phases, each printing what it found:

1. device: the backend is a TPU and no Pallas kernel would be interpreted;
2. plan path at 10,240 nodes: a fixed sequence of preemptive plans,
   normal-cycle plans, commits, a rollback of two and one ``plan_batch``
   of 8 through ``TopoScheduler(engine="auto", warmup=True)``
   (``imp_sharded`` on a one-device mesh), ``imp_batched`` and the
   ``imp_batched_full`` oracle, whose decisions must be identical;
3. the plain reference: the same sequence at 1,024 nodes on host ``imp``
   against ``imp_batched``, and the 24-node day on both engines;
4. the co-location day loop at 1,024 nodes on ``engine="auto"``;
5. ``imp_pallas`` against ``imp`` with the Mosaic kernels compiled.

With ``--chips 4`` only the four-chip phase runs: ``imp_sharded`` over a
4-device node mesh against ``imp_batched`` on device 0, same sequence.

A decision key is (kind, node, victims, hit, gpu_mask, cg_mask, tier).
Wall times printed here are smoke readings, not metrics.  Any failed check
raises; the last line of stdout, printed only when every phase passed, is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import importlib.metadata
import json
import sys
import time
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: the resident state size of the plan-path phases
NODES = 10240
#: size of the host-reference comparison and of the deployment-size day
REF_NODES = 1024
DAY_NODES = 24
DEPLOY_DAY_HOURS = 3.0
PALLAS_NODES = 128
TIMED_PLANS = 5


class SmokeFailure(AssertionError):
    """A phase found a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def decision_key(dec) -> tuple:
    p = dec.placement
    return (dec.kind, dec.node, tuple(dec.victims), dec.hit,
            None if p is None else (p.gpu_mask, p.cg_mask, p.tier))


def run_sequence(sched_sat, sched_fill, wl) -> list[tuple]:
    """Preemptive plans + commits, a rollback of two, a replan, one
    ``plan_batch`` of 8 (committed in order) on the saturated cluster, then
    normal-cycle plans on the partly filled one.  Returns decision keys."""
    keys = []
    committed = []
    for name in ("A", "B", "C", "B"):
        txn = sched_sat.plan(wl[name])
        keys.append(decision_key(txn.decision))
        txn.commit()
        committed.append(txn)
    for txn in reversed(committed[-2:]):
        txn.rollback()
    txn = sched_sat.plan(wl["C"])
    keys.append(decision_key(txn.decision))
    txn.commit()
    batch = sched_sat.plan_batch([wl["B"], wl["C"]] * 4)
    keys.extend(decision_key(t.decision) for t in batch)
    for t in batch:
        t.commit()
    for name in ("A", "B", "C", "D"):
        txn = sched_fill.plan(wl[name])
        keys.append(decision_key(txn.decision))
        txn.commit()
    txn = sched_fill.plan(wl["C"], allow_preempt=False)
    keys.append(decision_key(txn.decision))
    txn.commit()
    return keys


def _kinds(keys) -> dict[str, int]:
    return dict(Counter(k[0] for k in keys))


def _schedulers(engine: str, nodes: int, **kw):
    from repro.core import TopoScheduler
    from repro.core.simulator import build_scaled_cluster

    sat = TopoScheduler(build_scaled_cluster(nodes, seed=0), engine=engine,
                        **kw)
    fill = TopoScheduler(build_scaled_cluster(nodes, seed=0, fill=0.6),
                         engine=engine, **kw)
    return sat, fill


def _device_state(sched):
    return sched.cluster.device_state(sharded=sched.engine == "imp_sharded")


def _timed_plans(sched, wl) -> tuple[list[float], int]:
    """Warm ``plan()`` wall times in µs (pure reads; each plan reads its
    int32 winner back from the device, so it returns after the device
    finished) and the compiles that landed among them."""
    from repro.core.simulator import CompileWatch

    watch = CompileWatch.get()
    for _ in range(2):
        sched.plan(wl["B"])
    mark = watch.mark()
    times = []
    for _ in range(TIMED_PLANS):
        t0 = time.perf_counter()
        sched.plan(wl["B"])
        times.append((time.perf_counter() - t0) * 1e6)
    return times, watch.delta(mark)


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_device(chips: int) -> dict:
    import jax
    import jaxlib

    from repro.kernels.topo_score import _interpret_default

    devices = jax.devices()
    d0 = devices[0]
    try:
        libtpu = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        libtpu = "not installed"
    print(f"phase 1 device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devices)} jax={jax.__version__} "
          f"jaxlib={jaxlib.__version__} libtpu={libtpu}", flush=True)
    check(d0.platform == "tpu", f"no TPU: JAX found {d0.platform}")
    check(len(devices) >= chips,
          f"{chips} chips asked for, JAX found {len(devices)}")
    check(not _interpret_default(),
          "Pallas kernels would run in interpret mode on this backend")
    print("phase 1 device: ok (Pallas kernels compile for the TPU)",
          flush=True)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def phase_plan_path(nodes: int = NODES) -> None:
    from repro.core import AUTO_ENGINE_THRESHOLD, table3_workloads

    wl = {w.name: w for w in table3_workloads()}
    keys, scheds = {}, {}
    for engine, kw in (("auto", {"warmup": True}), ("imp_batched", {}),
                       ("imp_batched_full", {})):
        t0 = time.perf_counter()
        sat, fill = _schedulers(engine, nodes, **kw)
        setup = time.perf_counter() - t0
        t0 = time.perf_counter()
        keys[engine] = run_sequence(sat, fill, wl)
        seq = time.perf_counter() - t0
        scheds[engine] = (sat, fill)
        fb = _device_state(sat).fallbacks + _device_state(fill).fallbacks
        print(f"phase 2 plan path @{nodes}: engine={engine} "
              f"resolved={sat.engine} kinds={_kinds(keys[engine])} "
              f"host_python_rows={fb['host_rows']} "
              f"shortlist_full_sweeps={fb['shortlist_full_sweep']} "
              f"setup_s={setup:.1f} sequence_s={seq:.1f}", flush=True)
    want = "imp_sharded" if nodes >= AUTO_ENGINE_THRESHOLD else "imp_batched"
    check(scheds["auto"][0].engine == want,
          f"engine='auto' resolved to {scheds['auto'][0].engine}")
    kinds = _kinds(keys["imp_batched_full"])
    check(kinds.get("preempted", 0) > 0 and kinds.get("placed", 0) > 0,
          f"sequence exercised too little: {kinds}")
    for engine in ("auto", "imp_batched"):
        diff = [i for i, (a, b) in enumerate(zip(keys[engine],
                                                 keys["imp_batched_full"]))
                if a != b]
        check(not diff and len(keys[engine]) == len(keys["imp_batched_full"]),
              f"{engine} diverges from imp_batched_full at steps {diff}: "
              f"{[(keys[engine][i], keys['imp_batched_full'][i]) for i in diff[:3]]}")
    print(f"phase 2 plan path @{nodes}: ok, {len(keys['auto'])} decisions "
          f"identical across auto ({want}), imp_batched and "
          "imp_batched_full (masks included)", flush=True)
    for engine in ("auto", "imp_batched"):
        times, compiled = _timed_plans(scheds[engine][0], wl)
        print(f"phase 2 smoke reading (not a metric): engine={engine} "
              f"warm plan() wall us={[round(t, 1) for t in times]} "
              f"compiles_during={compiled}", flush=True)


def phase_reference(nodes: int = REF_NODES, day_nodes: int = DAY_NODES
                    ) -> None:
    from repro.core import ColocationConfig, run_day_cycle, table3_workloads

    wl = {w.name: w for w in table3_workloads()}
    keys = {}
    for engine in ("imp", "imp_batched"):
        t0 = time.perf_counter()
        sat, fill = _schedulers(engine, nodes)
        keys[engine] = run_sequence(sat, fill, wl)
        print(f"phase 3 reference @{nodes}: engine={engine} "
              f"kinds={_kinds(keys[engine])} "
              f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    diff = [i for i, (a, b) in enumerate(zip(keys["imp"], keys["imp_batched"]))
            if a != b]
    check(not diff and len(keys["imp"]) == len(keys["imp_batched"]),
          f"imp_batched diverges from host imp at steps {diff}: "
          f"{[(keys['imp'][i], keys['imp_batched'][i]) for i in diff[:3]]}")
    print(f"phase 3 reference @{nodes}: ok, {len(keys['imp'])} decisions "
          "identical to host imp + place() (masks included)", flush=True)
    metrics = {}
    for engine in ("imp", "imp_batched"):
        t0 = time.perf_counter()
        rep = run_day_cycle(ColocationConfig(num_nodes=day_nodes, seed=0,
                                             engine=engine))
        # every deterministic field but the engine's own name
        metrics[engine] = {k: v for k, v in rep.key_metrics().items()
                           if k != "engine"}
        print(f"phase 3 day @{day_nodes}: engine={engine} "
              f"preemptions={rep.preemptions} placements={rep.placements} "
              f"hours={len(rep.hours)} wall_s={time.perf_counter() - t0:.1f}",
              flush=True)
    check(metrics["imp"] == metrics["imp_batched"],
          "24-node day key_metrics differ between imp and imp_batched: "
          + str(sorted(k for k in metrics["imp"]
                       if metrics["imp"][k] != metrics["imp_batched"][k])))
    print(f"phase 3 day @{day_nodes}: ok, key_metrics() identical", flush=True)


def phase_day_loop(nodes: int = REF_NODES,
                   hours: float = DEPLOY_DAY_HOURS) -> None:
    from repro.core import ColocationConfig, ColocationSim
    from repro.core.colocation import default_policies
    from repro.core.simulator import CompileWatch

    watch = CompileWatch.get()
    cfg = ColocationConfig(num_nodes=nodes, seed=0, engine="auto",
                           horizon_hours=hours, warmup=True)
    t0 = time.perf_counter()
    sim = ColocationSim(cfg, policies=default_policies(cfg))
    setup = time.perf_counter() - t0
    hits = watch.cache_hits
    t0 = time.perf_counter()
    rep = sim.run()
    wall = time.perf_counter() - t0
    after_h0 = sum(r.compiled_n for r in rep.hours[1:])
    check(sim.events_processed > 0 and len(rep.hours) >= int(hours),
          f"day loop ran {sim.events_processed} events, "
          f"{len(rep.hours)} hour rows")
    print(f"phase 4 day loop @{nodes}: ok, engine={sim.sched.engine} "
          f"hours={hours} events={sim.events_processed} "
          f"preemptions={rep.preemptions} placements={rep.placements} "
          f"compiles_per_hour={[r.compiled_n for r in rep.hours]} "
          f"compiles_after_hour0={after_h0} "
          f"cache_loads_in_run={watch.cache_hits - hits}", flush=True)
    print(f"phase 4 smoke reading (not a metric): setup_s={setup:.1f} "
          f"wall_s={wall:.1f}", flush=True)


def phase_pallas(nodes: int = PALLAS_NODES, check_mosaic: bool = True
                 ) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core import TopoScheduler, table3_workloads
    from repro.core.simulator import SimConfig, build_saturated_cluster
    from repro.kernels.topo_score import TopoRequest, topo_score_argmax_pallas

    wl = {w.name: w for w in table3_workloads()}
    keys = {}
    for engine in ("imp", "imp_pallas"):
        sched = TopoScheduler(
            build_saturated_cluster(SimConfig(num_nodes=nodes, seed=0)),
            engine=engine)
        seq = []
        for name in ("A", "B", "C", "D", "C"):
            txn = sched.plan(wl[name])
            seq.append(decision_key(txn.decision))
            txn.commit()
        keys[engine] = seq
    check(keys["imp"] == keys["imp_pallas"],
          f"imp_pallas diverges from imp: {keys}")
    check(_kinds(keys["imp"]).get("preempted", 0) > 0,
          f"no preemption exercised: {keys['imp']}")
    if check_mosaic:
        spec = build_saturated_cluster(SimConfig(num_nodes=1, seed=0)).spec
        x = jnp.zeros(1 << 10, jnp.int32)
        text = jax.jit(lambda a: topo_score_argmax_pallas(
            a, a, a, a, spec, TopoRequest(2, 2, 1))).lower(x).as_text()
        check("tpu_custom_call" in text,
              "the lowered imp_pallas kernel holds no Mosaic custom call")
    print(f"phase 5 pallas @{nodes}: ok, {len(keys['imp'])} imp_pallas "
          "decisions identical to imp; kernel lowered to tpu_custom_call",
          flush=True)


def phase_four_chips(nodes: int = NODES) -> None:
    import jax

    from repro.core import table3_workloads

    wl = {w.name: w for w in table3_workloads()}
    keys, scheds = {}, {}
    for engine in ("imp_sharded", "imp_batched"):
        t0 = time.perf_counter()
        sat, fill = _schedulers(engine, nodes)
        keys[engine] = run_sequence(sat, fill, wl)
        scheds[engine] = sat
        print(f"phase 4-chip @{nodes}: engine={engine} "
              f"kinds={_kinds(keys[engine])} "
              f"wall_s={time.perf_counter() - t0:.1f}", flush=True)
    n_dev = len(jax.devices())
    sharded = _device_state(scheds["imp_sharded"])
    for name in ("nodestate", "victims", "drain"):
        arr = getattr(sharded, name)
        shards = sorted((s.device.id, tuple(s.data.shape))
                        for s in arr.addressable_shards)
        print(f"phase 4-chip imp_sharded {name}: shape={tuple(arr.shape)} "
              f"sharding={arr.sharding} shards={shards}", flush=True)
        check(len({d for d, _ in shards}) == n_dev
              and all(shape[1] == arr.shape[1] // n_dev
                      for _, shape in shards),
              f"{name} node axis is not split over {n_dev} devices")
    single = _device_state(scheds["imp_batched"])
    print("phase 4-chip imp_batched nodestate devices="
          f"{sorted(d.id for d in single.nodestate.devices())}", flush=True)
    diff = [i for i, (a, b) in enumerate(zip(keys["imp_sharded"],
                                             keys["imp_batched"])) if a != b]
    check(not diff and len(keys["imp_sharded"]) == len(keys["imp_batched"]),
          f"imp_sharded diverges from imp_batched at steps {diff}")
    print(f"phase 4-chip @{nodes}: ok, {len(keys['imp_sharded'])} decisions "
          f"of imp_sharded on {n_dev} chips identical to imp_batched on "
          "device 0 (masks included)", flush=True)
    for engine in ("imp_sharded", "imp_batched"):
        times, compiled = _timed_plans(scheds[engine], wl)
        print(f"phase 4-chip smoke reading (not a metric): engine={engine} "
              f"warm plan() wall us={[round(t, 1) for t in times]} "
              f"compiles_during={compiled}", flush=True)


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the imp_sharded four-chip phase")
    args = ap.parse_args(argv)

    from repro.jax_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    from repro.core.simulator import CompileWatch

    watch = CompileWatch.get()     # before the first compile
    t_start = time.perf_counter()
    device = phase_device(args.chips)
    print(f"compile cache: {cache_dir}", flush=True)
    if args.chips == 4:
        phase_four_chips()
    else:
        phase_plan_path()
        phase_reference()
        phase_day_loop()
        phase_pallas()
    cache = Path(cache_dir)
    entries = sum(1 for _ in cache.iterdir()) if cache.is_dir() else 0
    print(f"programs built={watch.count} of which loaded from the "
          f"compile cache={watch.cache_hits}; cache entries={entries}; "
          "smoke reading (not a metric): "
          f"total_s={time.perf_counter() - t_start:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
