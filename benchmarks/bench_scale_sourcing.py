"""Scale sweep — plan latency from 24 to 10k nodes, sharded vs single-device.

The tentpole claim of the mesh-sharded cluster state: end-to-end ``plan()``
P50 must grow SUB-linearly in node count (per-node cost falls as the cluster
grows — fixed dispatch overhead amortizes and the node axis shards across
the device mesh), and the ``imp_sharded`` engine must stay bit-identical to
``imp_batched`` at every size.

Protocol
--------
On a TPU the sweep runs in-process on the real devices (one process per
chip).  On the CPU the parent re-invokes this module as a subprocess with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so the sharded
engine gets a real 8-device mesh even on a single-CPU host (the flag must
be set before jax initializes, hence the subprocess).  Per size in
``SIZES`` the sweep:

* builds one saturated cluster per engine (sizes above the 128-node base
  are TILED — the base's instance pattern replayed per 128-node block —
  because random saturation does an O(instances x nodes) feasibility scan
  that is prohibitive at 10k nodes, and bind-replay is O(instances));
* runs a deterministic decision sequence (preemptive plans, commits, one
  ``plan_batch``) on BOTH engines and compares decision keys — the
  ``parity`` flag per size — and the same sequence on the ``*_full``
  oracle twins (shortlist front-end off) — the per-engine
  ``shortlist_parity`` flags;
* times ``plan_e2e`` for the full-sweep oracles first (hot jit buckets
  for any guaranteed-mode fallback), then ``plan_e2e`` (alternating B/C
  preemptors, pure reads), ``plan_batch8`` (persistent session,
  per-request, TWO untimed warm rounds), and ``plan_normal_e2e``
  (60%-filled cluster, normal-cycle admission) for the production
  engines, tagging any sample that still compiles (`CompileWatch`).

The production engines run with `TopoScheduler`'s default shortlist
front-end (top-K=128 representatives, guaranteed mode), so sizes above K
measure the two-stage path and the ``*_full`` rows are the all-nodes
sweep reference the CI speedup gate compares against.

The parent merges the result as the ``scale`` block of
``BENCH_sourcing.json``; ``benchmarks.check_sourcing_regression`` gates the
committed block (sub-linear growth, parity at every size, shortlist
parity + speedup vs the full sweep, no compiled timed samples) plus a
live small-size parity re-check.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

from repro.core.simulator import SCALE_BASE_NODES as BASE_NODES
from repro.core.simulator import build_scaled_cluster

from .common import FULL, emit, on_tpu, p

try:  # parent-only import cycle guard: the child imports this module too
    from .bench_sourcing_latency import BENCH_JSON
except ImportError:  # pragma: no cover - running as a script
    import pathlib

    BENCH_JSON = pathlib.Path(__file__).resolve().parent.parent / "BENCH_sourcing.json"

SIZES = (24, 128, 1024, 10240)
#: forced host-device count of the CPU child
DEVICES = 8
ENGINES = ("imp_batched", "imp_sharded")
#: full-sweep oracle twin per production engine (shortlist front-end off)
FULL_ENGINES = {"imp_batched": "imp_batched_full",
                "imp_sharded": "imp_sharded_full"}

#: per-size sample counts: (plan_e2e samples, batch rounds, normal samples)
_SAMPLES_FULL = {24: (20, 10, 20), 128: (20, 10, 20),
                 1024: (12, 6, 12), 10240: (6, 3, 6)}
_SAMPLES_SMALL = {24: (10, 6, 10), 128: (10, 6, 10),
                  1024: (6, 4, 6), 10240: (4, 2, 4)}

_CHILD_FLAG = "--child"
_MARK = "SCALE_RESULT_JSON:"


# ---------------------------------------------------------------------------
# child: runs under the forced 8-device host platform
# ---------------------------------------------------------------------------

def _decision_key(dec):
    """What parity compares: kind, node, victims, hit and the placement's
    concrete masks and tier."""
    return (str(dec.kind), dec.node, tuple(dec.victims), dec.hit,
            None if dec.placement is None else
            (dec.placement.gpu_mask, dec.placement.cg_mask,
             dec.placement.tier))


def _parity_sequence(sched, wl, batch: int):
    """Deterministic mixed plan/commit/batch sequence; returns decision keys.

    Commits mutate the cluster, so the same sequence on two engines'
    clusters exercises the delta-encoder path between plans.
    """
    keys = []
    for name in ("B", "C", "B"):
        txn = sched.plan(wl[name], allow_normal=True)
        keys.append(_decision_key(txn.decision))
        if txn.decision.kind != "reject":
            txn.commit()
    txns = sched.plan_batch([wl["B"]] * batch)
    for i, t in enumerate(txns):
        keys.append(_decision_key(t.decision))
        if i == 0 and t.decision.kind != "reject":
            t.commit()
    return keys


def measure(protocol: str) -> dict:
    """The whole sweep in this process, on whatever devices JAX has."""
    import time

    import jax

    from repro.core import TopoScheduler, table3_workloads
    from repro.core.simulator import CompileWatch

    per_size = _SAMPLES_FULL if protocol == "full" else _SAMPLES_SMALL
    wl = {w.name: w for w in table3_workloads()}
    watch = CompileWatch.get()
    rows: list[dict] = []
    parity: dict[str, bool] = {}
    shortlist_parity: dict[str, bool] = {}
    shortlist_meta: dict = {}

    for n in SIZES:
        samples, rounds, n_samples = per_size[n]
        keys: dict[str, list] = {}
        scheds: dict[str, TopoScheduler] = {}
        batch = 8 if n <= 1024 else 4
        for engine in ENGINES:
            cluster = build_scaled_cluster(n, seed=0)
            sched = TopoScheduler(cluster, engine=engine, alpha=0.5)
            keys[engine] = _parity_sequence(sched, wl, batch)
            scheds[engine] = sched
        parity[str(n)] = keys[ENGINES[0]] == keys[ENGINES[1]]
        sl = scheds[ENGINES[0]].shortlist
        shortlist_meta = {"k": sl.k if sl else 0,
                          "mode": sl.mode if sl else None}

        # full-sweep oracles: same deterministic sequence on fresh clusters
        # must be decision-identical to the shortlisted production engines
        for engine, full in FULL_ENGINES.items():
            cluster = build_scaled_cluster(n, seed=0)
            fsched = TopoScheduler(cluster, engine=full, alpha=0.5)
            shortlist_parity[f"{n}:{engine}"] = (
                keys[engine] == _parity_sequence(fsched, wl, batch))
            scheds[full] = fsched

        # time the oracles FIRST: their jit buckets then sit hot, so a
        # guaranteed-mode certainty fallback inside the production timing
        # loops below re-uses the compiled sweep instead of compiling
        # mid-sample (which the CI gate now refuses)
        for engine in ENGINES:
            fsched = scheds[FULL_ENGINES[engine]]
            for _ in range(2):      # untimed double warm
                fsched.plan(wl["B"])
                fsched.plan(wl["C"])
            times, compiled = [], 0
            for i in range(samples):
                m = watch.mark()
                t0 = time.perf_counter()
                fsched.plan(wl["B"] if i % 2 == 0 else wl["C"])
                times.append((time.perf_counter() - t0) * 1e6)
                compiled += watch.delta(m) > 0
            rows.append({"nodes": n, "engine": FULL_ENGINES[engine],
                         "metric": "plan_e2e",
                         "p50_us": p(times, 50), "p90_us": p(times, 90),
                         "n": samples, "compiled_n": compiled})

        for engine in ENGINES:
            sched = scheds[engine]
            # warm both preemptor programs at this size's buckets (twice:
            # the second round proves steady state before timing starts)
            for _ in range(2):
                sched.plan(wl["B"])
                sched.plan(wl["C"])
            times, compiled = [], 0
            for i in range(samples):
                m = watch.mark()
                t0 = time.perf_counter()
                sched.plan(wl["B"] if i % 2 == 0 else wl["C"])
                times.append((time.perf_counter() - t0) * 1e6)
                compiled += watch.delta(m) > 0
            rows.append({"nodes": n, "engine": engine, "metric": "plan_e2e",
                         "p50_us": p(times, 50), "p90_us": p(times, 90),
                         "n": samples, "compiled_n": compiled})

            sched.plan_batch([wl["B"]] * 8)      # warm rounds (excluded):
            sched.plan_batch([wl["B"]] * 8)      # two, so the second proves
            times, compiled = [], 0              # the session is steady
            for _ in range(rounds):
                m = watch.mark()
                t0 = time.perf_counter()
                sched.plan_batch([wl["B"]] * 8)
                times.append((time.perf_counter() - t0) * 1e6 / 8)
                compiled += watch.delta(m) > 0
            rows.append({"nodes": n, "engine": engine,
                         "metric": "plan_batch8",
                         "p50_us": p(times, 50), "p90_us": p(times, 90),
                         "n": rounds, "compiled_n": compiled})

            cluster = build_scaled_cluster(n, seed=1, fill=0.6)
            sched = TopoScheduler(cluster, engine=engine, alpha=0.5)
            dec = sched.plan(wl["B"]).decision   # warm x2, excluded
            assert dec.placed, f"60% fill not placeable at n={n}"
            sched.plan(wl["B"])
            times, compiled = [], 0
            for _ in range(n_samples):
                m = watch.mark()
                t0 = time.perf_counter()
                sched.plan(wl["B"])
                times.append((time.perf_counter() - t0) * 1e6)
                compiled += watch.delta(m) > 0
            rows.append({"nodes": n, "engine": engine,
                         "metric": "plan_normal_e2e",
                         "p50_us": p(times, 50), "p90_us": p(times, 90),
                         "n": n_samples, "compiled_n": compiled})
        print(f"# scale n={n} done (parity={parity[str(n)]})",
              file=sys.stderr, flush=True)

    dev = jax.devices()[0]
    return {"protocol": protocol, "platform": dev.platform,
            "device_kind": dev.device_kind, "devices": len(jax.devices()),
            "sizes": list(SIZES), "base_nodes": BASE_NODES, "rows": rows,
            "parity": parity, "shortlist": shortlist_meta,
            "shortlist_parity": shortlist_parity}


def _child_main() -> None:
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    assert len(jax.devices()) == DEVICES, jax.devices()
    print(_MARK + json.dumps(
        measure(os.environ.get("SCALE_PROTOCOL", "small"))))


# ---------------------------------------------------------------------------
# parent: in-process on a TPU, else spawn the 8-device CPU child; merge + emit
# ---------------------------------------------------------------------------

def _spawn_child(full: bool) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={DEVICES}").strip()
    env["SCALE_PROTOCOL"] = "full" if full else "small"
    env["PYTHONPATH"] = "src" + (os.pathsep + env["PYTHONPATH"]
                                 if env.get("PYTHONPATH") else "")
    repo_root = BENCH_JSON.parent
    proc = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_scale_sourcing", _CHILD_FLAG],
        cwd=repo_root, env=env, capture_output=True, text=True, timeout=3600)
    if proc.returncode != 0:
        raise RuntimeError(
            f"scale child failed ({proc.returncode}):\n{proc.stderr[-4000:]}")
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith(_MARK):
            payload = json.loads(line[len(_MARK):])
    if payload is None:
        raise RuntimeError(f"no scale result in child output:\n"
                           f"{proc.stdout[-2000:]}\n{proc.stderr[-2000:]}")
    return payload


def run(full: bool = FULL) -> dict:
    payload = (measure("full" if full else "small") if on_tpu()
               else _spawn_child(full))
    for row in payload["rows"]:
        emit(f"scale_{row['nodes']}_{row['engine']}_{row['metric']}",
             row["p50_us"],
             f"p90={row['p90_us']:.1f}us compiled_n={row['compiled_n']}")
    for size, ok in payload["parity"].items():
        emit(f"scale_{size}_sharded_parity", 0.0,
             "identical" if ok else "DIVERGED")
    for key, ok in payload.get("shortlist_parity", {}).items():
        emit(f"scale_{key.replace(':', '_')}_shortlist_parity", 0.0,
             "identical" if ok else "DIVERGED")
    doc = json.loads(BENCH_JSON.read_text()) if BENCH_JSON.exists() else {}
    doc["scale"] = payload
    BENCH_JSON.write_text(json.dumps(doc, indent=2) + "\n")
    return payload


if __name__ == "__main__":
    if _CHILD_FLAG in sys.argv:
        _child_main()
    else:
        run()
