"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace 0|1

Run from the root of a checkout on a machine with a TPU.  The cell is looked
up in ``BENCHMARK.json``; its configuration (``bench/configs/<config>.json``)
and traffic mix (``bench/traffic/<traffic>.json``) are data; the mix's
``loop`` names the loop that runs it, ``bench/loops/<loop>.py``, so a new
kind of traffic is a new file.  The run builds its start state from the seed, warms up every
program the cell's traffic uses (all of that is ``setup_s``), measures for
``--seconds``, then checks the plans chosen from the window against the plain
reference (`bench.reference`) and prints one JSON object.  With ``--trace 0``
it reports the cell's end-to-end metrics, with ``--trace 1`` its per-layer
metrics, read from the window under ``jax.profiler``.  Each metric is computed
by a reader of its own, ``bench/metrics/<metric>.py``.

Exits non-zero, printing no result, where JAX finds no TPU or fewer chips than
the cell asks for.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))
if str(ROOT / "src") not in sys.path:
    sys.path.insert(1, str(ROOT / "src"))

from bench import fleet  # noqa: E402

BENCH = ROOT / "bench"
#: the compile cache, at a fixed path inside the checkout
CACHE_DIR = ROOT / ".jax_cache"


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def benchmark() -> dict:
    with open(ROOT / "BENCHMARK.json") as f:
        return json.load(f)


def device_check(chips: int) -> None:
    import jax

    from repro.kernels.topo_score import _interpret_default

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devices[0].platform}")
    if len(devices) < chips:
        raise NoChip(f"{chips} chips asked for, JAX found {len(devices)}")
    if _interpret_default():
        raise NoChip("Pallas kernels would run in interpret mode here")


def _module(kind: str, name: str):
    """``bench/<kind>/<name>.py``, loaded by its path: a loop or a reader."""
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}", BENCH / kind / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def loop(name: str):
    """The loop a traffic mix names: ``bench/loops/<name>.py``, whose
    ``run(cfg, mix, seed, seconds, traced, engine_overrides, watch,
    t_start)`` returns ``(run, got, attempted, device)``."""
    return _module("loops", name)


def reader(name: str):
    """The reader of a metric: ``read(run)`` of ``bench/metrics/<name>.py``."""
    return _module("metrics", name).read


def _metrics_for(bench: dict, cell: str, traced: bool) -> list[dict]:
    group = bench["per_layer"] if traced else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def run_cell(workload: str, seed: int, seconds: float, traced: bool, *,
             require_chip: bool = True, config_overrides: dict | None = None,
             traffic_overrides: dict | None = None,
             engine_overrides: dict | None = None,
             t_start: float | None = None) -> dict:
    """One run of one cell; returns the result object.

    ``config_overrides`` (a smaller fleet), ``traffic_overrides`` (a shorter
    day) and ``require_chip=False`` are for the CPU tests;
    ``engine_overrides`` runs the program with other scheduler settings (the
    control).
    """
    t_start = time.perf_counter() if t_start is None else t_start
    bench = benchmark()
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    if require_chip:
        device_check(cell["chips"])
    from repro.core.simulator import CompileWatch

    watch = CompileWatch.get()
    cfg = dict(fleet.load_config(cell["config"]), chips=cell["chips"])
    cfg.update(config_overrides or {})
    with open(BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        mix = json.load(f)
    mix.update(traffic_overrides or {})
    run, got, attempted, device = loop(mix["loop"]).run(
        cfg, mix, seed, seconds, traced, engine_overrides, watch, t_start)
    failed = sum(got[k] for k in ("plans_wrong", "commits_wrong", "ops_wrong",
                                  "repeats_differing", "errors") if k in got)
    checks = {
        "plans_checked": {"value": got["plans_checked"],
                          "limit": mix["check_plans_min"]},
        "plans_wrong": {"value": got["plans_wrong"], "limit": 0},
        "commits_wrong": {"value": got["commits_wrong"], "limit": 0},
        "ops_wrong": {"value": got["ops_wrong"], "limit": 0},
    }
    for k in ("repeats_differing", "errors"):
        if k in got:
            checks[k] = {"value": got[k], "limit": 0}
    correct = (failed == 0
               and got["plans_checked"] >= mix["check_plans_min"])
    metrics = {}
    for m in _metrics_for(bench, workload, traced):
        value = reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        elif correct:
            # a run that is not correct is refused already, whatever it read
            raise RuntimeError(f"{m['name']}: nothing to read in this run of "
                               f"{workload}, which BENCHMARK.json lists it for")
    if traced:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if traced:
        out["breakdown"] = {"device_ops": run.trace["device_ops"],
                            "idle_gaps": run.trace["idle_gaps"]}
    out["summary"] = got["summary"]
    out["examples"] = got["examples"]
    out["checks"] = checks
    return out


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    # before JAX is imported, which reads it; the program's
    # enable_compile_cache then keeps to it
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_PROCESS)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(out.pop("summary"))
    for ex in out["examples"]:
        print(f"disagreement: {ex}", file=sys.stderr)
    for name, c in out["checks"].items():
        rel = ">=" if name == "plans_checked" else "<="
        print(f"check {name}={c['value']} (limit {rel} {c['limit']})",
              file=sys.stderr)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
