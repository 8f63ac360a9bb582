"""Read a cell's numbers compared for ``correct``, for the program and for its
control, on many seeds in one process.

    python3 bench/control.py --workload <cell> --seeds <n> [<n> ...]
        [--seconds 3] [--program 1] [--control '<json>']

The control is the program run with the scheduler settings of the mix's
``control`` key (or of ``--control``): a path of the program that breaks a
guarantee the configuration states.  Each run goes through
`bench.run.run_cell` at the cell's own size and load, window and check
included; one JSON line per run gives the seed, the side and its checks.
With ``--program 1`` the program's own runs come first, on the same seeds,
which read the lower end of each limit.  The benchmark's own runs never run
this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import run  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--program", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=json.loads, default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    cell = next(w for w in run.benchmark()["workloads"]
                if w["name"] == args.workload)
    with open(run.BENCH / "traffic" / f"{cell['traffic']}.json") as f:
        control = args.control or json.load(f)["control"]
    sides = ([("program", None)] if args.program else []) + [
        ("control", control)]
    try:
        for side, overrides in sides:
            for seed in args.seeds:
                out = run.run_cell(args.workload, seed, args.seconds, False,
                                   engine_overrides=overrides)
                print(json.dumps({
                    "side": side, "seed": seed, "overrides": overrides,
                    "correct": out["correct"], "summary": out["summary"],
                    "checks": {k: c["value"]
                               for k, c in out["checks"].items()}}),
                      flush=True)
    except run.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
