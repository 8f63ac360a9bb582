"""The program's own spans in a traced window: where each plan's host time
goes, and which program span covered the device's idle time.

The program (`repro.obs`, on while the window runs) writes ``sched.*`` and
``day.*`` spans into the profiler's host plane; the device planes carry the
``XLA Modules`` line, one event per run of a named program (``jit_<name>``).
From the planes `bench.trace.load` reads, `reduce` gives:

* ``per_plan``: per ``sched.plan`` span that starts in the window, the summed
  duration of every program span inside it, by name (nested ones counted in
  each of their names);
* ``idle_by_program_span``: the device's idle time inside the window, each
  piece given to the innermost program span covering it (a plan's own self
  time reads ``sched.plan``; ``outside program spans`` where none did);
* ``device_by_program``: device busy time per program, by module name;
* ``sched_share``: the share of the window inside outermost ``sched.plan``,
  ``sched.commit`` or ``sched.batch_session`` spans.

Spans and module runs cut by the window are clipped to it.  The program's
spans are all on one host thread, where they nest.

    python3 bench/spans.py --workload <cell> [<cell> ...] --seeds <n> [...]
        [--seconds 30] [--trace 0 1] [--keep <dir>]

runs each cell once per seed and per ``--trace`` value, all in one process,
through `bench.run.run_cell`, and prints one JSON line per run.  A traced run
has the program's spans on in the window and adds, under ``program``, the
reduction above, the ``dispatches`` and ``h2d_bytes`` counted in the window,
and the per-layer numbers they give; an untraced one gives the cell's
end-to-end metrics, so the two read the cost of tracing.  ``--keep`` copies
each raw trace into a directory.
"""
from __future__ import annotations

import re
import statistics
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
for _p in (ROOT / "src", ROOT):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

from bench.trace import DEVICE_PLANE, OPS_LINE, WINDOW, _union  # noqa: E402

PREFIXES = ("sched.", "day.")
PLAN = "sched.plan"
#: spans whose outermost instances are the scheduler's share of a day
SCHED_OUTER = ("sched.plan", "sched.commit", "sched.batch_session")
MODULES_LINE = "XLA Modules"
OUTSIDE = "outside program spans"
_RUN_ID = re.compile(r"\(\d+\)$")


def _host_lines(planes: list[dict]):
    return [ln for p in planes if not DEVICE_PLANE.match(p["name"])
            for ln in p["lines"]]


def _window(planes: list[dict]) -> tuple[float, float]:
    windows = [(s, s + d) for ln in _host_lines(planes)
               for name, s, d in ln["events"] if name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one '{WINDOW}' span, found "
                           f"{len(windows)}")
    return windows[0]


def _innermost(spans: list[tuple]) -> list[tuple]:
    """Disjoint ``(start, end, name)`` pieces of one thread's nested spans
    (sorted by start, outer first), each named for the innermost span
    covering it."""
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    out = []
    stack: list[tuple] = []
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while stack and stack[-1][1] <= a:
            stack.pop()
        while i < len(spans) and spans[i][0] <= a:
            if spans[i][1] > a:
                stack.append(spans[i])
            i += 1
        if stack:
            out.append((a, b, stack[-1][2]))
    return out


def _attribute(g0: float, g1: float, pieces: list[tuple], j: int,
               out: dict) -> int:
    """Adds the gap ``[g0, g1)`` to ``out`` by the disjoint sorted
    ``pieces`` covering it (the rest to `OUTSIDE`); the scan resumes at
    ``j``, the first piece not ended before an earlier gap."""
    while j < len(pieces) and pieces[j][1] <= g0:
        j += 1
    covered = 0.0
    k = j
    while k < len(pieces) and pieces[k][0] < g1:
        s, e, name = pieces[k]
        o = min(e, g1) - max(s, g0)
        if o > 0:
            out[name] = out.get(name, 0.0) + o
            covered += o
        k += 1
    if g1 - g0 > covered:
        out[OUTSIDE] = out.get(OUTSIDE, 0.0) + (g1 - g0 - covered)
    return j


def _per_plan(spans: list[tuple], w0: float) -> list[dict]:
    """Per `PLAN` span that starts inside the window: seconds inside it by
    span name."""
    out = []
    for i, (ps, pe, name) in enumerate(spans):
        if name != PLAN or ps == w0:
            continue
        kids: dict[str, float] = {}
        for s, e, kid in spans[i + 1:]:
            if s >= pe:
                break
            if e <= pe:
                kids[kid] = kids.get(kid, 0.0) + (e - s) / 1e9
        out.append(kids)
    return out


def reduce(planes: list[dict]) -> dict:
    """``per_plan``, ``idle_by_program_span``, ``device_by_program`` (lists
    of ``[name, seconds]``, largest first), ``sched_share`` (0..1) and
    ``plans`` of one traced window."""
    w0, w1 = _window(planes)
    per_plan: list[dict] = []
    pieces: list[tuple] = []
    outer: list[tuple] = []
    for ln in _host_lines(planes):
        spans = sorted(((max(s, w0), min(s + d, w1), name)
                        for name, s, d in ln["events"]
                        if name.startswith(PREFIXES)
                        and s < w1 and s + d > w0),
                       key=lambda x: (x[0], -x[1]))
        if not spans:
            continue
        if pieces:
            raise RuntimeError("program spans on more than one host thread")
        pieces = _innermost(spans)
        per_plan = _per_plan(spans, w0)
        outer = _union([(s, e) for s, e, name in spans
                        if name in SCHED_OUTER])
    idle: dict[str, float] = {}
    modules: dict[str, float] = {}
    n_dev = 0
    for p in planes:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        ops = []
        for ln in p["lines"]:
            for name, s, d in ln["events"]:
                s0, s1 = max(s, w0), min(s + d, w1)
                if s1 <= s0:
                    continue
                if ln["name"] == OPS_LINE:
                    ops.append((s0, s1))
                elif ln["name"] == MODULES_LINE:
                    key = _RUN_ID.sub("", name)
                    modules[key] = modules.get(key, 0.0) + (s1 - s0)
        if not ops:
            continue
        n_dev += 1
        cursor, j = w0, 0
        for s, e in _union(ops) + [(w1, w1)]:
            if s > cursor:
                j = _attribute(cursor, s, pieces, j, idle)
            cursor = max(cursor, e)
    n = max(n_dev, 1)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in sorted(d.items(),
                                                    key=lambda kv: -kv[1])]

    return {
        "plans": len(per_plan),
        "per_plan": per_plan,
        "idle_by_program_span": ranked(idle),
        "device_by_program": ranked(modules),
        "sched_share": sum(e - s for s, e in outer) / (w1 - w0),
    }


def per_layer(red: dict, counters: dict, cell_kind: str) -> dict:
    """The per-layer numbers of a window: ``admit`` (per plan medians of the
    stage spans, counts per plan) or ``day`` (the scheduler's share of the
    window, dispatches per plan)."""
    plans = max(red["plans"], 1)
    per = {"dispatches_per_plan": counters.get("dispatches", 0) / plans}
    if cell_kind == "day":
        per["sched_share"] = 100.0 * red["sched_share"]
        return {f"{k}.day": v for k, v in per.items()}
    for stage in ("sync", "patch", "route", "dispatch", "readback"):
        per[f"{stage}_ms_p50"] = 1e3 * statistics.median(
            [p.get(f"sched.{stage}", 0.0) for p in red["per_plan"]] or [0.0])
    per["h2d_bytes_per_plan"] = counters.get("h2d_bytes", 0) / plans
    return {f"{k}.admit": v for k, v in per.items()}


def run_cell(workload: str, seed: int, seconds: float, traced: bool,
             keep: str | None = None, **kw) -> dict:
    """One run of a cell through `bench.run.run_cell` (``kw`` passed on),
    its result line plus, when traced, the program's spans: the harness's
    window with `repro.obs` on inside it, the reduction of its planes and
    the counters of the window (``keep``: a directory to copy the raw
    trace into)."""
    from bench import run

    if not traced:
        return run.run_cell(workload, seed, seconds, False, **kw)
    import shutil
    from collections import Counter
    from pathlib import Path

    from bench import measure, trace
    from repro import obs

    got: dict = {}
    load, window = trace.load, measure.Window

    def load_and_keep(log_dir):
        got["planes"] = load(log_dir)
        if keep:
            shutil.copytree(log_dir, Path(keep) / Path(log_dir).name)
        return got["planes"]

    class ProgramSpans(window):
        def __enter__(self):
            self.counters0 = Counter(obs.counters)
            obs.enable(True)
            return super().__enter__()

        def __exit__(self, *exc):
            obs.enable(False)
            got["counters"] = dict(obs.counters - self.counters0)
            return super().__exit__(*exc)

    # the loops take `Window` from `bench.measure` as they load
    trace.load, measure.Window = load_and_keep, ProgramSpans
    try:
        out = run.run_cell(workload, seed, seconds, True, **kw)
    finally:
        trace.load, measure.Window = load, window
    red = reduce(got["planes"])
    kind = "day" if workload.endswith(".day") else "admit"
    out["program"] = {
        "per_layer": per_layer(red, got["counters"], kind),
        "counters": got["counters"],
        "per_plan_ms_p50": {
            k: 1e3 * statistics.median(p.get(k, 0.0)
                                       for p in red["per_plan"])
            for k in sorted({k for p in red["per_plan"] for k in p})},
        **{k: red[k] for k in ("plans", "sched_share",
                               "idle_by_program_span", "device_by_program")}}
    return out


def _main(argv=None) -> int:
    import argparse
    import json
    import os

    from bench import run

    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", nargs="+", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--trace", type=int, nargs="+", choices=(0, 1),
                    default=[1])
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE_DIR)
    from repro.jax_cache import enable_compile_cache

    enable_compile_cache()
    for workload in args.workload:
        for seed in args.seeds:
            for traced in args.trace:
                out = run_cell(workload, seed, args.seconds, bool(traced),
                               args.keep)
                line = {"workload": workload, "seed": seed, "trace": traced}
                line.update({k: out[k] for k in (
                    "correct", "attempted", "failed", "metrics", "device",
                    "summary", "program") if k in out})
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(_main())
