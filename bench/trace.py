"""Profiler trace reduction: device busy and idle over the measured window.

The run wraps its measured window in a host span named ``window`` and each call
into the program in a span of its own (the loop's ``SPANS``).  From the trace
that ``jax.profiler`` writes (``<dir>/plugins/profile/<time>/*.xplane.pb``):

* busy time of a device is the union of the intervals of the operations on its
  ``XLA Ops`` line, clipped to the window; ``busy_s`` is its mean over the
  devices that ran any operation in the window, and the idle share is one less
  busy over the window;
* ``device_ops``: the operations that took most device time, by name;
* ``idle_gaps``: the device's idle time inside the window, split by the host
  span that covered it (``"outside spans"`` where none did), largest first.
"""
from __future__ import annotations

import re
from pathlib import Path

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
WINDOW = "window"
TOP = 10


def load(log_dir: str | Path) -> list[dict]:
    """Planes of the one trace under ``log_dir`` as plain lists:
    ``[{"name", "lines": [{"name", "events": [(name, start_ns, dur_ns)]}]}]``."""
    from jax.profiler import ProfileData

    files = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if len(files) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(files)}")
    return planes_of(ProfileData.from_file(str(files[0])))


def planes_of(data) -> list[dict]:
    return [{"name": p.name,
             "lines": [{"name": ln.name,
                        "events": [(e.name, e.start_ns, e.duration_ns)
                                   for e in ln.events]}
                       for ln in p.lines]}
            for p in data.planes]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce(planes: list[dict], spans: tuple[str, ...]) -> dict:
    """``busy_s``, ``window_s``, ``idle_share`` (0..1), ``device_ops`` and
    ``idle_gaps`` (lists of ``[name, seconds]``) of one traced window."""
    host = [p for p in planes if not DEVICE_PLANE.match(p["name"])]
    windows = [(s, s + d) for p in host for ln in p["lines"]
               for name, s, d in ln["events"] if name == WINDOW]
    if len(windows) != 1:
        raise RuntimeError(f"expected one '{WINDOW}' span, found "
                           f"{len(windows)}")
    w0, w1 = windows[0]
    span_iv = sorted((max(s, w0), min(s + d, w1), name)
                     for p in host for ln in p["lines"]
                     for name, s, d in ln["events"]
                     if name in spans and s < w1 and s + d > w0)
    busy_per_device = []
    op_time: dict[str, float] = {}
    gaps: dict[str, float] = {}
    for p in planes:
        if not DEVICE_PLANE.match(p["name"]):
            continue
        iv = []
        for ln in p["lines"]:
            if ln["name"] != OPS_LINE:
                continue
            for name, s, d in ln["events"]:
                s0, s1 = max(s, w0), min(s + d, w1)
                if s1 > s0:
                    iv.append((s0, s1))
                    op_time[name] = op_time.get(name, 0.0) + (s1 - s0)
        if not iv:
            continue
        busy = _union(iv)
        busy_per_device.append(sum(e - s for s, e in busy))
        # idle gaps of this device, split over the host spans covering them
        cursor, j = w0, 0
        for s, e in busy + [(w1, w1)]:
            if s > cursor:
                j = _attribute(cursor, s, span_iv, j, gaps)
            cursor = max(cursor, e)
    if not busy_per_device:
        raise RuntimeError("no device operation ran inside the window")
    n_dev = len(busy_per_device)
    window_s = (w1 - w0) / 1e9
    busy_s = sum(busy_per_device) / n_dev / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window_s,
        "idle_share": 1.0 - busy_s / window_s,
        "devices": n_dev,
        "device_ops": [[k, v / n_dev / 1e9] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:TOP]],
        "idle_gaps": [[k, v / n_dev / 1e9] for k, v in sorted(
            gaps.items(), key=lambda kv: -kv[1])[:TOP]],
    }


def _attribute(g0: float, g1: float, span_iv: list[tuple], j: int,
               out: dict) -> int:
    """Adds the gap ``[g0, g1)`` to ``out`` by covering span; ``span_iv`` is
    sorted and its spans do not overlap, so the scan resumes at ``j``, the
    first span not ended before an earlier gap; returns the next ``j``."""
    while j < len(span_iv) and span_iv[j][1] <= g0:
        j += 1
    covered = 0.0
    k = j
    while k < len(span_iv) and span_iv[k][0] < g1:
        s, e, name = span_iv[k]
        o = min(e, g1) - max(s, g0)
        if o > 0:
            out[name] = out.get(name, 0.0) + o
            covered += o
        k += 1
    rest = (g1 - g0) - covered
    if rest > 0:
        out["outside spans"] = out.get("outside spans", 0.0) + rest
    return j
