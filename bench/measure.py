"""What every loop measures with: the window (the profiler and the ``window``
span when traced), the host spans around calls into the program, and the
device's own numbers."""
from __future__ import annotations

import shutil
import tempfile
import time
from contextlib import nullcontext

from bench import trace


def annotator(traced: bool):
    """``annotate(name)``: a host span in the profiler's trace when traced,
    nothing otherwise."""
    if not traced:
        return quiet
    import jax

    return jax.profiler.TraceAnnotation


def quiet(name: str):
    return nullcontext()


def device_info(chips: int) -> dict:
    import jax

    devices = jax.devices()[:chips]
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": peak}


class Window:
    """Starts the profiler (when traced) and the ``window`` span around the
    measured loop; holds the trace reduced over the loop's ``spans``
    afterwards."""

    def __init__(self, traced: bool, spans: tuple[str, ...]) -> None:
        self.traced = traced
        self.spans = spans
        self.trace = None
        self._dir = None

    def __enter__(self):
        if self.traced:
            import jax

            self._dir = tempfile.mkdtemp(prefix="bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self._dir, profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation(trace.WINDOW)
            self._span.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.seconds = time.perf_counter() - self.t0
        if self.traced:
            import jax

            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            if exc[0] is None:
                self.trace = trace.reduce(trace.load(self._dir), self.spans)
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
