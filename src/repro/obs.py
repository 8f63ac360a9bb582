"""In-program spans and counters, exported through the JAX profiler.

A span is a `jax.profiler.TraceAnnotation`: it lands on the profiler's host
plane on the same clock as the device's ``XLA Ops`` / ``XLA Modules`` lines,
so device idle time can be attributed to the host work that covered it.
Program span names carry a prefix, ``sched.`` (the scheduler) or ``day.``
(the co-location loop).  Everything is off until ``enable(True)``; while off
a span is one flag read returning a shared `nullcontext`, and a count is one
flag read.

Three helpers carry the device boundary, so call sites stay plain:

* `program` jits an evaluator under a stable name (the ``XLA Modules`` line
  reads ``jit_<name>``); each call opens ``sched.dispatch`` with
  ``program=<name>`` and counts ``dispatches``;
* `readback` is the device-to-host read of a plan result
  (``sched.readback``);
* `upload` is a host-to-device copy; it counts ``h2d_calls`` (one per host
  array, each a transfer of its own whatever its size) and ``h2d_bytes``.
"""
from __future__ import annotations

from collections import Counter
from contextlib import nullcontext

import jax
import jax.numpy as jnp
import numpy as np

#: spans and counters record only while this is True
enabled = False
#: every `count` name (``dispatches``, ``h2d_bytes``, ...), summed while
#: enabled
counters: Counter = Counter()

_OFF = nullcontext()


def enable(on: bool) -> None:
    global enabled
    enabled = bool(on)


def span(name: str, **args):
    """A host span named ``name`` with ``args`` as its stats, or a shared
    no-op context while disabled."""
    if not enabled:
        return _OFF
    return jax.profiler.TraceAnnotation(name, **args)


def count(name: str, n: int = 1) -> None:
    if enabled:
        counters[name] += n


class _Program:
    """A jitted evaluator under a stable name; attributes other than the
    call (``lower``, ...) are the jitted function's own."""

    __slots__ = ("name", "jitted")

    def __init__(self, name: str, jitted) -> None:
        self.name = name
        self.jitted = jitted

    def __call__(self, *args):
        if not enabled:
            return self.jitted(*args)
        count("dispatches")
        with span("sched.dispatch", program=self.name):
            return self.jitted(*args)

    def __getattr__(self, attr):
        return getattr(self.jitted, attr)


def program(name: str, fn, **jit_kw) -> _Program:
    """``jax.jit(fn, **jit_kw)`` whose module is named ``jit_<name>``."""

    def body(*args):
        return fn(*args)

    body.__name__ = body.__qualname__ = name
    return _Program(name, jax.jit(body, **jit_kw))


def readback(res) -> list[int]:
    """The device result ``res`` as host ints: device wait plus copy."""
    with span("sched.readback"):
        return [int(x) for x in jax.device_get(res)]


def upload(x, sharding=None):
    """``x`` on the device (laid out by ``sharding`` when given); counts
    host arrays and their bytes."""
    if isinstance(x, np.ndarray):
        count("h2d_calls")
        count("h2d_bytes", x.nbytes)
    if sharding is not None:
        return jax.device_put(x, sharding)
    return jnp.asarray(x)
