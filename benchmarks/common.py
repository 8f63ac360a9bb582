"""Shared benchmark plumbing: CSV emission + sizing knobs."""
from __future__ import annotations

import os
import sys

FULL = os.environ.get("BENCH_FULL", "") == "1"


def on_tpu() -> bool:
    """True when JAX's backend is a TPU (this initializes the backend, so
    the calling process then holds the chip: run the work in-process and
    start no JAX child that needs it)."""
    import jax

    return jax.default_backend() == "tpu"


def emit(name: str, us_per_call: float, derived: str = "") -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def p(x: list[float], q: float) -> float:
    import numpy as np

    return float(np.percentile(x, q)) if x else 0.0
