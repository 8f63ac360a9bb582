"""The trace reduction: busy as the union of device op intervals inside the
window, idle gaps split by the host span covering them."""
import pytest

from bench import trace


def _planes(device_ops, host_spans):
    return [
        {"name": "/host:CPU", "lines": [{"name": "python",
                                         "events": host_spans}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [("jit_f", 0, 10_000)]},
            {"name": "XLA Ops", "events": device_ops}]},
    ]


def test_busy_is_the_union_and_gaps_go_to_the_covering_span():
    ops = [("fusion", 100, 100), ("fusion", 150, 100),   # overlap: 100..250
           ("copy", 600, 50),                            # 600..650
           ("sort", 950, 200)]                           # clipped at 1000
    spans = [("window", 0, 1000), ("plan", 0, 500), ("commit", 500, 300),
             ("generate", 800, 100)]
    got = trace.reduce(_planes(ops, spans), ("plan", "commit", "generate"))
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx((150 + 50 + 50) * 1e-9)
    assert got["idle_share"] == pytest.approx(0.75)
    gaps = dict(got["idle_gaps"])
    # idle: 0..100 and 250..500 (plan), 500..600 and 650..800 (commit),
    # 800..900 (generate), 900..950 (no span)
    assert gaps == pytest.approx({"plan": 350e-9, "commit": 250e-9,
                                  "generate": 100e-9,
                                  "outside spans": 50e-9})
    assert dict(got["device_ops"]) == pytest.approx(
        {"fusion": 200e-9, "copy": 50e-9, "sort": 50e-9})


def test_a_window_without_device_work_is_refused():
    with pytest.raises(RuntimeError):
        trace.reduce(_planes([("fusion", 2000, 10)], [("window", 0, 1000)]),
                     ("plan",))


def test_load_reads_a_recorded_profile(tmp_path):
    """A trace recorded here on the CPU: the loader finds the host spans; the
    reduction refuses it, as a CPU has no TPU plane to measure."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 2)
    x = jnp.ones(8)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation(trace.WINDOW):
        with jax.profiler.TraceAnnotation("plan"):
            f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = trace.load(tmp_path)
    names = {e[0] for p in planes for ln in p["lines"] for e in ln["events"]}
    assert {trace.WINDOW, "plan"} <= names
    with pytest.raises(RuntimeError):
        trace.reduce(planes, ("plan",))


def test_reduce_reads_a_window_recorded_on_a_tpu():
    """``data/tpu_window.xplane.pb``: a window recorded on a TPU v5e, three
    jitted 256x256 matmuls each under a ``plan`` span and host-only work under
    ``commit`` spans."""
    from pathlib import Path

    got = trace.reduce(trace.load(Path(__file__).parent / "data"),
                       ("plan", "commit"))
    assert got["devices"] == 1
    assert 0 < got["busy_s"] < got["window_s"]
    gaps = dict(got["idle_gaps"])
    assert set(gaps) <= {"plan", "commit", "outside spans"}
    assert gaps["plan"] > 0 and gaps["commit"] > 0
    assert sum(gaps.values()) == pytest.approx(
        got["window_s"] - got["busy_s"], rel=1e-6)
    assert any("fusion" in name for name, _ in got["device_ops"])
