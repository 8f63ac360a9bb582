"""§Perf levers stay correct: chunked attention, MoE dispatch modes,
quick-failure pruning, and the loop-scaled HLO walker."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model


def _loss(cfg, params, batch):
    api = build_model(cfg)
    return float(jax.jit(api.loss)(params, batch)[0])


@pytest.mark.parametrize("arch", ["llama3.2-1b", "mixtral-8x7b",
                                  "paligemma-3b"])
def test_chunked_attention_matches_baseline(arch):
    """q-chunked attention (H4) is bit-identical across causal/SWA/prefix-LM."""
    cfg = get_config(arch, smoke=True)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    B, S = 2, 64
    batch = {}
    if cfg.frontend == "patch":
        batch["prefix_embeds"] = jnp.asarray(
            rng.standard_normal((B, cfg.frontend_len, cfg.d_model)),
            cfg.compute_dtype)
        batch["tokens"] = jnp.asarray(
            rng.integers(0, cfg.vocab, (B, S - cfg.frontend_len)), jnp.int32)
    else:
        batch["tokens"] = jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                      jnp.int32)
    base = _loss(cfg, params, batch)
    chunked = _loss(dataclasses.replace(cfg, attn_chunk_q=16), params, batch)
    assert base == pytest.approx(chunked, abs=1e-6)


def test_moe_dispatch_modes_agree():
    """per_sequence dispatch ~= global (capacity grouping noise only)."""
    cfg = get_config("mixtral-8x7b", smoke=True)
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (4, 32)),
                                   jnp.int32)}
    g = _loss(cfg, params, batch)
    ps = _loss(dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="per_sequence")),
        params, batch)
    assert g == pytest.approx(ps, abs=0.3)


def test_quick_failure_pruning_sound():
    """min_feasible_k never exceeds the true brute-force minimal size."""
    from repro.core import preemption
    from repro.core.simulator import SimConfig, build_saturated_cluster
    from repro.core.workload import table3_workloads

    # node counts must keep the scaled Table-3 instance mix exact (multiples
    # of 10 do; e.g. 8 nodes overflows by rounding)
    cluster = build_saturated_cluster(SimConfig(num_nodes=10, seed=2))
    wls = {w.name: w for w in table3_workloads()}
    for name in ("A", "B", "C"):
        wl = wls[name]
        for node in range(cluster.num_nodes):
            victims = cluster.victims_on(node, wl.priority)
            k_min = preemption.min_feasible_k(cluster, wl, node, victims)
            brute = preemption.brute_force_min_k(cluster, wl, node)
            if brute is not None:
                assert k_min <= brute[0], (name, node)


def test_hlo_walker_scales_scan_bodies():
    """The roofline FLOPs source: scan bodies multiplied by trip count."""
    from repro.launch import hlo

    def body(x, w):
        return x @ w, None

    def scanned(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    ws = jax.ShapeDtypeStruct((5, 64, 64), jnp.float32)
    compiled = jax.jit(scanned).lower(x, ws).compile()
    stats = hlo.walk_stats(compiled.as_text())
    assert stats["flops_scaled"] == 5 * 2 * 64 ** 3
    # raw cost_analysis counts the body once — the reason the walker exists
    assert compiled.cost_analysis()["flops"] < stats["flops_scaled"]


def test_collective_parser_on_sharded_module():
    import os
    import subprocess
    import sys
    import textwrap

    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                       "src"))
    env = dict(os.environ, PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch import hlo
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((4,), ("data",))
        sh = NamedSharding(mesh, P("data"))
        f = jax.jit(lambda x: x.sum(), in_shardings=(sh,))
        c = f.lower(jax.ShapeDtypeStruct((64, 8), jnp.float32)).compile()
        s = hlo.summarize(c.as_text())
        assert s["collective_counts"]["all-reduce"] >= 1, s
        print("ok")
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
