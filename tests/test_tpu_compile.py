"""Compile rehearsals for a TPU v5e, run without the chip.

The TPU compiler is installed alongside jaxlib, so the main path's kernels
and fused evaluators are compiled here for a DESCRIBED ``v5e:2x2`` topology
(nothing runs).  This catches what interpret mode and XLA:CPU cannot: Mosaic
block-shape rules, VMEM limits, programs that do not fit the device.

The topology is described inside a module fixture (never at import): only
one process may load the TPU library, and every test worker imports this
file.  Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cluster import (DRAIN_FIELDS, MAX_DENSE_VICTIMS, NODE_FIELDS,
                                VICTIM_FIELDS)
from repro.core.topology import RTX4090_SERVER

#: kernel width: 2^16 subset / node lanes
LANES = 1 << 16
#: the resident state of the largest committed cluster size
NODES = 10240
ALPHA = 0.5


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("kernel", ["topo_score", "placement_tier",
                                    "topo_score_argmax"])
def test_topo_score_kernels_compile_for_v5e(one_chip, kernel):
    from repro.kernels.topo_score import (TopoRequest, placement_tier_pallas,
                                          topo_score_argmax_pallas,
                                          topo_score_pallas)

    spec = RTX4090_SERVER
    req = TopoRequest(need_gpus=2, need_cgs=2, cgs_per_bundle=1)
    x = jax.ShapeDtypeStruct((LANES,), jnp.int32, sharding=one_chip)
    if kernel == "topo_score":
        text = _compiled_text(
            lambda g, c, p: topo_score_pallas(g, c, p, spec, req,
                                              interpret=False), x, x, x)
    elif kernel == "placement_tier":
        text = _compiled_text(
            lambda g, c: placement_tier_pallas(g, c, spec, req,
                                               interpret=False), x, x)
    else:
        text = _compiled_text(
            lambda g, c, p, k, ok: topo_score_argmax_pallas(
                g, c, p, k, spec, req, interpret=False, ok=ok),
            x, x, x, x, x)
    assert "tpu_custom_call" in text


def _resident_shapes(n_rows: int, node_sh, victim_sh, repl):
    """`DeviceClusterState` tensors plus a delta-free plan's packed
    argument buffer (empty; the shortlist's holds the rep-mask words) and
    the request vector, as shapes."""
    cap = MAX_DENSE_VICTIMS
    i32 = jnp.int32
    return {
        "nodestate": jax.ShapeDtypeStruct((NODE_FIELDS, n_rows), i32,
                                          sharding=node_sh),
        "victims": jax.ShapeDtypeStruct((VICTIM_FIELDS, n_rows, cap), i32,
                                        sharding=victim_sh),
        "drain": jax.ShapeDtypeStruct((DRAIN_FIELDS, n_rows), i32,
                                      sharding=node_sh),
        "aux": jax.ShapeDtypeStruct((0,), i32, sharding=repl),
        "rep_words": jax.ShapeDtypeStruct((-(-n_rows // 32),), i32,
                                          sharding=repl),
        "req": jax.ShapeDtypeStruct((4,), i32, sharding=repl),
    }


@pytest.mark.parametrize("evaluator", ["normal", "plan", "shortlist_plan"])
def test_fused_evaluators_compile_for_v5e(one_chip, evaluator):
    """The single-chip `imp_batched` programs at 10,240 resident nodes."""
    from repro.core import preemption_jax as pj

    spec = RTX4090_SERVER
    s = _resident_shapes(NODES, one_chip, one_chip, one_chip)
    if evaluator == "normal":
        fn = pj.normal_evaluator(None, spec, 0)
        args = (s["nodestate"], s["aux"], s["req"])
    elif evaluator == "plan":
        fn = pj.plan_evaluator(None, spec, pj.MIN_M, 0, 0, ALPHA)
        args = (s["nodestate"], s["victims"], s["drain"], s["aux"],
                s["req"])
    else:
        fn = pj.shortlist_plan_evaluator(None, spec, 128, 0, 0, ALPHA)
        args = (s["nodestate"], s["victims"], s["drain"], s["rep_words"],
                s["req"])
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None


def test_sharded_plan_evaluator_compiles_for_four_chips(topo):
    """`imp_sharded`'s chained plan program over a 4-chip node mesh."""
    from repro.core import preemption_jax as pj
    from repro.core.cluster import _mesh_shardings, cluster_mesh

    mesh = cluster_mesh(topo.devices)
    s = _resident_shapes(NODES, *_mesh_shardings(mesh))
    fn = pj.plan_evaluator(mesh, RTX4090_SERVER, pj.MIN_M, 0, 0, ALPHA)
    text = fn.lower(s["nodestate"], s["victims"], s["drain"], s["aux"],
                    s["req"]).compile().as_text()
    assert "all-reduce" in text or "all-gather" in text
