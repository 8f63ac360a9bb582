"""Distribution tests needing >1 device run in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count (tests in THIS process keep
the default single device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def run_py(code: str, devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_small_mesh_train_step_runs():
    """Real sharded execution (not just lowering) on a 4x2 host mesh."""
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import auto_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.common import set_batch_axes
        from repro import sharding as shd
        from repro.train import TrainConfig, init_train_state, make_train_step

        mesh = auto_mesh((4, 2), ("data", "model"))
        cfg = get_config("llama3.2-1b", smoke=True)
        api = build_model(cfg)
        set_batch_axes(("data",))
        state = init_train_state(api, jax.random.PRNGKey(0))
        state_sh = shd.make_param_shardings(cfg, mesh, jax.eval_shape(lambda: state))
        state = jax.device_put(state, state_sh)
        step = make_train_step(api, TrainConfig(accum_steps=2))
        toks = jnp.asarray(np.random.default_rng(0).integers(0, cfg.vocab, (8, 64)), jnp.int32)
        b_sh = shd.batch_sharding(mesh, jax.eval_shape(lambda: {"tokens": toks}))
        batch = jax.device_put({"tokens": toks}, b_sh)
        with mesh:
            jstep = jax.jit(step, in_shardings=(state_sh, b_sh),
                            out_shardings=(state_sh, NamedSharding(mesh, P())))
            state, m = jstep(state, batch)
            state, m = jstep(state, batch)
        loss = float(m["loss"])
        assert np.isfinite(loss), loss
        print("loss", loss)
    """))


def test_dryrun_cell_multi_pod_small():
    """The dry-run machinery on a (2,2,2) multi-pod mesh with a smoke arch."""
    print(run_py("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.launch.dryrun as dr
        from repro.launch.mesh import auto_mesh
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.common import set_batch_axes
        from repro import sharding as shd
        from repro.train import TrainConfig, make_train_step, train_state_specs
        from repro.configs.shapes import ShapeSpec, batch_specs

        mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("qwen3-8b", smoke=True)
        api = build_model(cfg)
        set_batch_axes(shd._batch_axes_for(mesh, 8))
        shape = ShapeSpec("t", 64, 8, "train")
        with mesh:
            state_shape = train_state_specs(api)
            state_sh = shd.make_param_shardings(cfg, mesh, state_shape)
            bspec = batch_specs(cfg, shape)
            b_sh = shd.batch_sharding(mesh, bspec)
            step = make_train_step(api, TrainConfig())
            lowered = jax.jit(step, in_shardings=(state_sh, b_sh),
                              out_shardings=(state_sh, NamedSharding(mesh, P()))
                              ).lower(state_shape, bspec)
            compiled = lowered.compile()
        print("mem", compiled.memory_analysis().temp_size_in_bytes)
        from repro.launch import hlo
        s = hlo.summarize(compiled.as_text())
        assert s["collective_bytes"] > 0
        print("collectives ok", s["collective_counts"])
    """))


def test_pod_sync_int8_compression():
    """Cross-pod compressed sync: pods converge to the mean delta; error
    feedback keeps long-run bias near zero."""
    print(run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import auto_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.train.sync import make_pod_sync, init_error_state, quantize_int8, dequantize_int8

        mesh = auto_mesh((2, 2, 2), ("pod", "data", "model"))
        sync = make_pod_sync(mesh)
        # params differ per pod: shard a leading axis of 2 over "pod"
        base = np.linspace(-1, 1, 2 * 4 * 4).reshape(2, 4, 4).astype(np.float32)
        delta = np.stack([np.full((4, 4), 0.5, np.float32),
                          np.full((4, 4), -0.1, np.float32)])
        params = {"w": jnp.asarray(base + delta)}
        anchor = {"w": jnp.asarray(base)}
        err = init_error_state(params)
        spec = {"w": P("pod", None, None)}
        with mesh:
            new_params, new_err = sync(params, anchor, err, spec)
        got = np.asarray(new_params["w"])
        want = base + delta.mean(axis=0)   # pmean of deltas
        np.testing.assert_allclose(got, want, atol=0.01)
        # error feedback: residual equals quantization error
        q, s = quantize_int8(jnp.asarray(delta[0]))
        assert float(jnp.max(jnp.abs(jnp.asarray(new_err["w"][0])))) <= float(s) + 1e-6
        print("pod sync ok")
    """))


def test_sharded_engine_decision_parity_randomized():
    """imp_sharded on a real 8-device mesh is bit-identical to imp_batched
    over randomized plan / commit / rollback / plan_batch sequences."""
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    print(run_py(f"""
        import random, sys
        sys.path.insert(0, {tests_dir!r})
        import jax
        assert len(jax.devices()) == 8, jax.devices()
        from test_fused_sourcing import random_cluster, _decision_key, WL3
        from repro.core import TopoScheduler

        rng = random.Random(7)
        for trial in range(5):
            seed = rng.randrange(10_000)
            nodes = rng.choice((5, 11, 13))   # 11/13 force node-axis padding
            seqs = {{}}
            for engine in ("imp_batched", "imp_sharded"):
                cluster = random_cluster(seed, nodes=nodes)
                sched = TopoScheduler(cluster, engine=engine)
                ops = random.Random(seed)
                seq = []
                for step in range(8):
                    wl = WL3[ops.choice("BCD")]
                    txn = sched.plan(wl, allow_normal=True)
                    seq.append(_decision_key(txn.decision))
                    if txn.decision.kind != "reject":
                        r = ops.random()
                        if r < 0.5:
                            txn.commit()
                        elif r < 0.75:
                            txn.commit()
                            txn.rollback()
                txns = sched.plan_batch(
                    [WL3[ops.choice("BC")] for _ in range(4)])
                seq.extend(_decision_key(t.decision) for t in txns)
                seqs[engine] = seq
            assert seqs["imp_batched"] == seqs["imp_sharded"], (seed, nodes)
            print("trial", trial, "seed", seed, "nodes", nodes, "ok")
        print("randomized sharded parity ok")
    """))


def test_sharded_engine_day_cycle_parity():
    """A short co-location day-cycle segment produces the identical hour
    rows under imp_sharded and imp_batched (same preemptions, same
    scheduled performance) on the 8-device mesh."""
    print(run_py("""
        import dataclasses
        import jax
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core.colocation import ColocationConfig, ColocationSim

        reports = {}
        for engine in ("imp_batched", "imp_sharded"):
            cfg = ColocationConfig(num_nodes=12, seed=5, engine=engine,
                                   horizon_hours=5.0)
            sim = ColocationSim(cfg)
            reports[engine] = sim.run()
        a, b = reports["imp_batched"], reports["imp_sharded"]
        assert len(a.hours) == len(b.hours)
        for ra, rb in zip(a.hours, b.hours):
            da, db = dataclasses.asdict(ra), dataclasses.asdict(rb)
            assert da == db, (da, db)
        assert a.preemptions == b.preemptions
        assert a.scheduled_perf == b.scheduled_perf
        print("day-cycle parity ok:", a.preemptions, "preemptions,",
              len(a.hours), "hours")
    """))


def test_sharded_state_layout_and_scatter():
    """The sharded cluster state pads the node axis to the mesh size,
    spreads every stacked tensor across all 8 devices, and keeps the
    sharding stable through delta syncs (scatter) and full rebuilds."""
    print(run_py("""
        import jax
        assert len(jax.devices()) == 8, jax.devices()
        from repro.core import Cluster, RTX4090_SERVER, table3_workloads
        from repro.core.cluster import cluster_mesh
        from repro.core.simulator import SimConfig, build_saturated_cluster

        cluster = build_saturated_cluster(SimConfig(num_nodes=13, seed=2))
        dcs = cluster.device_state(sharded=True)
        assert dcs.mesh is cluster_mesh()
        dcs.sync()
        assert dcs.n_rows == 16 and dcs.nodestate.shape[1] == 16
        for name in ("nodestate", "victims", "drain"):
            arr = getattr(dcs, name)
            devs = {s.device.id for s in arr.addressable_shards}
            assert len(devs) == 8, (name, devs)
        before = dcs.nodestate.sharding
        # delta path: evict one instance -> dirty row -> scatter
        uid = next(iter(cluster.instances))
        cluster.evict(uid)
        dcs.sync()
        assert dcs.nodestate.sharding == before, dcs.nodestate.sharding
        # full-rebuild path (majority-dirty fallback) keeps the layout too
        dcs._dirty.update(range(cluster.num_nodes))
        dcs.sync()
        assert dcs.nodestate.sharding == before, dcs.nodestate.sharding
        print("sharded layout stable across scatter + rebuild")
    """))
