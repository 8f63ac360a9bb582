import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this lowers the real train/prefill/decode step with the
production shardings onto the single-pod (16,16) and multi-pod (2,16,16)
meshes, compiles it, and records memory analysis, cost analysis, and the
collective schedule (parsed from the post-SPMD HLO) into
results/dryrun/<cell>.json (+ gzipped HLO for offline analysis).

Resumable: existing result files are skipped unless --force.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --all
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k --mesh single
"""
import argparse
import gzip
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import sharding as shd
from repro.configs import (ARCH_IDS, SHAPES, batch_specs, cache_capacity,
                           decode_specs, get_config, shape_applicable)
from repro.launch import hlo as hlo_util
from repro.launch.mesh import make_production_mesh
from repro.models import build_model
from repro.train import TrainConfig, make_train_step, train_state_specs

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def _out_dir() -> str:
    d = os.path.abspath(os.environ.get("DRYRUN_DIR", RESULTS_DIR))
    os.makedirs(os.path.join(d, "hlo"), exist_ok=True)
    return d


def _cell_name(arch: str, shape: str, mesh_kind: str) -> str:
    return f"{arch}__{shape}__{mesh_kind}"


def _memory_dict(ma) -> dict:
    fields = ("generated_code_size_in_bytes", "argument_size_in_bytes",
              "output_size_in_bytes", "alias_size_in_bytes",
              "temp_size_in_bytes")
    return {f: int(getattr(ma, f, 0)) for f in fields}


def lower_cell(arch: str, shape_name: str, mesh, overrides=None):
    """Build and lower the step function for one cell.  Returns lowered."""
    import dataclasses as dc

    from repro.models.common import set_batch_axes

    cfg = get_config(arch)
    if overrides:
        cfg = dc.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    api = build_model(cfg)
    repl = NamedSharding(mesh, P())
    set_batch_axes(shd._batch_axes_for(mesh, shape.global_batch), mesh=mesh)
    try:
        with mesh:
            if shape.kind == "train":
                state_shape = train_state_specs(api)
                state_sh = shd.make_param_shardings(cfg, mesh, state_shape)
                bspec = batch_specs(cfg, shape)
                b_sh = shd.batch_sharding(mesh, bspec)
                # 4-way microbatch accumulation keeps the per-device scan-saved
                # residuals (L x B_loc x S x d) within v5e HBM for the 7-9B
                # archs (see EXPERIMENTS.md §Dry-run memory notes).
                accum = int(os.environ.get("DRYRUN_ACCUM", "4"))
                step = make_train_step(api, TrainConfig(accum_steps=accum))
                fn = jax.jit(step, in_shardings=(state_sh, b_sh),
                             out_shardings=(state_sh, repl),
                             donate_argnums=(0,))
                return fn.lower(state_shape, bspec)

            params_shape = jax.eval_shape(api.init, jax.random.PRNGKey(0))
            p_sh = shd.make_param_shardings(cfg, mesh, params_shape)
            cap = cache_capacity(cfg, shape.seq_len)
            bspec = batch_specs(cfg, shape)

            axes = shd._batch_axes_for(mesh, shape.global_batch)
            logits_sh = NamedSharding(
                mesh, P(axes if axes else None,
                        "model" if cfg.vocab % int(mesh.shape["model"]) == 0
                        else None))

            if shape.kind == "prefill":
                b_sh = shd.batch_sharding(mesh, bspec)
                cache_shape = jax.eval_shape(
                    lambda p, b: api.prefill(p, b, cap), params_shape,
                    bspec)[1]
                cache_sh = shd.make_cache_shardings(cfg, mesh, cache_shape)
                fn = jax.jit(lambda p, b: api.prefill(p, b, cap),
                             in_shardings=(p_sh, b_sh),
                             out_shardings=(logits_sh, cache_sh))
                return fn.lower(params_shape, bspec)

            # decode: cache specs from an abstract prefill
            cache_shape = jax.eval_shape(
                lambda p, b: api.prefill(p, b, cap), params_shape, bspec)[1]
            cache_sh = shd.make_cache_shardings(cfg, mesh, cache_shape)
            tok_spec, pos_spec = decode_specs(shape)
            tok_sh = NamedSharding(mesh, P(axes) if axes else P())
            fn = jax.jit(api.decode_step,
                         in_shardings=(p_sh, cache_sh, tok_sh, repl),
                         out_shardings=(logits_sh, cache_sh),
                         donate_argnums=(1,))
            return fn.lower(params_shape, cache_shape, tok_spec, pos_spec)
    finally:
        set_batch_axes(None)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: str,
             force: bool = False, save_hlo: bool = True) -> dict:
    cell = _cell_name(arch, shape_name, mesh_kind)
    path = os.path.join(out_dir, cell + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {
        "cell": cell, "arch": arch, "shape": shape_name, "mesh": mesh_kind,
        "seq_len": shape.seq_len, "global_batch": shape.global_batch,
        "kind": shape.kind,
    }
    if not ok:
        rec.update(status="skipped", reason=why)
        _write(path, rec)
        return rec
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        t0 = time.time()
        lowered = lower_cell(arch, shape_name, mesh)
        t1 = time.time()
        compiled = lowered.compile()
        t2 = time.time()
        ca = compiled.cost_analysis()
        ma = compiled.memory_analysis()
        txt = compiled.as_text()
        rec.update(
            status="ok",
            lower_s=round(t1 - t0, 2),
            compile_s=round(t2 - t1, 2),
            devices=int(jnp.prod(jnp.array(list(mesh.shape.values())))),
            memory=_memory_dict(ma),
            cost={k: float(ca[k]) for k in ("flops", "bytes accessed",
                                            "optimal_seconds") if k in ca},
            hlo=hlo_util.summarize(txt),
        )
        if save_hlo:
            with gzip.open(os.path.join(out_dir, "hlo", cell + ".txt.gz"),
                           "wt") as f:
                f.write(txt)
        print(compiled.memory_analysis())
        print({k: rec["cost"].get(k) for k in ("flops", "bytes accessed")})
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _write(path, rec)
    return rec


def _write(path: str, rec: dict) -> None:
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(rec, f, indent=1)
    os.replace(tmp, path)


def run_scheduler_cell(mesh_kind: str, out_dir: str, force: bool = False) -> dict:
    """Dry-run the programs ``imp_sharded`` dispatches, over the mesh.

    Lowers and compiles, at 65,536 resident nodes sharded over the
    production mesh, the shortlisted chained plan (the record's top level),
    the full-sweep chained plan (``fused``) and the normal cycle
    (``normal_cycle``), each against a delta-free plan's arguments.
    """
    from repro.core import preemption_jax as pj
    from repro.core.cluster import (DRAIN_FIELDS, MAX_DENSE_VICTIMS,
                                    NODE_FIELDS, VICTIM_FIELDS,
                                    _mesh_shardings)
    from repro.core.topology import RTX4090_SERVER

    cell = _cell_name("scheduler-sourcing", "cluster64k", mesh_kind)
    path = os.path.join(out_dir, cell + ".json")
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)
    rec = {"cell": cell, "arch": "scheduler-sourcing", "shape": "cluster64k",
           "mesh": mesh_kind, "kind": "scheduler"}
    try:
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        node_sh, victim_sh, repl = _mesh_shardings(mesh)
        n, i32, spec, alpha = 65536, jnp.int32, RTX4090_SERVER, 0.5
        ns = jax.ShapeDtypeStruct((NODE_FIELDS, n), i32, sharding=node_sh)
        v = jax.ShapeDtypeStruct((VICTIM_FIELDS, n, MAX_DENSE_VICTIMS), i32,
                                 sharding=victim_sh)
        dr = jax.ShapeDtypeStruct((DRAIN_FIELDS, n), i32, sharding=node_sh)
        aux = jax.ShapeDtypeStruct((0,), i32, sharding=repl)
        words = jax.ShapeDtypeStruct((n // 32,), i32, sharding=repl)
        req = jax.ShapeDtypeStruct((4,), i32, sharding=repl)
        programs = (
            (None, pj.shortlist_plan_evaluator(mesh, spec, 128, 0, 0, alpha),
             (ns, v, dr, words, req)),
            ("fused", pj.plan_evaluator(mesh, spec, pj.NARROW_M, 0, 0, alpha),
             (ns, v, dr, aux, req)),
            ("normal_cycle", pj.normal_evaluator(mesh, spec, 0),
             (ns, aux, req)))
        for key, fn, args in programs:
            t0 = time.time()
            compiled = fn.lower(*args).compile()
            got = {"compile_s": round(time.time() - t0, 2),
                   "memory": _memory_dict(compiled.memory_analysis()),
                   "hlo": hlo_util.summarize(compiled.as_text())}
            if key is None:
                rec.update(status="ok", cost={
                    k: float(x) for k, x in compiled.cost_analysis().items()
                    if k in ("flops", "bytes accessed")}, **got)
            else:
                rec[key] = got
    except Exception as e:  # noqa: BLE001
        rec.update(status="error", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
    _write(path, rec)
    return rec


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=("single", "multi",
                                                       "both"))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--scheduler", action="store_true",
                    help="also dry-run the distributed scheduler sourcing")
    args = ap.parse_args()

    out_dir = _out_dir()
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    archs = ARCH_IDS if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]

    total = ok = 0
    for mesh_kind in meshes:
        for arch in archs:
            for shape in shapes:
                t0 = time.time()
                rec = run_cell(arch, shape, mesh_kind, out_dir,
                               force=args.force)
                total += 1
                ok += rec["status"] in ("ok", "skipped")
                print(f"[{rec['status']:>7}] {rec['cell']:58s} "
                      f"({time.time() - t0:6.1f}s)", flush=True)
        if args.scheduler or args.all:
            rec = run_scheduler_cell(mesh_kind, out_dir, force=args.force)
            total += 1
            ok += rec["status"] == "ok"
            print(f"[{rec['status']:>7}] {rec['cell']}", flush=True)
    print(f"dry-run: {ok}/{total} cells ok")
    if ok < total:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
