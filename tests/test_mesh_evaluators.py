"""One program family for every device layout: each factory of the device
programs, built with ``mesh=None`` and over `cluster_mesh()` (one device by
default, eight under ``--xla_force_host_platform_device_count=8``), gives
identical results on one seeded state under the names ``jit_<name>`` and
``jit_<name>_sharded``.

The state is a saturated 33-node cluster, so the mesh side's node axis pads
whenever the mesh has more than one device; a plan's arguments carry real
view-delta rows (planned evictions and a planned bind)."""
from types import SimpleNamespace

import numpy as np
import pytest

from repro.core import preemption_jax as pj
from repro.core.cluster import (DeviceClusterState, ViewDelta, _scatter_rows,
                                cluster_mesh, delta_encoder, encode_row,
                                pack_bits, pack_scatter, pad_idx)
from repro.core.placement import Placement
from repro.core.simulator import SimConfig, build_saturated_cluster
from repro.core.workload import table3_workloads

N = 33
ALPHA = 0.5
WL = {w.name: w for w in table3_workloads()}


@pytest.fixture(scope="module")
def world():
    cluster = build_saturated_cluster(SimConfig(num_nodes=N, seed=5))
    states = [DeviceClusterState(cluster).sync(),
              DeviceClusterState(cluster, mesh=cluster_mesh()).sync()]
    spec, wl = cluster.spec, WL["B"]
    view = cluster.view()
    for node in range(N):
        victims = view.victims_on(node, wl.priority)
        if victims:
            view.plan_evict(victims[0].uid)
        if len(view.delta_nodes()) == 3:
            break
    # a planned bind into the freed room of the last eviction
    v = victims[0]
    view.plan_bind(WL["D"], node, Placement(v.gpu_mask & -v.gpu_mask,
                                            v.cg_mask & -v.cg_mask, 0))
    ctx = cluster.sourcing_context()
    patches = {d: encode_row(view, d, ctx.cap)
               for d in sorted(view.delta_nodes())}
    reqs = [(w.priority,) + pj._req_scalars(spec, w)
            for w in (WL["B"], WL["C"])]
    return SimpleNamespace(
        cluster=cluster, states=states, spec=spec, view=view, ctx=ctx,
        patches=patches, pa=pj._pack_patches(patches, ctx.cap),
        delta=pad_idx(sorted(patches), 4), gidx=pad_idx([2, 3], 4),
        req=np.asarray(reqs[0], np.int32),
        batch=[np.asarray(col, np.int32) for col in zip(*reqs)])


def _res(dcs):
    return dcs.nodestate, dcs.victims, dcs.drain


def _resident(mesh, dcs, w):
    return (pj.resident_evaluator(mesh, w.spec, pj.MIN_M, w.pa.p,
                                  len(w.gidx), ALPHA),
            (*_res(dcs), pj._plan_aux(w.pa, w.gidx), w.req))


def _plan2(mesh, dcs, w):
    return (pj.plan_evaluator(mesh, w.spec, pj.MIN_M, w.pa.p, len(w.gidx),
                              ALPHA),
            (*_res(dcs), pj._plan_aux(w.pa, w.gidx), w.req))


def _normal(mesh, dcs, w):
    return (pj.normal_evaluator(mesh, w.spec, w.pa.p),
            (dcs.nodestate, pj._plan_aux(w.pa), w.req))


def _gathered(mesh, dcs, w):
    rows = pad_idx(list(range(0, N, 3)), 4)
    return (pj.gathered_evaluator(mesh, w.spec, pj.NARROW_M, w.pa.p, ALPHA),
            (*_res(dcs), pj._plan_aux(w.pa, rows), w.req))


def _shortlist_args(dcs, w):
    fidx = pad_idx(pj._forced_rows(dcs, w.patches), 4)
    return len(fidx), pj._plan_aux(w.pa, fidx, pack_bits(dcs.rep_classes()))


def _shortlist(mesh, dcs, w):
    f, aux = _shortlist_args(dcs, w)
    return (pj.shortlist_evaluator(mesh, w.spec, 8, w.pa.p, f, ALPHA),
            (*_res(dcs), aux, w.req))


def _shortlist_plan(mesh, dcs, w):
    f, aux = _shortlist_args(dcs, w)
    return (pj.shortlist_plan_evaluator(mesh, w.spec, 8, w.pa.p, f, ALPHA),
            (*_res(dcs), aux, w.req))


def _batch_class(mesh, dcs, w):
    return (pj.batch_class_evaluator(mesh, w.spec, pj.MIN_M, ALPHA),
            (*_res(dcs), *w.batch))


def _batch_args(mesh, dcs, w):
    """A batch session's per-plan arguments: request 0's class tensors,
    its delta rows masked out and gathered back, patched."""
    cls, args = _batch_class(mesh, dcs, w)
    aux = np.concatenate([w.delta, w.pa.idx, w.delta])
    return (*cls(*args), *_res(dcs), np.int32(0), aux, w.pa.host, w.req)


def _batch_merge(mesh, dcs, w):
    return (pj.batch_merge_evaluator(mesh, w.spec, pj.NARROW_M,
                                     len(w.delta), len(w.delta), ALPHA),
            _batch_args(mesh, dcs, w))


def _batch_plan(mesh, dcs, w):
    return (pj.batch_plan_evaluator(mesh, w.spec, pj.NARROW_M, len(w.delta),
                                    len(w.delta), w.pa.p, ALPHA),
            _batch_args(mesh, dcs, w))


def _apply_rows(mesh, dcs, w):
    return (_scatter_rows(mesh),
            (*_res(dcs), pack_scatter(w.pa.idx, w.pa.host)))


def _delta_encode(mesh, dcs, w):
    vd = ViewDelta(w.view, w.ctx, dcs.pending)
    assert len(vd.dense) and vd.addv.any()
    didx = pad_idx(vd.dense)
    descs = [np.pad(x, ((0, len(didx) - len(x)),) + ((0, 0),) * (x.ndim - 1))
             for x in (vd.rem, vd.fog, vd.foc, vd.og, vd.oc, vd.addg,
                       vd.addc, vd.addp, vd.addv)]
    return (delta_encoder(mesh, dcs.cap, vd.a),
            (dcs.nodestate, dcs.victims, didx, *descs))


#: program name -> (builder, positions of the result holding a row of the
#: resident node axis, where rows past it index the gathered section)
CASES = {
    "resident_sweep": (_resident, (1,)),
    "plan2": (_plan2, (6,)),
    "normal_cycle": (_normal, ()),
    "gathered": (_gathered, ()),
    "shortlist": (_shortlist, ()),
    "shortlist_plan": (_shortlist_plan, ()),
    "batch_class": (_batch_class, ()),
    "batch_merge": (_batch_merge, (1,)),
    "batch_plan": (_batch_plan, (6,)),
    "apply_rows": (_apply_rows, ()),
    "delta_encode": (_delta_encode, ()),
}


def _host(out, n_rows: int, rows) -> list[np.ndarray]:
    """A result independent of the node axis' padding: stacked tensors
    cut to the cluster's rows, gathered-section rows renumbered from the
    unpadded axis length."""
    if isinstance(out, tuple):          # ClassWinners, the scattered state
        return [np.asarray(a)[:, :N] for a in out]
    vals = np.asarray(out).copy()
    for i in rows:
        if vals[i] >= n_rows:
            vals[i] += N - n_rows
    return [vals]


@pytest.mark.parametrize("name", list(CASES))
def test_program_is_the_same_for_every_mesh(world, name):
    build, rows = CASES[name]
    got = []
    for dcs in world.states:
        d = 1 if dcs.mesh is None else dcs.mesh.size
        assert dcs.n_rows == -(-N // d) * d
        prog, args = build(dcs.mesh, dcs, world)
        want = name if dcs.mesh is None else f"{name}_sharded"
        assert prog.name == want
        assert f"module @jit_{want} " in prog.lower(*args).as_text()
        got.append(_host(prog(*args), dcs.n_rows, rows))
    plain, sharded = got
    assert len(plain) == len(sharded)
    for a, b in zip(plain, sharded):
        assert np.array_equal(a, b)
    if name in ("resident_sweep", "plan2", "batch_plan"):
        assert plain[0][rows[0] - 1], "the plan found no winner"
