"""A plan's arguments in one host-to-device transfer: the host packer
(`preemption_jax._plan_aux`, `cluster.pack_bits`, `cluster.pack_scatter`)
against the device slicers (`_split_aux`, `_rep_mask`,
`apply_packed_rows`), on random contents, compared with the separate
arrays they replace."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import preemption_jax as pj
from repro.core.cluster import (IDX_SENTINEL, DeviceClusterState,
                                apply_packed_rows, apply_rows, cluster_mesh,
                                pack_bits, pack_scatter, row_width)
from repro.core.simulator import SimConfig, build_saturated_cluster

CAP = 16
WIDTH = row_width(CAP)


def _rows(rng, q: int) -> np.ndarray:
    return rng.integers(-2**31, 2**31 - 1, (q, WIDTH), dtype=np.int32)


def _idx(rng, q: int, n: int) -> np.ndarray:
    """``q`` row indices whose last quarter is sentinel padding, as
    `cluster.pad_idx` leaves a bucket."""
    idx = rng.choice(n, q, replace=False).astype(np.int32)
    idx[q - q // 4:] = IDX_SENTINEL
    return idx


@pytest.mark.parametrize("s", [0, 4])
@pytest.mark.parametrize("p,dev_rows", [(0, 0), (4, 0), (4, 4), (256, 0),
                                        (256, 4)])
def test_packed_plan_args_slice_back_to_their_arrays(p, dev_rows, s):
    """``[pidx | sel | pbuf | rep words]`` crosses as ONE int32 buffer and
    the static slices give back each array; rows already on the device
    (``dev_rows`` of the ``p``: a view's encoded rows) are spliced in
    ahead of the host rows.  The rep mask (1,001 rows: not a multiple of
    32) unpacks bit for bit."""
    rng = np.random.default_rng(1000 * p + 10 * s + dev_rows)
    n_rows = 1001
    pidx = _idx(rng, p, n_rows)
    sel = _idx(rng, s, n_rows)
    pbuf = _rows(rng, p)
    mask = rng.random(n_rows) < 0.3
    dev = jnp.asarray(pbuf[:dev_rows]) if dev_rows else None
    pa = pj.PatchArgs(p, pidx, pbuf[dev_rows:], dev)
    aux = pj._plan_aux(pa, sel, pack_bits(mask))
    assert aux.shape == (p + s + p * WIDTH + -(-n_rows // 32),)

    @jax.jit
    def unpack(aux):
        i, g, buf, words = pj._split_aux(aux, p, s, WIDTH)
        return i, g, buf, pj._rep_mask(words, n_rows)

    i, g, buf, rep = unpack(aux)
    assert np.array_equal(np.asarray(i), pidx)
    assert np.array_equal(np.asarray(g), sel)
    assert np.array_equal(np.asarray(buf), pbuf)
    assert np.array_equal(np.asarray(rep), mask)


@pytest.mark.parametrize("n", [1, 31, 32, 33, 1001, 10240])
def test_pack_bits_is_little_endian_words_padded_with_zero(n):
    rng = np.random.default_rng(n)
    mask = rng.random(n) < 0.5
    mask[-1] = True                  # the last bit lands in the last word
    words = pack_bits(mask)
    assert words.dtype == np.int32 and words.shape == (-(-n // 32),)
    u = words.view(np.uint32)
    i = np.arange(len(u) * 32)
    bits = (u[i >> 5] >> (i & 31).astype(np.uint32)) & 1
    assert np.array_equal(bits[:n].astype(bool), mask)
    assert not bits[n:].any()
    assert np.array_equal(np.asarray(pj._rep_mask(jnp.asarray(words), n)),
                          mask)


@pytest.mark.parametrize("q", [4, 256])
def test_packed_scatter_equals_the_two_array_scatter(q):
    """The sync's dirty-row scatter: indices and rows in one buffer."""
    rng = np.random.default_rng(q)
    n = 300
    ns = rng.integers(0, 2**20, (5, n), dtype=np.int32)
    v = rng.integers(0, 2**20, (5, n, CAP), dtype=np.int32)
    dr = rng.integers(0, 2**20, (2, n), dtype=np.int32)
    idx = _idx(rng, q, n)
    buf = _rows(rng, q)
    want = apply_rows(jnp.asarray(ns), jnp.asarray(v), jnp.asarray(dr),
                      jnp.asarray(idx), jnp.asarray(buf))
    got = jax.jit(apply_packed_rows)(ns, v, dr, pack_scatter(idx, buf))
    for a, b in zip(got, want):
        assert np.array_equal(np.asarray(a), np.asarray(b))


class _PaddedSharded(DeviceClusterState):
    """A state over a one-device mesh whose node axis pads to a multiple
    of four, as it does on a four-device mesh."""

    @property
    def n_rows(self) -> int:
        return -(-max(self.cluster.num_nodes, 1) // 4) * 4


@pytest.mark.parametrize("state", ["device", "sharded_padded"])
def test_plan_upload_carries_the_host_rep_mask(state):
    """The rep bits a shortlist plan uploads, unpacked on the device in
    its own program's layout, equal `rep_classes()`'s host mask, and the
    padding rows past ``num_nodes`` stay False (33 nodes: the padded axis
    is 36 rows long, the words cover 64)."""
    cluster = build_saturated_cluster(SimConfig(num_nodes=33, seed=4))
    if state == "sharded_padded":
        cluster._device_state = _PaddedSharded(
            cluster, mesh=cluster_mesh(jax.devices()[:1]))
    dcs = cluster.device_state(sharded=state != "device").sync()
    # a commit leaves pending rows, which ride ahead of the mask words
    inst = next(i for i in cluster.instances.values() if i.preemptible)
    cluster.evict(inst.uid)
    dcs.sync(flush=False)
    pa = pj._patch_args(dcs, {})
    assert pa.p > 0
    rep = dcs.rep_classes()
    assert rep.shape == (dcs.n_rows,)
    assert not rep[cluster.num_nodes:].any()
    _, f, aux = pj._shortlist_plan_args(dcs, {}, 1000, pa)
    i, _, buf, words = pj._split_aux(aux, pa.p, f, WIDTH)
    assert np.array_equal(np.asarray(i), pa.idx)
    assert np.array_equal(np.asarray(buf), pa.host)
    got = np.asarray(pj._rep_mask(words, dcs.n_rows,
                                  pj._rep_sharding(dcs.mesh)))
    assert np.array_equal(got, rep)
