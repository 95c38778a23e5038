"""The main segment's build: each device's share of a mesh layout is mapped,
packed and placed on that device alone, the posting segments stay in host
memory, and the served answers are those of the one-device build."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import CFG, assert_scores_match, unit_factors

from repro.core.inverted_index import build_segment, csr_to_table
from repro.core.mapping import sparse_map
from repro.kernels.gam_retrieve import build_retrieval_meta
from repro.obs.tracing import Tracer
from repro.retriever import RetrieverSpec, open_retriever
from repro.service.sharded_index import MAP_CHUNK_ROWS, ShardedGamIndex

needs_four = pytest.mark.skipif(
    jax.device_count() < 4,
    reason="needs 4 devices (XLA_FLAGS host platform count)")

_STATE = ("item_bits_t", "block_union", "block_spill", "spill8")


def _mesh(n=4):
    from repro.launch.mesh import make_index_mesh

    return make_index_mesh(n)


def _patterns(x):
    tau, vals = sparse_map(jnp.asarray(x), CFG)
    return tau, jnp.asarray(np.asarray(vals) != 0.0)


def _state(idx) -> dict:
    """The kernel state of a single-group index, gathered to the host."""
    meta = idx.metas[0]
    names = _STATE + (("factors_q", "scales") if meta.quantize == "int8"
                      else ())
    out = {n: np.asarray(getattr(meta, n)) for n in names}
    out["factors"] = np.asarray(idx.factors_g[0])
    out["alive"] = np.asarray(idx.alive_g[0])
    return out


def test_one_device_build_gives_the_whole_layout_meta():
    """The one-device build's kernel state is the whole flat layout's, as
    ``build_retrieval_meta`` packs it (the uniform cut pads at the tail)."""
    items = unit_factors(700, 16, 60)
    idx = ShardedGamIndex.build(items, CFG, n_shards=3, min_overlap=2,
                                bucket=512)
    tau, mask = _patterns(items)
    want = build_retrieval_meta(np.asarray(tau), np.asarray(mask), CFG.p,
                                n_rows=idx.partition.n_rows,
                                bn=idx.partition.bns[0])
    for name in _STATE:
        np.testing.assert_array_equal(np.asarray(getattr(idx.meta, name)),
                                      np.asarray(getattr(want, name)))
    # the host mirror is the kernel's (words, rows) layout, never read back
    np.testing.assert_array_equal(idx._bits_host,
                                  np.asarray(want.item_bits_t)[None])


@needs_four
@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_four_device_build_matches_one_device_bit_for_bit(quantize):
    """Built on four devices, the kernel state gathered back is the
    one-device build's (padded to whole blocks per device), and after
    upserts, deletes and a compaction the answers equal the one-device
    service's and the dense posting-table oracle's."""
    items = unit_factors(1500, 16, 61)
    users = unit_factors(9, 16, 62)
    spec = RetrieverSpec(cfg=CFG, backend="sharded", n_shards=4,
                         min_overlap=2, kappa=10, bucket=512,
                         quantize=quantize)
    mesh = _mesh()
    svcs = [open_retriever(spec, items=items, mesh=mesh),
            open_retriever(spec, items=items)]
    one = ShardedGamIndex.build(items, CFG, n_shards=4, min_overlap=2,
                                bucket=512, quantize=quantize,
                                partition=svcs[0].base.partition)
    got, want = _state(svcs[0].base), _state(one)
    for name in want:
        np.testing.assert_array_equal(got[name], want[name], err_msg=name)
    # the host mirror holds the build's own block for each device, which
    # side by side are the one-device layout
    mirror = svcs[0].base._bits_host
    assert mirror.shape[0] == 4 and mirror.flags.c_contiguous
    np.testing.assert_array_equal(np.concatenate(list(mirror), axis=1),
                                  one._bits_host[0])
    for svc in svcs:
        svc.upsert([3, 700, 4000], unit_factors(3, 16, 63))
        svc.delete([11, 1499])
    for stage in ("mutated", "compacted"):
        if stage == "compacted":
            for svc in svcs:
                svc.compact(async_=True)
                while svc.maintenance_stats()["compaction"]["active"]:
                    svc.compaction_step()
        assert len(svcs[0].base._launch_units(0)) == 4
        for exact in (False, True):
            a, b = (s.query(users, 10, exact=exact) for s in svcs)
            np.testing.assert_array_equal(a.ids, b.ids)
            np.testing.assert_array_equal(a.scores, b.scores)
        base = svcs[0].base
        tau, q_mask = _patterns(users)
        kern = base.query(jnp.asarray(users), tau, q_mask, 10)
        dense = base.query_dense_reference(jnp.asarray(users), tau, q_mask,
                                           10)
        if quantize == "none":
            np.testing.assert_array_equal(kern.rows, dense.rows)
            real = np.asarray(dense.scores) > -1e37
            assert_scores_match(kern.scores[real],
                                np.asarray(dense.scores)[real])
        np.testing.assert_array_equal(kern.shard_candidates,
                                      dense.shard_candidates)


class _Puts:
    """Every array put on a device while recording: the shape each device
    received."""

    def __init__(self, monkeypatch):
        self.shapes = []
        for mod, name in ((jax, "device_put"), (jnp, "asarray"),
                          (jnp, "array")):
            monkeypatch.setattr(mod, name, self._wrap(getattr(mod, name)))

    def _wrap(self, fn):
        def put(*a, **kw):
            out = fn(*a, **kw)
            for leaf in jax.tree.leaves(out):
                if isinstance(leaf, jax.Array):
                    self.shapes += [(sh.device, sh.data.shape)
                                    for sh in leaf.addressable_shards]
            return out
        return put


@needs_four
@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_four_device_build_puts_no_device_more_than_its_share(
        monkeypatch, quantize):
    """No device receives a row-axis array longer than its share of the
    rows plus one block, however the build sends it: the map's chunks,
    the packed patterns and every other piece of kernel state."""
    items = unit_factors(3000, 16, 64)
    puts = _Puts(monkeypatch)
    idx = ShardedGamIndex.build(items, CFG, n_shards=4, min_overlap=2,
                                bucket=512, mesh=_mesh(), quantize=quantize)
    share = idx.partition.n_rows // 4
    bn = idx.partition.bns[0]
    assert share < len(items) and puts.shapes
    assert {d for d, _ in puts.shapes} == set(_mesh().devices.flat)
    for dev, shape in puts.shapes:
        assert max(shape) <= share + bn, (dev, shape)
    # and what each device holds is its share alone
    for arr, axis in ((idx.factors_g[0], 0), (idx.metas[0].item_bits_t, 1)):
        assert {sh.data.shape[axis] for sh in arr.addressable_shards} \
            == {share}


def test_map_chunks_are_bounded(monkeypatch):
    """A catalog longer than a map chunk is mapped a chunk at a time."""
    import repro.service.sharded_index as si

    monkeypatch.setattr(si, "MAP_CHUNK_ROWS", 128)
    items = unit_factors(1000, 16, 65)
    puts = _Puts(monkeypatch)
    tau, mask = si.map_catalog(items, CFG, [(None, 0, 1000)])
    chunks = [s for _, s in puts.shapes if s == (128, 16)]
    assert len(chunks) == 8 and MAP_CHUNK_ROWS >= 1 << 16
    want_tau, want_mask = _patterns(items)
    want_mask = np.asarray(want_mask)
    np.testing.assert_array_equal(mask, want_mask)
    np.testing.assert_array_equal(tau[mask], np.asarray(want_tau)[want_mask])


@pytest.mark.parametrize("n_devices", [1, 4])
def test_posting_tables_stay_on_the_host(n_devices):
    """The posting segments (one CSR pair a shard), ``counts`` and
    ``spills`` are host arrays, and the posting load is the one the
    device-resident tables gave."""
    if jax.device_count() < n_devices:
        pytest.skip("needs 4 devices (XLA_FLAGS host platform count)")
    items = unit_factors(900, 16, 66)
    idx = ShardedGamIndex.build(items, CFG, n_shards=3, min_overlap=1,
                                bucket=64,
                                mesh=_mesh(n_devices) if n_devices > 1
                                else None)
    assert len(idx.postings) == 3
    for arr in (*(a for pair in idx.postings for a in pair), idx.counts,
                idx.spills):
        assert type(arr) is np.ndarray
    assert idx.counts.shape == (3, CFG.p)
    assert idx.spills.shape[1] > 0             # bucket 64 spills rows
    load = idx.posting_load()
    np.testing.assert_array_equal(
        load, np.asarray(jnp.sum(jnp.asarray(idx.counts), axis=-1)))
    assert load.dtype == np.int32
    tau, mask = _patterns(items)
    part = idx.partition
    want = [int(np.minimum(np.bincount(
        np.asarray(tau)[part.starts[s]:part.starts[s] + part.lengths[s]][
            np.asarray(mask)[part.starts[s]:part.starts[s]
                             + part.lengths[s]]], minlength=CFG.p),
        64).sum()) for s in range(3)]
    assert load.tolist() == want
    # the build keeps the segments as CSR; laid out, they are
    # build_segment's dense tables bit for bit
    for s in range(3):
        lo, hi = part.starts[s], part.starts[s] + part.lengths[s]
        table, counts, spill = build_segment(
            np.asarray(tau)[lo:hi], CFG.p, 64, np.asarray(mask)[lo:hi],
            sentinel=part.caps[s])
        post, off = idx.postings[s]
        got, got_counts = csr_to_table(post, off, 64, sentinel=part.caps[s])
        np.testing.assert_array_equal(got, table)
        np.testing.assert_array_equal(got_counts, counts)
        np.testing.assert_array_equal(idx.counts[s], counts)
        np.testing.assert_array_equal(idx.spills[s][:spill.size], spill)


@pytest.mark.parametrize("n_devices", [1, 4])
def test_build_and_launch_spans(n_devices):
    """The build is timed under ``build`` (map, pack and place per device,
    with ``device``, ``rows`` and ``bytes``; the posting segments), and a
    query opens one ``launch`` span per device enqueue."""
    if jax.device_count() < n_devices:
        pytest.skip("needs 4 devices (XLA_FLAGS host platform count)")
    mesh = _mesh(n_devices) if n_devices > 1 else None
    tracer = Tracer()
    items = unit_factors(1000, 16, 67)
    idx = ShardedGamIndex.build(items, CFG, n_shards=4, min_overlap=2,
                                bucket=512, mesh=mesh, tracer=tracer)
    [root] = tracer.finished
    assert root.name == "build" and root.attrs["rows"] == 1000
    kids = [c.name for c in root.children]
    assert kids.count("build_postings") == 1
    ids = set()
    for name in ("build_map", "build_pack", "build_place"):
        spans = root.find(name)
        assert len(spans) == n_devices, name
        assert sum(s.attrs["rows"] for s in spans) == (
            1000 if name == "build_map" else idx.partition.n_rows)
        assert all(s.attrs["bytes"] > 0 and s.t1 >= s.t0 for s in spans)
        ids |= {s.attrs["device"] for s in spans}
    assert len(ids) == n_devices
    users = unit_factors(5, 16, 68)
    tau, q_mask = _patterns(users)
    with tracer.trace("q"):
        idx.query(jnp.asarray(users), tau, q_mask, 10, tracer=tracer)
    q = tracer.finished[-1]
    launches = q.find("launch")
    assert len(launches) == n_devices
    assert {s.attrs["device"] for s in launches} == ids
    assert sum(s.attrs["rows"] for s in launches) == idx.partition.n_rows
    for s in launches:
        assert [c.name for c in s.children] == ["gam_retrieve"]
    assert len(q.find("group_merge")) == (1 if n_devices > 1 else 0)


def test_a_restored_index_reads_its_mirror_back_writable():
    """An index assembled from device arrays (a restore, a carved slice)
    reads the packed patterns back into a mirror kill() can write."""
    items = unit_factors(300, 16, 69)
    idx = ShardedGamIndex.build(items, CFG, n_shards=2, min_overlap=1,
                                bucket=512)
    again = ShardedGamIndex(
        idx.cfg, idx.item_ids, idx.postings, idx.counts, idx.spills,
        idx.factors_g[0], idx._alive_host.copy(), idx.partition,
        idx.min_overlap, idx.bucket, None,
        [dataclasses.replace(idx.meta)])
    again.kill([0, 299])
    assert not again._bits_host[0][:, [0, 299]].any()
    np.testing.assert_array_equal(np.asarray(again.meta.item_bits_t),
                                  again._bits_host[0])


@pytest.mark.parametrize("layout", ["csr", "varint", "dense"])
def test_a_snapshot_restores_the_posting_segments(layout, tmp_path):
    """A snapshot stores the shards' CSR posting segments as one stream
    (as is, or varint-encoded under ``compress_postings``); a file holding
    the dense (S, p, bucket) tables of earlier writers reads back to the
    same segments.  Each restore answers as the index it was taken from."""
    from repro.checkpoint import load_arrays, save_arrays

    items = unit_factors(800, 16, 70)
    users = unit_factors(7, 16, 71)
    spec = RetrieverSpec(cfg=CFG, backend="sharded", n_shards=3,
                         min_overlap=1, bucket=64,
                         compress_postings=layout == "varint")
    ret = open_retriever(spec, items=items)
    ret.delete([5, 400])
    path = str(tmp_path / "snap.npz")
    ret.snapshot(path)
    arrays, header = load_arrays(path)
    assert ("base_tables_data" in arrays) == (layout == "varint")
    assert ("base_postings" in arrays) == (layout != "varint")
    assert "base_tables" not in arrays
    base = ret.base
    if layout == "dense":
        arrays["base_tables"] = np.stack([
            csr_to_table(post, off, 64, sentinel=base.partition.caps[s])[0]
            for s, (post, off) in enumerate(base.postings)])
        del arrays["base_postings"]
        save_arrays(path, arrays, header)
    back = open_retriever(spec, snapshot=path)
    for (p0, o0), (p1, o1) in zip(base.postings, back.base.postings,
                                  strict=True):
        np.testing.assert_array_equal(p1, p0)
        np.testing.assert_array_equal(o1, o0)
        assert p1.dtype == np.int32
    np.testing.assert_array_equal(back.base.counts, base.counts)
    want, got = ret.query(users, 10), back.query(users, 10)
    np.testing.assert_array_equal(got.ids, want.ids)
    np.testing.assert_array_equal(got.scores, want.scores)
