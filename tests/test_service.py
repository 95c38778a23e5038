"""Sharded streaming retrieval service: parity, streaming, microbatching
(tests for src/repro/service/ and the ``sharded`` retriever backend)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import CFG, assert_scores_match, unit_factors as _factors

from repro.core.inverted_index import build_segment
from repro.core.mapping import sparse_map
from repro.retriever import RetrieverSpec, open_retriever
from repro.service import (
    DeltaSegment,
    Microbatcher,
    Partition,
    Repartitioner,
    ServiceMetrics,
    ShardedGamIndex,
)


def _sharded(items, *, ids=None, n_shards=1, min_overlap=1, kappa=10,
             bucket=256, batch_size=8, max_delay_s=2e-3, **kw):
    spec = RetrieverSpec(cfg=CFG, backend="sharded", n_shards=n_shards,
                         min_overlap=min_overlap, kappa=kappa, bucket=bucket,
                         batch_size=batch_size, max_delay_s=max_delay_s)
    return open_retriever(spec, items=items, ids=ids, **kw)


def _gam_device(items, *, min_overlap=2, bucket=512):
    return open_retriever(
        RetrieverSpec(cfg=CFG, backend="gam-device", min_overlap=min_overlap,
                      bucket=bucket), items=items)


def _fresh_service(svc):
    """A retriever built from scratch over svc's current catalog."""
    ids = np.sort(np.fromiter(svc.catalog.keys(), np.int64, svc.n_items))
    fac = np.stack([svc.catalog[int(i)] for i in ids])
    return open_retriever(svc.spec, items=fac, ids=ids)


# ------------------------------------------------------- vectorised build


def _build_segment_reference(item_indices, p, bucket, mask):
    """The original sequential O(N*k) build, kept as the test oracle."""
    n = item_indices.shape[0]
    table = np.full((p, bucket), n, dtype=np.int32)
    counts = np.zeros(p, dtype=np.int32)
    spilled = set()
    for item in range(n):
        for slot in item_indices[item][mask[item]]:
            c = counts[slot]
            if c < bucket:
                table[slot, c] = item
            else:
                spilled.add(item)
            counts[slot] = c + 1
    spill = np.fromiter(sorted(spilled), dtype=np.int32, count=len(spilled))
    return table, np.minimum(counts, bucket).astype(np.int32), spill


@pytest.mark.parametrize("bucket", [4, 64])
def test_vectorised_segment_build_matches_sequential(bucket):
    items = _factors(300, 16, 0)
    tau, vals = sparse_map(jnp.asarray(items), CFG)
    tau, mask = np.asarray(tau), np.asarray(vals) != 0.0
    t_ref, c_ref, s_ref = _build_segment_reference(tau, CFG.p, bucket, mask)
    t_vec, c_vec, s_vec = build_segment(tau, CFG.p, bucket, mask)
    np.testing.assert_array_equal(t_vec, t_ref)
    np.testing.assert_array_equal(c_vec, c_ref)
    np.testing.assert_array_equal(s_vec, s_ref)


# ------------------------------------------------- vectorised device query


def test_gam_retriever_device_query_is_batched_and_consistent():
    """The gam-device query path (one fused kernel pass over the batch)
    agrees with the per-query CPU backend: identical candidate counts, and
    identical top-kappa up to float summation order in the scores."""
    items = _factors(400, 16, 1)
    users = _factors(20, 16, 2)
    cpu = open_retriever(
        RetrieverSpec(cfg=CFG, backend="gam", min_overlap=2), items=items)
    dev = _gam_device(items)
    r_cpu = cpu.query(users, 10)
    r_dev = dev.query(users, 10)
    np.testing.assert_array_equal(r_dev.n_scored, r_cpu.n_scored)
    for qi in range(20):
        c = set(r_cpu.ids[qi][r_cpu.ids[qi] >= 0].tolist())
        d = set(r_dev.ids[qi][r_dev.ids[qi] >= 0].tolist())
        assert len(c & d) >= 0.9 * len(c), (qi, c, d)
        for slot, iid in enumerate(r_dev.ids[qi]):
            if iid >= 0:
                np.testing.assert_allclose(
                    r_dev.scores[qi, slot], users[qi] @ items[iid], rtol=1e-4)


# ------------------------------------------------------- sharded parity


@pytest.mark.parametrize("n_shards", [2, 3])
def test_sharded_index_bit_identical_to_single_shard(n_shards):
    """Acceptance: multi-shard query returns the single-shard device
    retriever's top-kappa ids exactly, and its scores up to float summation
    order, on a fixed catalog.  n=350 is deliberately not divisible by 3
    (pad-row handling)."""
    items = _factors(350, 16, 3)
    users = _factors(16, 16, 4)
    r1 = _gam_device(items).query(users, 10)
    svc = _sharded(items, n_shards=n_shards, min_overlap=2, bucket=512)
    res = svc.query(users, 10)
    np.testing.assert_array_equal(res.ids, r1.ids)
    finite = np.isfinite(r1.scores)
    np.testing.assert_array_equal(finite, np.isfinite(res.scores))
    assert_scores_match(res.scores[finite], r1.scores[finite])


def test_sharded_exact_path_matches_brute_force():
    items = _factors(200, 16, 5)
    users = _factors(8, 16, 6)
    svc = _sharded(items, n_shards=2, kappa=7)
    res = svc.query(users, 7, exact=True)
    brute = open_retriever(RetrieverSpec(cfg=CFG, backend="brute"),
                           items=items).query(users, 7)
    np.testing.assert_array_equal(res.ids, brute.ids)


def test_sharded_spill_preserves_recall():
    """Tiny buckets force spill in every shard; spill rows stay candidates,
    so exact-match items are never lost."""
    items = _factors(300, 16, 7)
    svc = _sharded(items, n_shards=2, min_overlap=1, kappa=1, bucket=4)
    res = svc.query(items[:32], 1)          # query each item with itself
    assert (res.ids[:, 0] == np.arange(32)).all()


def test_shard_balance_and_posting_load(rng, cfg):
    items = rng.normal(size=(256, 16)).astype(np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    idx = ShardedGamIndex.build(items, cfg, n_shards=4, min_overlap=1)
    load = idx.posting_load()
    assert load.shape == (4,)
    assert load.sum() > 0
    # random catalog, contiguous partition: shards within 2x of each other
    assert load.max() <= 2 * max(load.min(), 1)


# ------------------------------------------------------- streaming delta


def test_upsert_then_query_matches_fresh_rebuild():
    """Acceptance: upsert-then-query == fresh-rebuild-then-query, exactly,
    both before and after compact()."""
    items = _factors(250, 16, 9)
    users = _factors(12, 16, 10)
    svc = _sharded(items, n_shards=2, min_overlap=2, kappa=10, bucket=512)
    # inserts, overwrites, deletes — interleaved
    svc.upsert([250, 251, 252], _factors(3, 16, 12))
    svc.delete([17, 99])
    svc.upsert([5, 250], _factors(2, 16, 13))    # overwrite base + delta rows
    res_a = svc.query(users, 10)

    fresh = _fresh_service(svc)
    res_f = fresh.query(users, 10)
    np.testing.assert_array_equal(res_a.ids, res_f.ids)
    np.testing.assert_array_equal(res_a.scores, res_f.scores)

    svc.compact()
    assert len(svc.delta) == 0
    res_c = svc.query(users, 10)
    np.testing.assert_array_equal(res_c.ids, res_f.ids)
    np.testing.assert_array_equal(res_c.scores, res_f.scores)


def test_delete_then_query_matches_fresh_rebuild():
    items = _factors(150, 16, 14)
    users = _factors(6, 16, 15)
    svc = _sharded(items, n_shards=3, min_overlap=1, kappa=8, bucket=512)
    svc.delete(np.arange(0, 150, 7))
    res_a = svc.query(users, 8)
    fresh = _fresh_service(svc)
    res_f = fresh.query(users, 8)
    np.testing.assert_array_equal(res_a.ids, res_f.ids)
    assert_scores_match(res_a.scores, res_f.scores)
    # deleted ids never appear
    assert not np.isin(res_a.ids, np.arange(0, 150, 7)).any()


def test_deleted_items_not_returned_even_as_self_query():
    items = _factors(60, 16, 16)
    svc = _sharded(items, min_overlap=1, kappa=60)
    svc.delete([3])
    res = svc.query(items[3:4], 60)
    assert 3 not in set(res.ids.ravel().tolist())


def test_kill_refreshes_block_metadata_so_skip_rate_survives_tombstones():
    """Regression (ROADMAP staleness bug): a kill-heavy stream must not
    erode the fused kernel's zero-candidate block-skip rate until compact().
    Tombstoning a whole pattern-coherent cluster makes its blocks skippable
    immediately — and the discard/parity contracts hold throughout."""
    rng = np.random.default_rng(28)
    nc, per = 8, 256                     # 8 clusters, 1 block each (bn=256)
    centers = rng.normal(size=(nc, 16)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    items = (np.repeat(centers, per, axis=0)
             + 0.03 * rng.normal(size=(nc * per, 16)).astype(np.float32))
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    users = (centers[0] + 0.03 * rng.normal(size=(6, 16))).astype(np.float32)
    users /= np.linalg.norm(users, axis=1, keepdims=True)

    svc = _sharded(items, n_shards=1, min_overlap=3, bucket=2048)
    res_before = svc.query(users, 10)
    skip_before = svc._last_query_stats["tiles_skipped_frac"]

    svc.delete(np.arange(per))           # tombstone the whole home cluster
    res_after = svc.query(users, 10)
    skip_after = svc._last_query_stats["tiles_skipped_frac"]

    # the freed block becomes skippable NOW, not only after compact()
    assert skip_after > skip_before, (skip_before, skip_after)
    # discarded_frac (vs the live set) must not degrade either
    assert (res_after.discarded_frac
            >= res_before.discarded_frac - 1e-9).all()
    # and the refresh never changes answers: parity with a fresh rebuild
    fresh = _fresh_service(svc)
    res_f = fresh.query(users, 10)
    np.testing.assert_array_equal(res_after.ids, res_f.ids)
    np.testing.assert_array_equal(res_after.scores, res_f.scores)


def test_upsert_duplicate_ids_in_one_batch_last_wins():
    items = _factors(30, 16, 23)
    svc = _sharded(items, n_shards=2, min_overlap=1, kappa=31)
    f = _factors(2, 16, 24)
    svc.upsert([40, 40], f)
    assert len(svc.delta) == 1
    np.testing.assert_array_equal(svc.delta.factors[0], f[1])
    res = svc.query(f[1:2], 31)
    assert (res.ids == 40).sum() == 1         # never returned twice
    res_f = _fresh_service(svc).query(f[1:2], 31)
    np.testing.assert_array_equal(res.ids, res_f.ids)


def test_delta_segment_rewrites_in_place():
    d = DeltaSegment(CFG, min_overlap=1)
    f1, f2 = _factors(2, 16, 17)
    d.upsert([7], f1[None])
    d.upsert([7], f2[None])                   # overwrite, not append
    assert len(d) == 1
    np.testing.assert_array_equal(d.factors[0], f2)
    d.delete([7])
    assert len(d) == 0


def test_delta_factor_capacity_is_shape_stable():
    """Consecutive upserts keep the device factor array in power-of-two
    capacity bands, so the jit'd scoring path doesn't recompile per
    mutation."""
    d = DeltaSegment(CFG, min_overlap=1)
    d.upsert([0, 1, 2], _factors(3, 16, 25))
    assert d._factors_dev.shape[0] == 4
    d.upsert([3], _factors(1, 16, 26))
    assert d._factors_dev.shape[0] == 4       # same shape: no recompile
    d.upsert([4], _factors(1, 16, 27))
    assert d._factors_dev.shape[0] == 8


# ------------------------------------------------------- microbatcher


def _manual_clock():
    t = [0.0]

    def clock():
        return t[0]

    return t, clock


def test_microbatcher_size_trigger_ordering_and_padding():
    """Short + full batches: every request gets ITS result (ordering) and
    pad rows never leak (padding)."""
    items = _factors(120, 16, 18)
    users = _factors(7, 16, 19)               # 7 requests, batch of 4
    t, clock = _manual_clock()
    svc = _sharded(items, n_shards=2, min_overlap=1, kappa=5, batch_size=4,
                   max_delay_s=0.01, clock=clock)
    ref = svc.query(users, 5)

    reqs = []
    for i in range(7):
        t[0] += 0.001
        reqs.append(svc.batcher.submit(users[i]))
    assert svc.batcher.pending == 3           # size trigger fired at 4
    assert not svc.batcher.poll()             # deadline not reached yet
    t[0] += 0.02
    assert svc.batcher.poll()                 # deadline trigger
    assert svc.batcher.pending == 0
    for i, rid in enumerate(reqs):
        res = svc.batcher.result(rid)
        assert res is not None
        np.testing.assert_array_equal(res.ids, ref.ids[i])
        np.testing.assert_array_equal(res.scores, ref.scores[i])
        assert res.latency_s >= 0.0
    assert svc.batcher.result(reqs[0]) is None    # popped exactly once
    # pad rows never pollute per-request stats: 7 requests -> 7 samples
    assert svc.metrics.discard_hist.n == 7


def test_microbatcher_latency_and_occupancy_metrics():
    t, clock = _manual_clock()
    metrics = ServiceMetrics(clock)

    def query_fn(users, n_real):
        t[0] += 0.004                          # 4ms of "device time"
        assert n_real == 1                     # pad rows flagged to callee
        b = users.shape[0]
        return np.zeros((b, 3), np.int64), np.zeros((b, 3), np.float32)

    mb = Microbatcher(query_fn, dim=4, batch_size=4, max_delay_s=0.01,
                      clock=clock, metrics=metrics)
    mb.submit(np.zeros(4))
    t[0] += 0.02
    mb.poll()
    snap = metrics.snapshot()
    assert snap["n_requests"] == 1 and snap["n_batches"] == 1
    assert snap["occupancy_mean"] == 0.25      # 1 of 4 slots
    # histogram percentiles carry ~2% bucketing error; the split must
    # decompose the total: 20ms queue wait + 4ms service = 24ms latency
    np.testing.assert_allclose(snap["latency_p50_ms"], 24.0, rtol=0.05)
    np.testing.assert_allclose(snap["queue_wait_p50_ms"], 20.0, rtol=0.05)
    np.testing.assert_allclose(snap["service_p50_ms"], 4.0, rtol=0.05)


# ------------------------------------------------------- property test


def test_delta_items_never_silently_dropped_property():
    """Property (hypothesis): after any upsert stream, every live item
    queried by its own factor is returned (the index never loses a delta
    item) and every deleted item is not."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    items = _factors(40, 16, 20)

    @settings(max_examples=15, deadline=None)
    @given(st.lists(st.tuples(st.integers(0, 47), st.integers(0, 2**31 - 1),
                              st.booleans()),
                    min_size=1, max_size=6))
    def check(ops):
        svc = _sharded(items, n_shards=2, min_overlap=1, kappa=48,
                       bucket=512)
        for iid, seed, is_delete in ops:
            if is_delete:
                svc.delete([iid])
            else:
                svc.upsert([iid], _factors(1, 16, seed))
        live = sorted(svc.catalog)
        fac = np.stack([svc.catalog[i] for i in live])
        res = svc.query(fac, 48)
        for row, iid in enumerate(live):
            assert iid in set(res.ids[row].tolist()), (iid, res.ids[row])
        dead = set(range(48)) - set(live)
        assert not (np.isin(res.ids, sorted(dead))).any()

    check()


# ------------------------------------------------- partition / repartitioner


def test_partition_uniform_reproduces_legacy_layout():
    """Partition.uniform is the pre-repartitioner arithmetic: one shared
    cap rounded to whole kernel blocks, ragged only at the tail, a single
    bn-group."""
    p = Partition.uniform(350, 3)
    assert p.lengths == (120, 120, 110)
    assert p.bns == (120, 120, 120) and p.caps == (120, 120, 120)
    assert p.groups == ((0, 3),) and p.n_rows == 360
    p0 = Partition.uniform(0, 2)
    assert p0.lengths == (0, 0) and p0.caps == (8, 8)


def test_partition_validation_is_loud():
    with pytest.raises(ValueError, match="multiple of 8"):
        Partition((10,), (12,), (12,))
    with pytest.raises(ValueError, match="multiple of bn"):
        Partition((10,), (8,), (12,))
    with pytest.raises(ValueError, match="one entry per shard"):
        Partition((10, 10), (8,), (16,))


def test_partition_padded_to_blocks_appends_dead_blocks_to_last_shard():
    part = Partition.from_lengths((100, 650, 250), (64, 64, 64))  # 17 blocks
    pad = part.padded_to_blocks(4)
    assert pad.lengths == part.lengths and pad.bns == part.bns
    assert pad.caps == (128, 704, 256 + 3 * 64)
    assert pad.padded_to_blocks(4) == pad == pad.padded_to_blocks(1)
    # several bn-groups are not mesh-placed: left as they are
    hetero = Partition.from_lengths((100, 650), (16, 64))
    assert hetero.padded_to_blocks(4) == hetero


def test_repartitioner_balances_weights_and_sizes_bn():
    """Heavy head of the catalog -> shorter head shards with narrower
    blocks; every shard carries ~equal total weight."""
    w = np.concatenate([np.full(200, 10.0), np.full(800, 1.0)])
    part = Repartitioner(target_blocks=8).plan(w, 4)
    assert part.n == 1000 and part.n_shards == 4
    totals = [w[s:s + ln].sum()
              for s, ln in zip(part.starts, part.lengths)]
    assert max(totals) <= 1.6 * min(totals), totals
    assert part.lengths[0] < part.lengths[-1]
    assert part.bns[0] < part.bns[-1]
    assert all(b % 8 == 0 for b in part.bns)
    # skew statistic
    assert Repartitioner.skew([1, 1, 1, 1]) == 1.0
    assert Repartitioner.skew([3, 1, 1, 1]) == 2.0
    assert Repartitioner.skew([]) == 1.0


@pytest.mark.parametrize("lengths,bns", [
    ((100, 150, 100), (16, 64, 24)),      # three bn-groups
    ((50, 300), (8, 8)),                  # one group, ragged lengths
    ((0, 350), (16, 256)),                # empty first shard
])
def test_heterogeneous_partition_bit_identical_to_uniform(lengths, bns):
    """A repartitioned layout changes performance knobs only: pruned AND
    exact answers keep the uniform single-launch layout's ids exactly, and
    its scores up to float summation order."""
    items = _factors(350, 16, 3)
    users = _factors(8, 16, 4)
    ref = _sharded(items, n_shards=2, min_overlap=2, bucket=512)
    svc = _sharded(items, n_shards=len(lengths), min_overlap=2, bucket=512)
    svc.compact(partition=Partition.from_lengths(lengths, bns))
    for exact in (False, True):
        a = ref.query(users, 10, exact=exact)
        b = svc.query(users, 10, exact=exact)
        np.testing.assert_array_equal(a.ids, b.ids)
        assert_scores_match(a.scores, b.scores)
        np.testing.assert_array_equal(a.n_scored, b.n_scored)


def test_heterogeneous_partition_dense_reference_parity():
    """The dense (Q, N)-mask oracle agrees with the fused multi-group
    launch on a heterogeneous partition, including per-shard counts."""
    items = _factors(300, 16, 5)
    users = _factors(6, 16, 6)
    svc = _sharded(items, n_shards=3, min_overlap=2, bucket=512)
    svc.compact(partition=Partition.from_lengths((60, 180, 60), (16, 64, 8)))
    svc.delete([10, 100, 299])            # exercise kill across groups
    base = svc.base
    tau, vals = sparse_map(jnp.asarray(users), CFG)
    q_mask = jnp.asarray(np.asarray(vals) != 0.0)
    got = base.query(jnp.asarray(users), tau, q_mask, 10)
    want = base.query_dense_reference(jnp.asarray(users), tau, q_mask, 10)
    np.testing.assert_array_equal(np.asarray(got.rows),
                                  np.asarray(want.rows))
    real = np.asarray(want.scores) > -1e37
    assert_scores_match(np.asarray(got.scores)[real],
                        np.asarray(want.scores)[real])
    np.testing.assert_array_equal(np.asarray(got.shard_candidates),
                                  np.asarray(want.shard_candidates))


def test_metrics_maintenance_counters_and_block_skew():
    m = ServiceMetrics()
    m.record_compact()
    m.record_compact(async_=True)
    m.record_compact_slice()
    m.record_repartition(skew_before=2.5)
    m.record_query_stats(block_candidates=np.array([[3, 1], [1, 1]]))
    snap = m.snapshot()
    assert snap["n_compactions"] == 2
    assert snap["n_async_compactions"] == 1
    assert snap["n_compact_slices"] == 1
    assert snap["n_repartitions"] == 1
    assert snap["last_repartition_skew"] == 2.5
    assert snap["block_balance"] == pytest.approx(4 / 3)
    # a repartition that changes the block count restarts the accumulator
    m.record_query_stats(block_candidates=np.array([[1, 1, 1]]))
    assert m.block_candidates.shape == (3,)


# ------------------------------------------------------- device placement


@pytest.mark.skipif(jax.device_count() < 2,
                    reason="needs >1 device (XLA_FLAGS host platform count)")
def test_index_mesh_places_shards_on_devices():
    from repro.launch.mesh import make_index_mesh

    mesh = make_index_mesh(2)
    items = _factors(128, 16, 21)
    idx = ShardedGamIndex.build(items, CFG, n_shards=2, min_overlap=1,
                                mesh=mesh)
    # the posting segments stay in host memory as CSR (no query reads them
    # on the device), while the kernel state is split over the items axis
    assert len(idx.postings) == 2
    assert all(isinstance(a, np.ndarray) for pair in idx.postings
               for a in pair)
    assert isinstance(idx.counts, np.ndarray)
    assert not idx.metas[0].item_bits_t.sharding.is_fully_replicated
    # and the sharded query still matches the single-shard retriever
    users = _factors(4, 16, 22)
    svc = _sharded(items, n_shards=2, min_overlap=2, bucket=512, mesh=mesh)
    single = _gam_device(items)
    res = svc.query(users, 10)
    np.testing.assert_array_equal(res.ids, single.query(users, 10).ids)
    # the kernel state itself lives on both devices, one row run each
    for arr in (idx.factors_g[0], idx.alive_g[0], idx.metas[0].item_bits_t):
        assert len(arr.sharding.device_set) == 2


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs 4 devices (XLA_FLAGS host platform count)")
@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_four_device_mesh_serves_each_shard_from_its_device(quantize):
    """One process, four devices: every device holds a quarter of the
    kernel state and answers for its own rows, and the merged answers —
    pruned and exact, after upserts that tombstone base rows — equal the
    one-device layout's."""
    from repro.launch.mesh import make_index_mesh

    mesh = make_index_mesh(4)
    items = _factors(1000, 16, 31)
    users = _factors(9, 16, 32)
    spec = RetrieverSpec(cfg=CFG, backend="sharded", n_shards=4,
                         min_overlap=2, kappa=10, bucket=512,
                         quantize=quantize)
    svcs = [open_retriever(spec, items=items, mesh=mesh),
            open_retriever(spec, items=items)]
    for svc in svcs:
        svc.upsert([3, 500, 2000], _factors(3, 16, 33))   # kills base rows
        svc.delete([7, 999])
    base = svcs[0].base
    meta = base.metas[0]
    arrays = [base.factors_g[0], base.alive_g[0], meta.item_bits_t,
              meta.block_union, meta.spill8]
    if quantize == "int8":
        arrays += [meta.factors_q, meta.scales]
    for arr in arrays:                 # kill() kept every placement
        assert len(arr.sharding.device_set) == 4
    rows = {sh.device: sh.data.shape[0]
            for sh in base.factors_g[0].addressable_shards}
    assert sorted(rows.values()) == [base.partition.n_rows // 4] * 4
    assert len(base._launch_units(0)) == 4
    for exact in (False, True):
        got, want = (s.query(users, 10, exact=exact) for s in svcs)
        np.testing.assert_array_equal(got.ids, want.ids)
        # the same (bq, bn) tiles on every device: scores exactly equal
        np.testing.assert_array_equal(got.scores, want.scores)
        np.testing.assert_array_equal(got.n_scored, want.n_scored)


@pytest.mark.skipif(jax.device_count() < 4,
                    reason="needs 4 devices (XLA_FLAGS host platform count)")
@pytest.mark.parametrize("skewed", [False, True])
def test_four_device_mesh_keeps_its_placement_through_compaction(skewed):
    """Layouts whose block count does not split over four devices — three
    uniform shards, or a one-bn skewed plan with ragged caps — are padded
    with dead blocks at build and at the background compaction, so the
    mesh deployment keeps one launch per device and the one-device
    layout's answers."""
    from repro.launch.mesh import make_index_mesh

    items = _factors(1000, 16, 41)
    users = _factors(9, 16, 42)
    spec = RetrieverSpec(cfg=CFG, backend="sharded", n_shards=3,
                         min_overlap=2, kappa=10, bucket=512)
    svcs = [open_retriever(spec, items=items, mesh=make_index_mesh(4)),
            open_retriever(spec, items=items)]
    for svc in svcs:
        svc.upsert([5, 1500], _factors(2, 16, 43))
        svc.delete([8])
        part = (Partition.from_lengths((100, svc.n_items - 350, 250),
                                       (64, 64, 64)) if skewed else None)
        svc.compact(async_=True, partition=part)
        while svc.maintenance_stats()["compaction"]["active"]:
            svc.compaction_step()
    mesh_base, one_base = svcs[0].base, svcs[1].base
    bn = one_base.partition.bns[0]
    assert one_base.partition.n_rows // bn % 4       # 6 or 17 blocks
    assert mesh_base.partition.n_rows // bn % 4 == 0
    assert len(mesh_base._launch_units(0)) == 4
    for exact in (False, True):
        got, want = (s.query(users, 10, exact=exact) for s in svcs)
        np.testing.assert_array_equal(got.ids, want.ids)
        np.testing.assert_array_equal(got.scores, want.scores)
        np.testing.assert_array_equal(got.n_scored, want.n_scored)
