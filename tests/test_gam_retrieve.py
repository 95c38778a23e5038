"""Fused streaming retrieval kernel: parity with the dense masked path.

Every case runs in interpret mode so tier-1 stays CPU-only.  The contract
under test: ``gam_retrieve`` returns the same ids as ``masked_topk`` over
``DeviceIndex`` candidate masks — including score tie-breaks, spill-list
candidates and empty-candidate padding — and the same scores up to float
summation order, after the NEG-slot normalisation every consumer applies
(fused empties are (NEG, -1); the dense path parks arbitrary ``lax.top_k``
indices there).
"""
import importlib

import jax.numpy as jnp
import numpy as np
import pytest
from conftest import CFG, assert_scores_match, unit_factors as _factors

from repro.core.inverted_index import DeviceIndex
from repro.core.mapping import GamConfig, sparse_map
from repro.core.retrieval import masked_topk
from repro.retriever import RetrieverSpec, open_retriever
from repro.kernels import ref
from repro.kernels.gam_retrieve import (build_retrieval_meta, gam_retrieve,
                                        pack_patterns)
from repro.kernels.gam_score import NEG


def _mapped(factors, cfg=CFG):
    tau, vals = sparse_map(jnp.asarray(factors), cfg)
    return np.asarray(tau), np.asarray(vals) != 0.0


def _dense_reference(users, items, tau, mask, q_tau, q_mask, kappa, mo,
                     bucket, cfg=CFG):
    """masked_topk over DeviceIndex masks, NEG slots normalised to -1."""
    dev = DeviceIndex.build(tau, cfg.p, bucket, mask=mask)
    masks = dev.batch_candidate_mask(jnp.asarray(q_tau), mo,
                                     jnp.asarray(q_mask))
    vals, ids = masked_topk(jnp.asarray(users), jnp.asarray(items), masks,
                            kappa)
    vals, ids = np.asarray(vals), np.asarray(ids)
    return np.where(vals <= NEG / 2, -1, ids), vals, dev, masks


def _assert_same_topk(res, ref_ids, ref_vals):
    empty = ref_vals <= NEG / 2
    np.testing.assert_array_equal(np.asarray(res.rows), ref_ids)
    got = np.asarray(res.vals)
    np.testing.assert_array_equal(got <= NEG / 2, empty)
    assert_scores_match(got[~empty], ref_vals[~empty])
    # fused empty slots are exactly NEG (never a fabricated score)
    assert (got[empty] == NEG).all()


@pytest.mark.parametrize("n,q,kappa,mo,bucket,bn,bq", [
    (350, 16, 10, 2, 512, 128, 32),    # plain randomized catalog
    (300, 7, 5, 1, 4, 64, 8),          # tiny bucket forces spill candidates
    (123, 3, 50, 3, 256, 32, 8),       # kappa > candidates, ragged shapes
    (513, 11, 17, 2, 8, 96, 8),        # spill + non-divisible Q and N blocks
])
@pytest.mark.parametrize("loop_merge", [False, True])
def test_fused_bit_identical_to_masked_topk(n, q, kappa, mo, bucket, bn, bq,
                                            loop_merge):
    items = _factors(n, 16, n)
    users = _factors(q, 16, n + 1)
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    kk = min(kappa, n)
    ref_ids, ref_vals, dev, masks = _dense_reference(
        users, items, tau, mask, q_tau, q_mask, kk, mo, bucket)
    meta = build_retrieval_meta(tau, mask, CFG.p,
                                spill_rows=np.asarray(dev.spill), bn=bn)
    res = gam_retrieve(users, items, q_tau, q_mask, meta, kk,
                       min_overlap=mo, bq=bq, interpret=True,
                       loop_merge=loop_merge)
    _assert_same_topk(res, ref_ids, ref_vals)
    # n_scored comes from the block prepass counts and must equal the dense
    # mask's candidate count exactly
    np.testing.assert_array_equal(np.asarray(res.blk_counts).sum(1),
                                  np.asarray(masks).sum(1))


def test_score_ties_break_by_lowest_row():
    """Duplicate factor rows produce exact score ties; the on-chip merge must
    resolve them like lax.top_k (lowest row first), across block boundaries."""
    base = _factors(8, 16, 0)
    items = np.concatenate([base] * 8)            # rows i, i+8, i+16, ... tie
    users = base[:4]
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    ref_ids, ref_vals, dev, _ = _dense_reference(
        users, items, tau, mask, q_tau, q_mask, 12, 1, 512)
    meta = build_retrieval_meta(tau, mask, CFG.p,
                                spill_rows=np.asarray(dev.spill), bn=16)
    for loop_merge in (False, True):
        res = gam_retrieve(users, items, q_tau, q_mask, meta, 12,
                           min_overlap=1, bq=8, interpret=True,
                           loop_merge=loop_merge)
        _assert_same_topk(res, ref_ids, ref_vals)


def test_all_empty_candidate_rows():
    """min_overlap beyond any possible pattern overlap, no spill: every slot
    must come back as the (NEG, -1) empty pad, and nothing is scored."""
    items = _factors(200, 16, 5)
    users = _factors(6, 16, 6)
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    meta = build_retrieval_meta(tau, mask, CFG.p, bn=64)
    res = gam_retrieve(users, items, q_tau, q_mask, meta, 10,
                       min_overlap=17, interpret=True)
    assert (np.asarray(res.rows) == -1).all()
    assert (np.asarray(res.vals) == NEG).all()
    assert (np.asarray(res.blk_counts) == 0).all()
    # the block prepass proves emptiness, so every tile is skipped
    assert np.asarray(res.skipped).all()


def test_block_skipping_prunes_tiles_without_changing_results():
    """Cluster-sorted catalog: far blocks fail the union-popcount bound and
    are skipped outright, yet results stay bit-identical to the dense path."""
    rng = np.random.default_rng(2)
    centers = _factors(8, 16, 7)
    items = np.repeat(centers, 64, axis=0) + \
        0.04 * rng.normal(size=(512, 16)).astype(np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    users = centers[:2] + 0.04 * rng.normal(size=(2, 16)).astype(np.float32)
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    bucket = 4096                      # no spill: discard reflects pruning
    ref_ids, ref_vals, dev, masks = _dense_reference(
        users, items, tau, mask, q_tau, q_mask, 10, 4, bucket)
    meta = build_retrieval_meta(tau, mask, CFG.p,
                                spill_rows=np.asarray(dev.spill), bn=64)
    res = gam_retrieve(users, items, q_tau, q_mask, meta, 10,
                       min_overlap=4, bq=8, interpret=True)
    _assert_same_topk(res, ref_ids, ref_vals)
    assert np.asarray(res.skipped).mean() > 0.2, "no tiles were pruned"
    # skipped tiles truly had zero candidates (skip is never lossy)
    blk = np.asarray(res.blk_counts)
    assert blk[:, np.asarray(res.skipped)[0]].sum() == 0


def test_matches_pattern_oracle():
    """Independent O(k^2) pattern-overlap oracle (no bit-packing, no posting
    table) agrees with the kernel."""
    items = _factors(150, 16, 9)
    users = _factors(5, 16, 10)
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    meta = build_retrieval_meta(tau, mask, CFG.p, bn=64)
    res = gam_retrieve(users, items, q_tau, q_mask, meta, 7,
                       min_overlap=2, interpret=True)
    vals, rows = ref.gam_retrieve_ref(users, items, q_tau, q_mask, tau, mask,
                                      7, min_overlap=2)
    np.testing.assert_array_equal(np.asarray(res.rows), np.asarray(rows))
    real = np.asarray(vals) > NEG / 2
    np.testing.assert_array_equal(np.asarray(res.vals)[real],
                                  np.asarray(vals)[real])


def test_pack_patterns_roundtrip():
    tau, mask = _mapped(_factors(64, 16, 11))
    bits_t = pack_patterns(tau, mask, CFG.p)     # the kernel's layout
    assert bits_t.shape == (-(-CFG.p // 32), 64)
    bits = np.ascontiguousarray(bits_t.T)
    pop = np.unpackbits(bits.view(np.uint8), axis=1).sum(1)
    np.testing.assert_array_equal(pop, mask.sum(1))
    # set bits are exactly the masked destinations
    for i in (0, 17, 63):
        got = {w * 32 + b for w in range(bits.shape[1]) for b in range(32)
               if bits[i, w] >> np.uint32(b) & np.uint32(1)}
        assert got == set(tau[i][mask[i]].tolist())


def test_alive_mask_and_exact_path():
    """min_overlap=0 + alive == brute force over live rows (the service's
    exact reference path through the same kernel)."""
    items = _factors(100, 16, 12)
    users = _factors(4, 16, 13)
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    meta = build_retrieval_meta(tau, mask, CFG.p, bn=32)
    alive = np.ones(100, bool)
    alive[::3] = False
    res = gam_retrieve(users, items, q_tau, q_mask, meta, 10,
                       min_overlap=0, alive=alive, interpret=True)
    scores = users @ items.T
    scores[:, ~alive] = -np.inf
    order = np.argsort(-scores, axis=1, kind="stable")[:, :10]
    np.testing.assert_array_equal(np.asarray(res.rows), order)
    np.testing.assert_array_equal(np.asarray(res.blk_counts).sum(1),
                                  np.full(4, int(alive.sum())))


def test_device_retriever_equals_dense_reference_end_to_end():
    """The gam-device backend — now streaming — reproduces the dense
    masked path it replaced, including n_scored."""
    items = _factors(400, 16, 14)
    users = _factors(20, 16, 15)
    gam = open_retriever(
        RetrieverSpec(cfg=CFG, backend="gam-device", min_overlap=2,
                      bucket=512), items=items)
    res = gam.query(users, 10)
    q_tau, q_mask = gam.map_queries(users)
    masks = gam.device_index.batch_candidate_mask(
        jnp.asarray(q_tau), 2, jnp.asarray(q_mask))
    vals, ids = masked_topk(jnp.asarray(users), jnp.asarray(items), masks, 10)
    vals, ids = np.asarray(vals), np.asarray(ids)
    empty = vals <= NEG / 2
    np.testing.assert_array_equal(res.ids, np.where(empty, -1, ids))
    assert_scores_match(res.scores[~empty], vals[~empty])
    np.testing.assert_array_equal(res.n_scored, np.asarray(masks).sum(1))


def test_sharded_merge_equals_dense_reference():
    """The service's fused sharded query == the retained dense-mask
    reference (query_dense_reference: per-shard posting-table masks +
    masked_topk), bit for bit, including per-shard candidate counts and
    tombstoned rows."""
    items = _factors(350, 16, 16)
    users = _factors(9, 16, 17)
    svc = open_retriever(
        RetrieverSpec(cfg=CFG, backend="sharded", n_shards=3, min_overlap=2,
                      kappa=10, bucket=512), items=items)
    svc.delete([5, 170, 349])          # exercise the alive mask
    base = svc.base
    tau, vals_ = sparse_map(jnp.asarray(users.astype(np.float32)), CFG)
    q_mask = np.asarray(vals_) != 0.0
    got = base.query(jnp.asarray(users), tau, jnp.asarray(q_mask), 10)
    want = base.query_dense_reference(jnp.asarray(users), tau,
                                      jnp.asarray(q_mask), 10)
    w_vals = np.asarray(want.scores)
    w_rows = np.where(w_vals <= NEG / 2, -1, np.asarray(want.rows))
    kk = w_rows.shape[1]
    g_vals = np.asarray(got.scores)[:, :kk]
    np.testing.assert_array_equal(np.asarray(got.rows)[:, :kk], w_rows)
    real = w_vals > NEG / 2
    np.testing.assert_array_equal(g_vals[real], w_vals[real])
    # anything past the reference's kappa' columns is empty padding
    assert (np.asarray(got.scores)[:, kk:] <= NEG / 2).all()
    np.testing.assert_array_equal(np.asarray(got.shard_candidates),
                                  np.asarray(want.shard_candidates))


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_batch_split_at_skip_map_bound_keeps_results(quantize, monkeypatch):
    """A batch whose skip map would pass SKIP_MAP_TILES runs as several
    launches over consecutive query rows, with the one-launch results; an
    int8 pool launched with rerank=False and re-ranked after is the same
    answer as the re-ranked launch."""
    # the package re-exports the function under the module's name
    gr = importlib.import_module("repro.kernels.gam_retrieve")
    items = _factors(300, 16, 61)
    users = _factors(21, 16, 62)
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    meta = build_retrieval_meta(tau, mask, CFG.p, bn=64, factors=items,
                                quantize=quantize)           # 5 item blocks

    def run(**kw):
        return gam_retrieve(users, items, q_tau, q_mask, meta, 10,
                            min_overlap=2, bq=8, interpret=True, **kw)

    whole = run()
    monkeypatch.setattr(gr, "SKIP_MAP_TILES", 2 * meta.n_blocks)
    split = run()                          # 16 query rows a launch: 2 launches
    for a, b in zip(split, whole):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    if quantize == "int8":
        pool = run(rerank=False)
        assert np.asarray(pool.vals).shape == (21, 40)
        late = gr.rerank_pool(pool, users, items, 10)
        for a, b in zip(late, whole):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# --------------------------------------------------- active-word compaction

# k=48 parse-tree patterns span 146 words, more than COMPACT_WORDS
CFG48 = GamConfig(k=48, scheme="parse_tree", threshold=0.2)
WORDS48 = -(-CFG48.p // 32)


def _full_width(run):
    """``run()`` with the overlap loop over every pattern word, the program
    as it was before the compaction."""
    gr = importlib.import_module("repro.kernels.gam_retrieve")
    saved = gr.COMPACT_WORDS
    gr._gam_retrieve.clear_cache()
    gr.COMPACT_WORDS = WORDS48         # an index this narrow: full loop only
    try:
        return run()
    finally:
        gr.COMPACT_WORDS = saved
        gr._gam_retrieve.clear_cache()


def _batch_setting(active, tau, mask, items, q, n_pad_rows, seed):
    """A batch of ``q`` rows whose real queries set exactly ``active``
    distinct pattern words: each real row keeps an item's pattern bits
    that fall in a chosen word set, and fillers cover the set's other
    words.  The last ``n_pad_rows`` rows are the service's pads (zero
    vector, no pattern bits)."""
    rng = np.random.default_rng(seed)
    # word 0 is in the set: the list's unused slots name word 0 too
    words = (np.concatenate([[0], 1 + rng.choice(
        WORDS48 - 1, size=active - 1, replace=False)]) if active
        else np.zeros(0, np.int64))
    k = tau.shape[1]
    users = np.zeros((q, k), np.float32)
    q_tau = np.zeros((q, k), np.int32)
    q_mask = np.zeros((q, k), bool)
    real = q - n_pad_rows if active else 0
    for i in range(real):
        r = rng.integers(len(items))
        users[i] = items[r]
        q_tau[i] = tau[r]
        q_mask[i] = mask[r] & np.isin(tau[r] // 32, words)
    covered = set((q_tau[q_mask] // 32).tolist())
    row = 0
    for w in sorted(set(words.tolist()) - covered):
        while q_mask[row % real].all():
            row += 1
        i = row % real
        j = int(np.flatnonzero(~q_mask[i])[0])
        set_in_w = tau[mask][tau[mask] // 32 == w]    # a bit items set
        q_tau[i, j] = (rng.choice(set_in_w) if set_in_w.size else
                       32 * w + rng.integers(0, min(32, CFG48.p - 32 * w)))
        q_mask[i, j] = True
        row += 1
    got = np.unique(q_tau[q_mask] // 32).size
    assert got == active, (got, active)
    return users, q_tau, q_mask


@pytest.mark.parametrize("active,spill,mo,quantize,delta", [
    pytest.param(1, False, 1, "none", False, id="one-word"),
    pytest.param(40, False, 2, "none", False, id="few-words"),
    pytest.param(127, False, 2, "none", False, id="below-width"),
    pytest.param(128, False, 2, "none", False, id="at-width"),
    pytest.param(129, False, 2, "none", False, id="above-width-full-loop"),
    pytest.param(0, False, 2, "none", False, id="all-pad-batch"),
    pytest.param(40, True, 2, "none", False, id="spill-rows"),
    pytest.param(65, False, 0, "none", False, id="exact-min-overlap-0"),
    pytest.param(65, False, 2, "int8", False, id="int8-pool"),
    pytest.param(40, False, 2, "none", True, id="delta-segment"),
])
def test_active_word_compaction_is_bit_identical(active, spill, mo, quantize,
                                                 delta):
    """The overlap loop over the words the batch sets returns the full
    loop's vals, rows, per-block counts and skip map bit for bit, and runs
    over COMPACT_WORDS listed words, or over every word when the batch
    sets more than that."""
    gr = importlib.import_module("repro.kernels.gam_retrieve")
    n = 40 if delta else 256
    items = _factors(n, CFG48.k, 31)
    tau, mask = (np.array(a) for a in _mapped(items, CFG48))
    # a third of the items also set a bit of word 0, the word that unused
    # rung slots gather (parse-tree patterns at k=48 never reach it)
    some = np.arange(0, n, 3)
    free = np.argmin(mask, axis=1)[some]
    tau[some, free], mask[some, free] = 5, True
    # a delta segment pads its rows to a power of two, bn = min(256, cap)
    n_rows = 64 if delta else n
    factors = np.zeros((n_rows, CFG48.k), np.float32)
    factors[:n] = items
    meta = build_retrieval_meta(
        tau, mask, CFG48.p, n_rows=n_rows, bn=64,
        spill_rows=np.arange(0, n, 9) if spill else None,
        factors=factors if quantize == "int8" else None, quantize=quantize)
    q = 32 if delta else 24
    users, q_tau, q_mask = _batch_setting(active, tau, mask, items, q, 4,
                                          seed=active + 7)

    def run():
        return gam_retrieve(users, factors, q_tau, q_mask, meta, 10,
                            min_overlap=mo, alive=np.arange(n_rows) < n,
                            bq=8, interpret=True, rerank=False)

    got = run()
    want = _full_width(run)
    for field in ("vals", "rows", "blk_counts", "skipped"):
        np.testing.assert_array_equal(np.asarray(getattr(got, field)),
                                      np.asarray(getattr(want, field)),
                                      err_msg=field)
    looped = gr.COMPACT_WORDS if active <= gr.COMPACT_WORDS else WORDS48
    np.testing.assert_array_equal(np.asarray(got.loop_words), looped)
    np.testing.assert_array_equal(np.asarray(want.loop_words), WORDS48)
    scored = int(np.asarray(got.blk_counts).sum())
    assert scored > 0 if (active or spill or mo == 0) else scored == 0


def test_sharded_query_reports_active_words_frac():
    """``ShardTopK.active_words_frac`` is the share of the index's words
    the loop ran over, and the ``sharded`` retriever keeps the last
    query's in ``stats()``."""
    items = _factors(512, CFG48.k, 33)
    svc = open_retriever(
        RetrieverSpec(cfg=CFG48, backend="sharded", n_shards=2,
                      min_overlap=2, kappa=10, bucket=512), items=items)
    tau, mask = _mapped(items, CFG48)
    users, q_tau, q_mask = _batch_setting(100, tau, mask, items, 16, 0,
                                          seed=34)
    res = svc.base.query(jnp.asarray(users), jnp.asarray(q_tau),
                         jnp.asarray(q_mask), 10)
    assert res.active_words_frac == 128 / WORDS48
    svc.query(users, 10)
    assert 0.0 < svc.stats()["active_words_frac"] <= 1.0


# ------------------------------------------------- the bounded Mosaic merge


def _scaled(scale, seed):
    """Rows along one direction, row i scaled by ``scale[i]``, and eight
    queries near that direction: every query orders the rows by scale."""
    v = _factors(1, 16, seed)[0]
    rng = np.random.default_rng(seed)
    users = (v + 0.05 * rng.normal(size=(8, 16))).astype(np.float32)
    return (v[None, :] * np.asarray(scale, np.float32)[:, None]), users


def _merge_case(case):
    """-> items, users, retrieve keywords, alive, bucket, expected merge
    steps a query block (None: the numpy count alone decides)."""
    n, kappa, bn = 256, 10, 32
    tiles = n // bn
    kw = dict(kappa=kappa, mo=1, bn=bn, quantize="none")
    ramp = np.linspace(0.5, 2.0, n)
    if case == "ascending":          # every tile beats the pool
        items, users = _scaled(ramp, 41)
        return items, users, kw, None, 512, kappa * tiles
    if case == "descending":         # only the first tile enters the pool
        items, users = _scaled(ramp[::-1], 42)
        return items, users, kw, None, 512, kappa
    if case == "all-equal":          # ties break by row: the first tile's
        items, users = _scaled(np.ones(n), 43)
        return items, users, kw, None, 512, kappa
    if case == "tile-beats-kappa":   # a middle tile holds 32 new entries
        scale = ramp[::-1].copy()
        scale[3 * bn:4 * bn] = 3.0
        items, users = _scaled(scale, 44)
        return items, users, kw, None, 512, 2 * kappa
    items = _factors(300, 16, 45)
    users = _factors(11, 16, 46)
    kw.update(bn=64)
    if case == "spill-and-dead":
        alive = np.ones(300, bool)
        alive[::4] = False
        return items, users, dict(kw, mo=2), alive, 4, None
    if case == "int8-pool":
        return items, users, dict(kw, mo=2, quantize="int8"), None, 512, None
    assert case == "exact"
    return items, users, dict(kw, mo=0), None, 512, None


@pytest.mark.parametrize("case", ["ascending", "descending", "all-equal",
                                  "tile-beats-kappa", "spill-and-dead",
                                  "int8-pool", "exact"])
def test_bounded_merge_matches_top_k_merge_and_oracle(case):
    """The Mosaic merge, which runs only as many selection steps as the
    tile's entries that beat a query's kappa-th score (at most kappa), is
    the ``lax.top_k`` merge bit for bit (the int8 pool too) and the dense
    ``masked_topk`` oracle's answer, on catalogs where every tile, only
    the first, or one middle tile past kappa entries enters the pool."""
    items, users, kw, alive, bucket, want_steps = _merge_case(case)
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    kappa = kw["kappa"]
    dev = DeviceIndex.build(tau, CFG.p, bucket, mask=mask)
    meta = build_retrieval_meta(
        tau, mask, CFG.p, spill_rows=np.asarray(dev.spill), bn=kw["bn"],
        factors=items if kw["quantize"] == "int8" else None,
        quantize=kw["quantize"])

    def run(loop_merge, rerank=True):
        return gam_retrieve(users, items, q_tau, q_mask, meta, kappa,
                            min_overlap=kw["mo"], alive=alive, bq=8,
                            interpret=True, loop_merge=loop_merge,
                            rerank=rerank)

    bounded, top_k = run(True), run(False)
    for field in bounded._fields:
        np.testing.assert_array_equal(np.asarray(getattr(bounded, field)),
                                      np.asarray(getattr(top_k, field)),
                                      err_msg=field)
    if kw["quantize"] == "int8":
        pool_b, pool_t = run(True, rerank=False), run(False, rerank=False)
        assert np.asarray(pool_b.vals).shape[1] == 4 * kappa
        for field in pool_b._fields:
            np.testing.assert_array_equal(np.asarray(getattr(pool_b, field)),
                                          np.asarray(getattr(pool_t, field)),
                                          err_msg=field)
    masks = dev.batch_candidate_mask(jnp.asarray(q_tau), kw["mo"],
                                     jnp.asarray(q_mask))
    if alive is not None:
        masks = masks & jnp.asarray(alive)[None, :]
    vals, ids = masked_topk(jnp.asarray(users), jnp.asarray(items), masks,
                            kappa)
    vals, ids = np.asarray(vals), np.asarray(ids)
    _assert_same_topk(bounded, np.where(vals <= NEG / 2, -1, ids), vals)
    if want_steps is not None:
        np.testing.assert_array_equal(np.asarray(bounded.merge_steps),
                                      want_steps)


def _numpy_merge_steps(scores, cand, kappa, bq, bn):
    """Per query block: over the item blocks, the most entries any of its
    queries has above its running kappa-th score (capped at kappa), the
    running top kappa kept under (score desc, row asc)."""
    q, n = scores.shape
    out = np.zeros(-(-q // bq), np.int64)
    pool = [[] for _ in range(q)]               # (score, row), best first
    for lo in range(0, n, bn):
        beat = np.zeros(q, np.int64)
        for i in range(q):
            rows = [r for r in range(lo, min(lo + bn, n)) if cand[i, r]]
            thr = pool[i][kappa - 1][0] if len(pool[i]) >= kappa else -np.inf
            beat[i] = sum(scores[i, r] > thr for r in rows)
            pool[i] = sorted(pool[i] + [(scores[i, r], r) for r in rows],
                             key=lambda e: (-e[0], e[1]))[:kappa]
        for b in range(len(out)):
            out[b] += min(kappa, beat[b * bq:(b + 1) * bq].max())
    return out


@pytest.mark.parametrize("loop_merge", [False, True])
def test_merge_steps_counts_entries_beating_the_kappa_th(loop_merge):
    """``merge_steps`` is a numpy count of the tile entries above each
    query's running kappa-th score, capped at kappa and maxed over the
    query block, summed over item blocks.  Small integer factors make
    every score exact, ties included."""
    rng = np.random.default_rng(47)
    items = rng.integers(-3, 4, size=(300, 16)).astype(np.float32)
    users = rng.integers(-3, 4, size=(16, 16)).astype(np.float32)
    tau, mask = _mapped(items)
    q_tau, q_mask = _mapped(users)
    dev = DeviceIndex.build(tau, CFG.p, 512, mask=mask)
    meta = build_retrieval_meta(tau, mask, CFG.p,
                                spill_rows=np.asarray(dev.spill), bn=32)
    res = gam_retrieve(users, items, q_tau, q_mask, meta, 6, min_overlap=2,
                       bq=8, interpret=True, loop_merge=loop_merge)
    cand = np.asarray(dev.batch_candidate_mask(jnp.asarray(q_tau), 2,
                                               jnp.asarray(q_mask)))
    want = _numpy_merge_steps(users @ items.T, cand, 6, 8, 32)
    assert 0 < want.sum() < 6 * 10 * 2            # not every tile runs all
    np.testing.assert_array_equal(np.asarray(res.merge_steps), want)
    vals, ids = masked_topk(jnp.asarray(users), jnp.asarray(items),
                            jnp.asarray(cand), 6)
    vals, ids = np.asarray(vals), np.asarray(ids)
    _assert_same_topk(res, np.where(vals <= NEG / 2, -1, ids), vals)


def test_sharded_query_reports_merge_steps_frac():
    """``ShardTopK.merge_steps_frac`` is the launch's merge steps over
    kappa a scored tile, and the ``sharded`` retriever keeps the last
    query's in ``stats()``."""
    items = _factors(600, 16, 48)
    users = _factors(12, 16, 49)
    svc = open_retriever(
        RetrieverSpec(cfg=CFG, backend="sharded", n_shards=2, min_overlap=2,
                      kappa=5, bucket=512), items=items)
    tau, vals_ = sparse_map(jnp.asarray(users), CFG)
    q_mask = jnp.asarray(np.asarray(vals_) != 0.0)
    res = svc.base.query(jnp.asarray(users), tau, q_mask, 5)
    (_, meta, fac, alive), = svc.base._launch_units(0)
    direct = gam_retrieve(jnp.asarray(users), fac, tau, q_mask, meta, 5,
                          min_overlap=2, alive=alive, interpret=True)
    scored = int(np.logical_not(np.asarray(direct.skipped)).sum())
    steps = int(np.asarray(direct.merge_steps).sum())
    assert 0 < steps < 5 * scored              # the pool fills, then few
    assert res.merge_steps_frac == steps / (5 * scored)
    svc.query(users, 5)
    assert 0.0 < svc.stats()["merge_steps_frac"] < 1.0
