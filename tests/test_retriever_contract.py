"""Unified retriever API: the cross-backend contract suite.

One scenario — build / query / upsert / delete / compact / query /
snapshot / restore — parametrized over all four first-class backends,
asserting (a) exact-mode top-kappa agreement with the ``brute`` oracle,
(b) bit-identical query results across a snapshot -> restore round trip
(including with a non-empty delta segment on ``sharded``), and (c) typed
``UnsupportedOp`` — never silent divergence — where a backend genuinely
cannot honour an operation.
"""
import os

import numpy as np
import pytest
from conftest import CFG, assert_scores_match, unit_factors as _factors

from repro.core.mapping import GamConfig
from repro.retriever import (
    BACKEND_IDS,
    RetrieverSpec,
    UnsupportedOp,
    available_backends,
    open_retriever,
    register_backend,
)

BACKENDS = ["brute", "gam", "gam-device", "sharded", "sharded-multihost"]


def _spec(backend, **kw):
    kw.setdefault("min_overlap", 2)
    kw.setdefault("bucket", 512)
    if backend == "sharded":
        kw.setdefault("n_shards", 2)
    if backend == "sharded-multihost":
        kw.setdefault("n_shards", 4)
        kw.setdefault("n_hosts", 2)
        kw.setdefault("replication", 2)
    return RetrieverSpec(cfg=CFG, backend=backend, **kw)


# ------------------------------------------------------------ registry


def test_registry_lists_all_backends():
    assert set(BACKENDS) <= set(BACKEND_IDS)
    assert set(BACKEND_IDS) <= set(available_backends())


def test_unknown_backend_is_a_loud_keyerror():
    with pytest.raises(KeyError, match="unknown retriever backend"):
        open_retriever(RetrieverSpec(cfg=CFG, backend="faiss"))


def test_register_backend_extends_registry():
    calls = []

    @register_backend("contract-test-null")
    def _factory(spec, **kw):
        calls.append(spec)
        return open_retriever(RetrieverSpec(cfg=spec.cfg, backend="brute"))

    r = open_retriever(RetrieverSpec(cfg=CFG, backend="contract-test-null"))
    assert calls and r.spec.backend == "brute"
    assert "contract-test-null" in available_backends()


# ------------------------------------------------------------ the scenario


@pytest.mark.parametrize("backend", BACKENDS)
def test_full_lifecycle_scenario_against_brute_oracle(backend, tmp_path,
                                                      catalog, users):
    """The same scenario on every backend; after every mutation the
    exact-mode answers must agree with the brute oracle bit-for-bit."""
    k = CFG.k
    items = catalog
    ids0 = np.arange(300, dtype=np.int64)

    r = open_retriever(_spec(backend), items=items, ids=ids0)
    oracle = open_retriever(_spec("brute"), items=items, ids=ids0)

    def check(tag):
        got = r.query(users, 10, exact=True)
        want = oracle.query(users, 10, exact=True)
        np.testing.assert_array_equal(got.ids, want.ids, err_msg=tag)
        # ids must agree bit-for-bit; scores only to float summation order
        # (matvec vs matmul vs on-chip dot_general accumulate differently —
        # BIT-identity is the snapshot round-trip requirement below)
        np.testing.assert_allclose(got.scores, want.scores, rtol=1e-5,
                                   atol=1e-6, err_msg=tag)

    check("after build")
    assert r.n_items == 300

    new_ids = np.array([500, 501, 502], np.int64)
    new_fac = _factors(3, k, 3)
    r.upsert(new_ids, new_fac)
    oracle.upsert(new_ids, new_fac)
    check("after insert")
    assert r.n_items == 303

    over_fac = _factors(2, k, 4)
    r.upsert([5, 500], over_fac)
    oracle.upsert([5, 500], over_fac)
    check("after overwrite")
    assert r.n_items == 303

    r.delete([0, 1, 2, 501, 999999])
    oracle.delete([0, 1, 2, 501, 999999])
    check("after delete (incl. unknown id)")
    assert r.n_items == 299

    # snapshot mid-stream (sharded: non-empty delta), restore into a fresh
    # instance, and require BIT-identical pruned-mode answers
    pruned_before = r.query(users, 10)
    path = os.fspath(tmp_path / f"{backend}.npz")
    r.snapshot(path)
    restored = open_retriever(_spec(backend), snapshot=path)
    assert restored.n_items == 299
    pruned_after = restored.query(users, 10)
    np.testing.assert_array_equal(pruned_after.ids, pruned_before.ids)
    np.testing.assert_array_equal(pruned_after.scores, pruned_before.scores)

    r.compact()
    check("after compact")
    pruned_compacted = r.query(users, 10)
    np.testing.assert_array_equal(pruned_compacted.ids, pruned_before.ids)
    np.testing.assert_array_equal(pruned_compacted.scores,
                                  pruned_before.scores)


@pytest.mark.parametrize("backend", BACKENDS)
def test_background_compact_is_part_of_the_contract(backend):
    """``compact(async_=True)`` is accepted everywhere: backends without a
    delta tier complete instantly; the sharded backend runs the incremental
    planner to completion under query-interleaved stepping, advancing its
    generation — and answers never change along the way."""
    items = _factors(200, CFG.k, 22)
    users = _factors(6, CFG.k, 23)
    r = open_retriever(_spec(backend), items=items)
    oracle = open_retriever(_spec("brute"), items=items)
    new = _factors(5, CFG.k, 24)
    r.upsert(np.arange(300, 305), new)
    oracle.upsert(np.arange(300, 305), new)
    before = r.query(users, 10)
    gen0 = r.maintenance_stats()["generation"]
    r.compact(async_=True)
    steps = 0
    while r.maintenance_stats()["compaction"]["active"]:
        got = r.query(users, 10, exact=True)
        want = oracle.query(users, 10, exact=True)
        np.testing.assert_array_equal(got.ids, want.ids)
        steps += 1
        assert steps < 100
    after = r.query(users, 10)
    np.testing.assert_array_equal(before.ids, after.ids)
    assert_scores_match(before.scores, after.scores)
    if backend in ("sharded", "sharded-multihost"):
        assert steps > 0
        assert r.maintenance_stats()["generation"] == gen0 + 1
        assert len(r.delta) == 0


def test_maintenance_stats_surface():
    items = _factors(64, CFG.k, 25)
    for backend in BACKENDS:
        ms = open_retriever(_spec(backend), items=items).maintenance_stats()
        assert ms["backend"] == backend
        assert ms["generation"] == 0
        assert ms["compaction"]["active"] is False


def test_sharded_snapshot_preserves_live_delta():
    items = _factors(200, CFG.k, 5)
    r = open_retriever(_spec("sharded"), items=items)
    r.upsert(np.arange(300, 310), _factors(10, CFG.k, 6))
    r.delete([0, 7])
    assert len(r.delta) == 10


@pytest.mark.parametrize("backend", ["gam", "gam-device", "sharded",
                                     "sharded-multihost"])
def test_pruned_mode_matches_gam_candidate_semantics(backend):
    """All index backends share one candidate definition (pattern overlap +
    spill), so with a common generous bucket their pruned answers are
    bit-identical — not just statistically close."""
    items = _factors(350, CFG.k, 7)
    users = _factors(10, CFG.k, 8)
    ref = open_retriever(_spec("gam"), items=items).query(users, 10)
    got = open_retriever(_spec(backend), items=items).query(users, 10)
    np.testing.assert_array_equal(got.ids, ref.ids)
    np.testing.assert_array_equal(got.n_scored, ref.n_scored)
    np.testing.assert_allclose(got.scores, ref.scores, rtol=1e-5, atol=1e-6)
    if backend in ("sharded", "sharded-multihost"):
        # same fused kernel as gam-device, over another layout
        dev = open_retriever(_spec("gam-device"), items=items).query(users, 10)
        np.testing.assert_array_equal(got.ids, dev.ids)
        assert_scores_match(got.scores, dev.scores)


@pytest.mark.parametrize("backend", BACKENDS)
def test_score_ties_break_identically_across_backends(backend):
    """Duplicate factor rows force exact score ties (including across the
    kappa boundary); every backend must realise the same total order
    (score desc, id asc) as the brute oracle — ties may never make
    backends diverge."""
    base = _factors(40, CFG.k, 21)
    items = np.concatenate([base, base, base[:8]])     # many exact ties
    users = base[:6]
    ids = np.arange(items.shape[0], dtype=np.int64)
    got = open_retriever(_spec(backend), items=items, ids=ids).query(
        users, 12, exact=True)
    want = open_retriever(_spec("brute"), items=items, ids=ids).query(
        users, 12, exact=True)
    np.testing.assert_array_equal(got.ids, want.ids)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stream_from_empty(backend):
    """open_retriever(spec) with no items is a valid (empty) retriever:
    queries answer all-empty and upsert streams the catalog up from zero."""
    users = _factors(4, CFG.k, 9)
    r = open_retriever(_spec(backend))
    res = r.query(users, 5)
    assert (res.ids == -1).all() and np.isneginf(res.scores).all()
    r.upsert(np.arange(6), _factors(6, CFG.k, 10))
    assert r.n_items == 6
    res = r.query(users, 5, exact=True)
    assert (res.ids >= 0).all()


@pytest.mark.parametrize("backend", BACKENDS)
def test_query_default_kappa_comes_from_spec(backend):
    items = _factors(64, CFG.k, 11)
    r = open_retriever(_spec(backend, kappa=7), items=items)
    assert r.query(_factors(3, CFG.k, 12)).ids.shape == (3, 7)


def test_stats_surface(make_factors):
    items = make_factors(128, CFG.k, 13)
    for backend in BACKENDS:
        st = open_retriever(_spec(backend), items=items).stats()
        assert st["backend"] == backend and st["n_items"] == 128


# ------------------------------------------------------------ UnsupportedOp


@pytest.mark.parametrize("backend", ["srp-lsh", "superbit-lsh", "cro",
                                     "pca-tree"])
def test_baseline_backends_are_query_only(backend):
    items = _factors(150, CFG.k, 14)
    users = _factors(5, CFG.k, 15)
    r = open_retriever(RetrieverSpec(cfg=CFG, backend=backend), items=items)
    res = r.query(users, 10)
    assert res.ids.shape == (5, 10)
    exact = r.query(users, 10, exact=True)
    assert (exact.ids >= 0).all()
    for op in (lambda: r.upsert([0], items[:1]),
               lambda: r.delete([0]),
               lambda: r.compact(),
               lambda: r.snapshot("/tmp/never-written.npz"),
               lambda: r.candidate_masks(users)):
        with pytest.raises(UnsupportedOp):
            op()


def test_candidate_masks_support_matrix():
    items = _factors(100, CFG.k, 16)
    users = _factors(3, CFG.k, 17)
    dev = open_retriever(_spec("gam-device"), items=items)
    masks = np.asarray(dev.candidate_masks(users))
    assert masks.shape == (3, 100) and masks.dtype == bool
    for backend in ["brute", "gam", "sharded", "sharded-multihost"]:
        with pytest.raises(UnsupportedOp):
            open_retriever(_spec(backend), items=items).candidate_masks(users)


# ------------------------------------------------------------ explain


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("exact", [False, True])
def test_explain_is_pure_observation(backend, exact):
    """query(..., explain=True) must never perturb the answer: ids and
    scores are BIT-identical with and without it, on every backend, in both
    pruned and exact mode, with a live delta segment in play."""
    items = _factors(250, CFG.k, 40)
    users = _factors(6, CFG.k, 41)
    r = open_retriever(_spec(backend), items=items)
    r.upsert(np.arange(300, 308), _factors(8, CFG.k, 42))
    plain = r.query(users, 10, exact=exact)
    explained = r.query(users, 10, exact=exact, explain=True)
    np.testing.assert_array_equal(plain.ids, explained.ids)
    np.testing.assert_array_equal(plain.scores, explained.scores)
    np.testing.assert_array_equal(plain.n_scored, explained.n_scored)
    np.testing.assert_array_equal(plain.discarded_frac,
                                  explained.discarded_frac)
    assert plain.explain is None
    exp = explained.explain
    assert exp is not None and exp["backend"] == backend
    assert len(exp["n_candidates"]) == 6
    # rerunning without explain afterwards is still bit-identical (explain
    # left no state behind)
    again = r.query(users, 10, exact=exact)
    np.testing.assert_array_equal(plain.ids, again.ids)
    np.testing.assert_array_equal(plain.scores, again.scores)


def test_explain_backend_schemas():
    """Each backend reports the provenance it actually has — per-shard
    counts, block prepass skips, delta-vs-base source, winning slice and
    replica — with shapes tied to (q, kappa)."""
    items = _factors(300, CFG.k, 43)
    users = _factors(5, CFG.k, 44)
    q, kappa = 5, 10

    exp = open_retriever(_spec("brute"), items=items).query(
        users, kappa, explain=True).explain
    assert exp["shard_candidates"] == [[300]] * q     # one logical shard
    assert exp["n_candidates"] == [300] * q

    exp = open_retriever(_spec("gam-device"), items=items).query(
        users, kappa, explain=True).explain
    assert len(exp["block_candidates"]) == q
    assert len(exp["blocks_skipped"]) == q
    assert all(0 <= s <= exp["n_blocks"] for s in exp["blocks_skipped"])
    for cand, skipped in zip(exp["n_candidates"], exp["blocks_skipped"]):
        assert cand >= 0 and skipped >= 0

    r = open_retriever(_spec("sharded"), items=items)
    r.upsert(np.arange(400, 410), _factors(10, CFG.k, 45))
    res = r.query(users, kappa, explain=True)
    exp = res.explain
    assert np.asarray(exp["shard_candidates"]).shape == (q, 2)  # n_shards=2
    assert np.asarray(exp["n_candidates"]).shape == (q,)
    assert len(exp["delta_candidates"]) == q
    src = np.asarray(exp["source"], object)
    assert src.shape == (q, kappa)
    assert set(src.ravel()) <= {"base", "delta", ""}
    # source is truthful: every id >= 400 came from the delta segment
    from_delta = res.ids >= 400
    assert (src[from_delta] == "delta").all()
    assert (src[(res.ids >= 0) & ~from_delta] == "base").all()
    shard = np.asarray(exp["shard"])
    assert shard.shape == (q, kappa)
    assert ((shard >= 0) == (src == "base")).all()    # -1 off the base tier

    r = open_retriever(_spec("sharded-multihost"), items=items)
    exp = r.query(users, kappa, explain=True).explain
    sl, rep = np.asarray(exp["slice"]), np.asarray(exp["replica"])
    assert sl.shape == rep.shape == (q, kappa)
    assert (sl >= 0).all() and (rep >= 0).all()       # no delta, no failover
    assert sl.max() < r.base.placement.n_slices


def test_explain_delta_item_queried_by_own_factor():
    """A delta item queried by its own factor wins rank 0 and is labelled
    as delta provenance."""
    items = _factors(150, CFG.k, 46)
    r = open_retriever(_spec("sharded"), items=items)
    fresh = _factors(1, CFG.k, 47)
    r.upsert([999], fresh)
    res = r.query(fresh, 5, explain=True)
    assert res.ids[0, 0] == 999
    assert res.explain["source"][0][0] == "delta"


@pytest.mark.parametrize("backend", ["srp-lsh", "superbit-lsh", "cro",
                                     "pca-tree"])
def test_baseline_backends_cannot_explain(backend):
    """Hash/tree baselines keep no per-shard or per-block provenance:
    explain=True is a typed UnsupportedOp, never a silently empty dict."""
    items = _factors(120, CFG.k, 48)
    users = _factors(3, CFG.k, 49)
    r = open_retriever(RetrieverSpec(cfg=CFG, backend=backend), items=items)
    with pytest.raises(UnsupportedOp, match="explain|provenance"):
        r.query(users, 10, explain=True)


# ------------------------------------------------------------ snapshot guards


def test_restore_rejects_mismatched_spec(tmp_path):
    items = _factors(80, CFG.k, 18)
    path = os.fspath(tmp_path / "snap.npz")
    open_retriever(_spec("gam"), items=items).snapshot(path)
    with pytest.raises(ValueError, match="snapshot/spec mismatch"):
        open_retriever(_spec("gam", min_overlap=3), snapshot=path)
    with pytest.raises(ValueError, match="does not match"):
        open_retriever(
            RetrieverSpec(cfg=GamConfig(k=16, threshold=0.4), backend="gam",
                          min_overlap=2, bucket=512), snapshot=path)
    with pytest.raises(ValueError, match="mismatch"):
        open_retriever(_spec("gam-device"), snapshot=path)


def test_restore_rejects_mismatched_delta_bucket(tmp_path):
    """delta_bucket is result-bearing (spill turns delta rows into
    unconditional candidates) — restoring under a different width must fail
    loudly, not silently change candidate sets."""
    items = _factors(60, CFG.k, 30)
    spec = _spec("sharded", delta_bucket=1)
    r = open_retriever(spec, items=items)
    r.upsert(np.arange(100, 110), _factors(10, CFG.k, 31))
    path = os.fspath(tmp_path / "delta.npz")
    r.snapshot(path)
    with pytest.raises(ValueError, match="delta_bucket"):
        open_retriever(_spec("sharded"), snapshot=path)


def test_open_retriever_rejects_items_plus_snapshot(tmp_path):
    items = _factors(10, CFG.k, 19)
    path = os.fspath(tmp_path / "s.npz")
    open_retriever(_spec("brute"), items=items).snapshot(path)
    with pytest.raises(ValueError, match="either items or snapshot"):
        open_retriever(_spec("brute"), items=items, snapshot=path)


def test_duplicate_ids_rejected_on_build():
    items = _factors(4, CFG.k, 20)
    for backend in BACKENDS:
        with pytest.raises(ValueError, match="unique"):
            open_retriever(_spec(backend), items=items,
                           ids=np.array([0, 1, 1, 2]))
