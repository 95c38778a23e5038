"""Online-learning tier: event batches, the streaming trainer, the
geometry-aware push policy, and the end-to-end drift contract.

The acceptance property mirrors the maintenance suites': pushing
re-trained factors through ``PushPolicy`` may change WHAT the index
serves, but never silently — after any drift run, the live retriever's
answers must be bit-identical to a from-scratch rebuild of the same
catalog state (the pushed factors are the catalog state), on every
first-class backend.
"""
import numpy as np
import pytest
from conftest import CFG, assert_scores_match, unit_factors

from repro.factorization import MfConfig, MfState, train_mf
from repro.online import (DriftSimulator, EventBatch, OnlineMFConfig,
                          PushPolicy, StreamingMF)
from repro.retriever import RetrieverSpec, open_retriever
from repro.retriever.types import dedupe_last_write
from repro.service.faults import FaultInjected

K = CFG.k


# ------------------------------------------------------------- event batches


def test_event_batch_stable_sorts_by_timestamp():
    ev = EventBatch(ts=[3.0, 1.0, 2.0, 1.0], users=[10, 11, 12, 13],
                    items=[0, 1, 2, 3], values=[0.3, 0.1, 0.2, 0.15])
    assert list(ev.ts) == [1.0, 1.0, 2.0, 3.0]
    # stable: the two ts=1.0 events keep producer order (11 before 13)
    assert list(ev.users) == [11, 13, 12, 10]
    assert len(ev) == 4


def test_event_batch_validates():
    with pytest.raises(ValueError, match="lengths"):
        EventBatch(ts=[1.0], users=[0, 1], items=[0], values=[1.0])
    with pytest.raises(ValueError, match="negative"):
        EventBatch(ts=[1.0], users=[-1], items=[0], values=[1.0])


def test_event_batch_jsonl_round_trip(tmp_path):
    rng = np.random.default_rng(0)
    ev = EventBatch(ts=np.arange(32, dtype=np.float64),
                    users=rng.integers(0, 8, 32),
                    items=rng.integers(0, 50, 32),
                    values=rng.normal(size=32))
    path = tmp_path / "events.jsonl"
    ev.to_jsonl(path)
    back = EventBatch.from_jsonl(path)
    np.testing.assert_array_equal(ev.ts, back.ts)
    np.testing.assert_array_equal(ev.users, back.users)
    np.testing.assert_array_equal(ev.items, back.items)
    np.testing.assert_array_equal(ev.values, back.values)
    # value is optional in the schema and defaults to implicit 1.0
    (tmp_path / "min.jsonl").write_text('{"ts": 0.5, "user": 2, "item": 7}\n')
    minimal = EventBatch.from_jsonl(tmp_path / "min.jsonl")
    assert list(minimal.values) == [1.0]


def test_event_batch_concat_resorts():
    a = EventBatch(ts=[2.0], users=[0], items=[0], values=[1.0])
    b = EventBatch(ts=[1.0], users=[1], items=[1], values=[1.0])
    cat = EventBatch.concat([a, b])
    assert list(cat.ts) == [1.0, 2.0]
    assert len(EventBatch.empty()) == 0


# --------------------------------------------------------- streaming trainer


def _observations(rng, users, items, n, noise=0.0):
    u = rng.integers(0, users.shape[0], n)
    i = rng.integers(0, items.shape[0], n)
    vals = np.sum(users[u] * items[i], axis=1)
    if noise:
        vals = vals + noise * rng.normal(size=n)
    return EventBatch(ts=np.arange(n, dtype=np.float64), users=u, items=i,
                      values=vals.astype(np.float32))


def test_partial_fit_reduces_mse():
    rng = np.random.default_rng(7)
    users = unit_factors(16, K, 1)
    items = unit_factors(32, K, 2)
    t = StreamingMF(OnlineMFConfig(k=K, lr=0.5, momentum=0.6, seed=3))
    ev = _observations(rng, users, items, 512)
    first = t.partial_fit(ev)["mse"]
    for _ in range(8):
        last = t.partial_fit(ev)["mse"]
    assert last < first * 0.5
    stats = t.stats()
    assert stats["n_events"] == 512 * 9
    assert stats["n_users"] == 16 and stats["n_items"] == 32


def test_touched_ids_and_factor_getters():
    t = StreamingMF(OnlineMFConfig(k=K, seed=0))
    ev = EventBatch(ts=[0.0, 1.0], users=[3, 5], items=[7, 7],
                    values=[0.5, 0.25])
    fit = t.partial_fit(ev)
    np.testing.assert_array_equal(fit["touched_users"], [3, 5])
    np.testing.assert_array_equal(fit["touched_items"], [7])
    assert t.item_factors([7]).shape == (1, K)
    assert t.user_factors().shape == (6, K)
    with pytest.raises(IndexError):
        t.item_factors([99])


def test_capacity_growth_is_pow2_and_path_independent():
    """Cold-start rows are seeded per capacity block, so growing 64->512
    directly and growing 64->128->512 materialise bit-identical tables."""
    cfg = OnlineMFConfig(k=K, seed=11)
    big = EventBatch(ts=[0.0], users=[0], items=[511], values=[1.0])
    small = EventBatch(ts=[0.0], users=[0], items=[100], values=[1.0])

    t1 = StreamingMF(cfg)
    t1.partial_fit(big)                      # 64 -> 512 in one grow
    t2 = StreamingMF(cfg)
    t2.partial_fit(small)                    # 64 -> 128
    t2.partial_fit(big)                      # 128 -> 512
    assert t1.capacity[1] == t2.capacity[1] == 512
    assert t2.n_grows > t1.n_grows
    cold = np.setdiff1d(np.arange(512), [0, 100, 511])
    np.testing.assert_array_equal(t1.item_factors()[cold],
                                  t2.item_factors()[cold])


def test_warm_start_adopts_train_mf_state_bit_exactly():
    rng = np.random.default_rng(5)
    rows = rng.integers(0, 12, 256)
    cols = rng.integers(0, 20, 256)
    vals = rng.normal(loc=3.0, size=256).astype(np.float32)
    cfg = MfConfig(k=K, epochs=2, batch=128, seed=9)
    u0, v0, h0 = train_mf(rows, cols, vals, 12, 20, cfg)
    u1, v1, h1, state = train_mf(rows, cols, vals, 12, 20, cfg,
                                 return_state=True)
    # the return_state spelling changes NOTHING about the training outputs
    np.testing.assert_array_equal(u0, u1)
    np.testing.assert_array_equal(v0, v1)
    assert h0 == h1
    assert isinstance(state, MfState)
    assert state.offset == pytest.approx(float(vals.mean()))

    t = StreamingMF.from_state(state, OnlineMFConfig(k=K))
    np.testing.assert_array_equal(t.user_factors(), u1)
    np.testing.assert_array_equal(t.item_factors(), v1)
    assert t.offset == state.offset
    np.testing.assert_array_equal(
        np.asarray(t._vel["v"][:20]), np.asarray(state.vel["v"]))


# -------------------------------------------------------------- push policy


class _RecordingRetriever:
    """Minimal upsert sink: records batches, optionally faults."""

    def __init__(self, fail=False):
        self.batches = []
        self.fail = fail

    def upsert(self, ids, factors):
        if self.fail:
            raise FaultInjected("delta_error")
        self.batches.append((np.asarray(ids).copy(),
                             np.asarray(factors).copy()))


def _policy(retriever, clock, **kw):
    kw.setdefault("min_cos", 0.99)
    kw.setdefault("staleness_s", 5.0)
    return PushPolicy(retriever, clock=lambda: clock[0], **kw)


def test_push_gate_cold_drift_stale_suppress():
    r = _RecordingRetriever()
    clock = [0.0]
    p = _policy(r, clock)
    f = unit_factors(1, K, 0)

    p.offer([1], f)                          # never pushed before
    ids, _ = p.flush()
    assert list(ids) == [1] and len(r.batches) == 1

    p.offer([1], 2.0 * f)                    # same direction: cos == 1
    ids, _ = p.flush()
    assert ids.size == 0 and len(r.batches) == 1
    assert list(p.pending_ids) == [1]        # suppressed stays pending

    clock[0] += 10.0                         # past the staleness budget
    ids, _ = p.flush()
    assert list(ids) == [1] and p.pending_ids.size == 0

    rot = unit_factors(1, K, 99)             # far off-axis: drift gate
    p.offer([1], rot)
    ids, fac = p.flush()
    assert list(ids) == [1]
    np.testing.assert_array_equal(fac, rot)
    assert p.n_pushed == 3 and p.n_suppressed == 1
    assert 0 < p.stats()["suppression_rate"] < 1


def test_push_seed_registers_without_pushing():
    r = _RecordingRetriever()
    clock = [0.0]
    p = _policy(r, clock)
    base = unit_factors(4, K, 3)
    p.seed(np.arange(4), base)
    assert not r.batches
    p.offer(np.arange(4), base)              # identical to what's served
    ids, _ = p.flush()
    assert ids.size == 0 and not r.batches   # all suppressed


def test_push_duplicate_offers_last_write_wins():
    r = _RecordingRetriever()
    p = _policy(r, [0.0])
    f1 = unit_factors(1, K, 1)
    f2 = unit_factors(1, K, 2)
    p.offer([5], f1)
    p.offer([5], f2)
    ids, fac = p.flush(force=True)
    assert list(ids) == [5] and len(r.batches) == 1
    np.testing.assert_array_equal(fac, f2)   # the later offer won

    # the underlying contract helper this rides on
    d_ids, d_fac = dedupe_last_write(
        np.asarray([5, 6, 5], np.int64),
        np.stack([f1[0], f1[0], f2[0]]))
    np.testing.assert_array_equal(np.sort(d_ids), [5, 6])
    np.testing.assert_array_equal(d_fac[list(d_ids).index(5)], f2[0])


def test_push_fault_leaves_batch_pending_and_retryable():
    r = _RecordingRetriever(fail=True)
    p = _policy(r, [0.0])
    f = unit_factors(2, K, 4)
    p.offer([1, 2], f)
    with pytest.raises(FaultInjected):
        p.flush(force=True)
    # no state mutated: batch still pending, nothing recorded as pushed
    np.testing.assert_array_equal(p.pending_ids, [1, 2])
    assert p.n_pushed == 0 and not r.batches

    ok = _RecordingRetriever()
    p.retriever = ok                         # rebind (restore / failover)
    ids, _ = p.flush(force=True)
    np.testing.assert_array_equal(np.sort(ids), [1, 2])
    assert len(ok.batches) == 1


def test_push_wires_metrics_and_journal_from_sharded_retriever():
    items = unit_factors(32, K, 6)
    svc = open_retriever(RetrieverSpec(cfg=CFG, backend="sharded",
                                       n_shards=2, min_overlap=2),
                         items=items)
    p = PushPolicy(svc, min_cos=0.99, staleness_s=5.0)
    assert p.metrics is svc.metrics and p.events is svc.events
    p.seed(np.arange(32), items)
    p.offer([0, 40], np.stack([items[0], unit_factors(1, K, 8)[0]]))
    p.flush()                                # 40 cold-pushes, 0 suppressed
    snap = svc.metrics.snapshot()
    assert snap["push_total"] == 1
    assert snap["push_suppressed"] == 1
    assert snap["push_flushes"] == 1
    kinds = [e["kind"] for e in svc.events.tail()]
    assert "factor_push" in kinds


# ------------------------------------------------- end-to-end drift parity


def _drift_spec(backend):
    kw = dict(min_overlap=2, n_shards=2)
    if backend == "sharded-multihost":
        kw.update(n_hosts=2, replication=2)
    elif backend == "gam":
        kw = {}
    return RetrieverSpec(cfg=CFG, backend=backend, **kw)


@pytest.mark.parametrize("backend", ["gam", "sharded", "sharded-multihost"])
def test_drift_run_matches_from_scratch_rebuild(backend):
    """The 'zero silently wrong' contract: after rounds of drift ->
    partial_fit -> gated pushes, the live retriever answers bit-identically
    to a retriever rebuilt from scratch from the same pushed catalog."""
    sim = DriftSimulator(n_users=8, n_items=64, k=K, seed=13, drift=0.25,
                         hot_frac=0.5, events_per_round=256)
    catalog = {i: f.copy() for i, f in enumerate(sim.items_at_start)}
    svc = open_retriever(_drift_spec(backend), items=sim.items_at_start)
    t = StreamingMF(OnlineMFConfig(k=K, lr=0.5, momentum=0.6, seed=21,
                                   update_users=False))
    t.warm_start(u=sim.users, v=sim.items_at_start)
    tick = [0.0]
    policy = PushPolicy(svc, min_cos=0.995, staleness_s=2.0,
                        clock=lambda: tick[0])
    policy.seed(np.arange(sim.n_items), sim.items_at_start)

    for _ in range(3):
        tick[0] += 1.0
        fit = t.partial_fit(sim.step())
        touched = fit["touched_items"]
        policy.offer(touched, t.item_factors(touched))
        p_ids, p_fac = policy.flush()
        for i, f in zip(p_ids, p_fac):
            catalog[int(i)] = f.copy()

    assert policy.n_pushed > 0               # the gate let something through
    assert policy.n_suppressed > 0           # ... and held something back

    ids = np.asarray(sorted(catalog), np.int64)
    fresh = open_retriever(_drift_spec(backend),
                           items=np.stack([catalog[int(i)] for i in ids]),
                           ids=ids)
    for exact in (True, False):
        got = svc.query(sim.users, 8, exact=exact)
        want = fresh.query(sim.users, 8, exact=exact)
        np.testing.assert_array_equal(got.ids, want.ids)
        assert_scores_match(got.scores, want.scores)
