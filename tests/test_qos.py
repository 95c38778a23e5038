"""QoS under failure: admission control, deadlines, the degrade ladder,
circuit-breaker failover, hedged reads and seeded fault injection.

The layer's contract is pinned here from three angles:

* **Policy units** — `QosPolicy` rung selection, `FaultSpec` parsing and
  the `HealthTracker` breaker state machine are pure and clock-injected,
  so every transition is tested deterministically.
* **Microbatcher QoS** — queue caps, priority coalescing, flush-time
  deadline sheds, result eviction and the NoLiveReplica-to-typed-shed
  conversion, all under a manual clock.
* **Never silently wrong** — under any injected fault mix the retriever's
  answers are bit-identical to a fault-free run, *flagged* degraded, or a
  typed shed; the assertions here mirror what the chaos CI job checks on
  real processes.
"""
import numpy as np
import pytest
from conftest import CFG, unit_factors as _factors

from repro.obs.exporters import snapshot_to_prometheus
from repro.retriever import RetrieverSpec, open_retriever
from repro.service.collective import NoLiveReplica
from repro.service.faults import FaultInjected, FaultInjector, FaultSpec
from repro.service.metrics import ServiceMetrics
from repro.service.microbatch import Microbatcher, QueryResult
from repro.service.qos import (
    DEGRADE_RUNGS,
    HealthTracker,
    QosPolicy,
    RequestShed,
    ResultEvicted,
)


def _manual_clock():
    t = [0.0]
    return t, lambda: t[0]


def _spec(backend="sharded", **kw):
    kw.setdefault("n_shards", 2)
    kw.setdefault("min_overlap", 1)
    kw.setdefault("kappa", 8)
    if backend == "sharded-multihost":
        kw.setdefault("n_hosts", 2)
        kw.setdefault("replication", 2)
    return RetrieverSpec(cfg=CFG, backend=backend, **kw)


def _assert_same(a, b, tag=""):
    np.testing.assert_array_equal(a.ids, b.ids, err_msg=tag)
    np.testing.assert_array_equal(a.scores, b.scores, err_msg=tag)


# ------------------------------------------------------------ policy units


def test_choose_rung_is_a_pure_threshold_ladder():
    pol = QosPolicy(degrade_ratios=(1.0, 0.5, 0.25))
    assert pol.choose_rung(None, 1.0) == 0          # no deadline -> full
    assert pol.choose_rung(0.0, 1.0) == 3           # budget spent -> floor
    assert pol.choose_rung(-1.0, None) == 3
    assert pol.choose_rung(5.0, None) == 0          # no estimate yet
    assert pol.choose_rung(1.0, 1.0) == 0           # ratio 1.0
    assert pol.choose_rung(0.7, 1.0) == 1           # ratio 0.7
    assert pol.choose_rung(0.3, 1.0) == 2           # ratio 0.3
    assert pol.choose_rung(0.1, 1.0) == 3           # ratio 0.1
    assert DEGRADE_RUNGS == ("none", "skip_exact", "raise_overlap",
                             "base_only")


def test_policy_per_class_tuples_broadcast_last_entry():
    pol = QosPolicy(queue_caps=(4, 64), deadlines_s=(0.01,))
    assert pol.queue_cap(0) == 4
    assert pol.queue_cap(1) == 64
    assert pol.queue_cap(9) == 64                   # beyond -> last entry
    assert pol.deadline_for(0) == pol.deadline_for(7) == 0.01
    noop = QosPolicy()
    assert noop.queue_cap(0) is None and noop.deadline_for(0) is None


def test_policy_rides_in_spec_options():
    spec = _spec(options=(("queue_caps", (8,)), ("hedge_factor", 3.0)))
    pol = QosPolicy.from_spec(spec)
    assert pol.queue_caps == (8,) and pol.hedge_factor == 3.0
    assert pol.deadlines_s is None                  # absent -> no-op default


def test_fault_spec_parses_and_validates():
    fs = FaultSpec.parse("stall=0.1,drop=0.05,slow=0.3:0.02,"
                         "delta_error=0.01,hosts=1+2")
    assert fs.stall == 0.1 and fs.drop == 0.05
    assert fs.slow == 0.3 and fs.slow_s == 0.02
    assert fs.delta_error == 0.01 and fs.hosts == (1, 2)
    with pytest.raises(ValueError):
        FaultSpec.parse("stall=0.9,drop=0.9")       # p sums past 1
    with pytest.raises(ValueError):
        FaultSpec.parse("nonsense=1")               # unknown key is loud
    with pytest.raises(ValueError):
        FaultSpec(stall=1.5)                        # not a probability


def test_fault_fates_are_seed_deterministic_and_routing_independent():
    """SPMD safety: two injectors with the same seed deal identical fates
    regardless of what the caller does between rounds — exactly n_hosts
    draws per round, in host order."""
    a = FaultInjector("stall=0.3,slow=0.2:0.01", seed=11)
    b = FaultInjector("stall=0.3,slow=0.2:0.01", seed=11)
    for _ in range(50):
        assert a.host_fates(3) == b.host_fates(3)
    # a restricted injector still burns one draw per host, so fates stay
    # aligned across processes whatever the hosts= restriction
    c = FaultInjector("stall=0.5,hosts=0", seed=7)
    d = FaultInjector("stall=0.5,hosts=0+1", seed=7)
    for _ in range(50):
        fc, fd = c.host_fates(2), d.host_fates(2)
        assert fc[1] == (None, 0.0)                 # host 1 excluded in c
        assert fc[0] == fd[0]                       # same draw for host 0


# ------------------------------------------------------------- breaker unit


def test_duration_clocks_are_monotonic_and_survive_clock_steps():
    """Regression for the wall-vs-monotonic audit: every duration clock in
    the serving stack defaults to ``time.monotonic`` (a wall clock stepping
    under NTP correction must never fire deadlines, probes or staleness
    pushes spuriously), and a backward step of an injected clock — what a
    wall clock would have done — leaves all that machinery quiescent."""
    import time
    import types

    from repro.online.push import PushPolicy

    assert HealthTracker(2).clock is time.monotonic
    assert Microbatcher(_null_query_fn, dim=4).clock is time.monotonic
    assert PushPolicy(types.SimpleNamespace()).clock is time.monotonic
    ret = open_retriever(_spec(), _factors(20, 16, 0))
    assert ret.clock is time.monotonic
    assert PushPolicy(ret).clock is time.monotonic   # inherited from owner

    # microbatcher: a backward step must not age the queue into a deadline
    # flush; only genuinely elapsed time on the same clock does
    t, clock = _manual_clock()
    mb = Microbatcher(_null_query_fn, dim=4, batch_size=8, clock=clock,
                      max_delay_s=0.5)
    mb.submit(np.zeros(4))
    t[0] = -3600.0
    assert not mb.poll() and mb.pending == 1
    t[0] = 0.6
    assert mb.poll() and mb.pending == 0

    # breaker: a backward step must not count down the probe backoff
    t[0] = 0.0
    ht = HealthTracker(2, failures=1, probe_s=1.0, clock=clock)
    ht.record_failure(0)
    t[0] = -3600.0
    assert ht.due_probes() == []
    t[0] = 1.5
    assert ht.due_probes() == [0]

    # push policy: a backward step must not make a fresh candidate "stale"
    t[0] = 0.0
    pushed = []
    stub = types.SimpleNamespace(upsert=lambda i, f: pushed.append(len(i)))
    pol = PushPolicy(stub, min_cos=0.5, staleness_s=60.0, clock=clock)
    f0 = np.ones(16, np.float32)
    pol.seed([7], f0[None])
    pol.offer([7], f0[None])            # cos == 1: only staleness can push
    t[0] = -3600.0
    ids, _ = pol.flush()
    assert ids.size == 0 and pol.pending_ids.tolist() == [7]
    t[0] = 61.0
    ids, _ = pol.flush()
    assert ids.tolist() == [7] and pushed == [1]


def test_breaker_opens_probes_and_closes_deterministically():
    t, clock = _manual_clock()
    opened, closed = [], []
    m = ServiceMetrics(clock)
    ht = HealthTracker(2, failures=3, probe_s=1.0, probe_max_s=4.0,
                       clock=clock, on_open=opened.append,
                       on_close=closed.append, metrics=m)
    ht.record_failure(1)
    ht.record_failure(1)
    assert not ht.is_open(1)                        # streak 2 < 3
    ht.record_success(1)                            # success resets streak
    ht.record_failure(1)
    ht.record_failure(1)
    ht.record_failure(1)
    assert ht.is_open(1) and opened == [1]          # 3 consecutive -> open
    assert ht.due_probes() == []                    # backoff not elapsed
    t[0] = 1.5
    assert ht.due_probes() == [1]
    ht.probe_result(1, ok=False)                    # failed probe: backoff x2
    assert ht.due_probes() == []
    t[0] = 1.5 + 1.9
    assert ht.due_probes() == []                    # 2.0s backoff
    t[0] = 1.5 + 2.1
    assert ht.due_probes() == [1]
    ht.probe_result(1, ok=True)
    assert not ht.is_open(1) and closed == [1]
    snap = m.snapshot()
    assert snap["breaker_opens"] == 1
    assert snap["breaker_probes"] == 2 and snap["breaker_closes"] == 1
    # further failures below threshold keep it closed
    ht.record_failure(1)
    assert not ht.is_open(1)


# ------------------------------------------------------- microbatcher QoS


def _null_query_fn(users, n_real):
    b = users.shape[0]
    return np.zeros((b, 3), np.int64), np.zeros((b, 3), np.float32)


def test_queue_cap_sheds_loudly_per_class():
    t, clock = _manual_clock()
    m = ServiceMetrics(clock)
    mb = Microbatcher(_null_query_fn, dim=4, batch_size=64, clock=clock,
                      metrics=m, policy=QosPolicy(queue_caps=(2, 1)))
    mb.submit(np.zeros(4), priority=0)
    mb.submit(np.zeros(4), priority=0)
    with pytest.raises(RequestShed) as ei:
        mb.submit(np.zeros(4), priority=0)          # class-0 cap is 2
    assert ei.value.reason == "queue_full" and ei.value.priority == 0
    mb.submit(np.zeros(4), priority=1)              # class 1 has its own cap
    with pytest.raises(RequestShed):
        mb.submit(np.zeros(4), priority=1)
    snap = m.snapshot()
    assert snap["shed_total"] == 2 == snap["shed_queue_full"]
    assert snap["shed_by_class"] == {"0": 1, "1": 1}
    assert mb.pending == 3


def test_priority_coalescing_serves_class0_first():
    """When the queue holds more than one batch's worth, a flush takes the
    highest-priority (then oldest) requests; best-effort traffic waits."""
    seen = []

    def query_fn(users, n_real):
        seen.append(users[:n_real, 0].astype(int).tolist())
        return _null_query_fn(users, n_real)

    t, clock = _manual_clock()
    mb = Microbatcher(query_fn, dim=1, batch_size=4, clock=clock)
    ids = {}
    for i, pr in enumerate([1, 1, 1, 0, 0]):        # 3 best-effort first
        mb.batch_size = 8                           # hold the size trigger
        ids[i] = mb.submit(np.full(1, float(i)), priority=pr)
        mb.batch_size = 4
    t[0] += 1.0
    mb.poll()
    assert mb.pending == 0
    # first batch = the two class-0 rows (3, 4) then the two oldest class-1
    assert seen[0] == [3, 4, 0, 1] and seen[1] == [2]
    assert all(isinstance(mb.result(r), QueryResult) for r in ids.values())


def test_poll_drains_every_overdue_batch():
    """A driver that stalled between polls catches up in ONE poll() call:
    the deadline trigger loops until no overdue request remains."""
    t, clock = _manual_clock()
    mb = Microbatcher(_null_query_fn, dim=4, batch_size=8,
                      max_delay_s=0.01, clock=clock)
    rids = [mb.submit(np.zeros(4)) for _ in range(5)]
    mb.batch_size = 2                               # stalled-driver backlog
    t[0] += 1.0
    assert mb.poll()                                # one call ...
    assert mb.pending == 0                          # ... drains 3 batches
    assert all(isinstance(mb.result(r), QueryResult) for r in rids)


def test_flush_sheds_requests_whose_deadline_already_expired():
    t, clock = _manual_clock()
    m = ServiceMetrics(clock)
    mb = Microbatcher(_null_query_fn, dim=4, batch_size=4, clock=clock,
                      metrics=m, policy=QosPolicy(deadlines_s=(0.05,)))
    dead = mb.submit(np.zeros(4))                   # policy deadline 50ms
    alive = mb.submit(np.zeros(4), deadline_s=10.0)  # explicit override
    t[0] += 0.1                                     # both wait 100ms
    mb.flush()
    shed = mb.result(dead)
    assert isinstance(shed, RequestShed)
    assert shed.reason == "deadline" and shed.waited_s == pytest.approx(0.1)
    assert isinstance(mb.result(alive), QueryResult)
    assert m.snapshot()["shed_deadline"] == 1
    # an all-shed batch burns no device pass
    rid = mb.submit(np.zeros(4))
    t[0] += 0.1
    before = m.snapshot()["n_batches"]
    mb.flush()
    assert isinstance(mb.result(rid), RequestShed)
    assert m.snapshot()["n_batches"] == before


def test_result_eviction_is_typed_and_counted():
    t, clock = _manual_clock()
    m = ServiceMetrics(clock)
    mb = Microbatcher(_null_query_fn, dim=4, batch_size=1, clock=clock,
                      metrics=m, max_results=2)
    r0 = mb.submit(np.zeros(4))                     # batch_size=1: instant
    r1 = mb.submit(np.zeros(4))
    r2 = mb.submit(np.zeros(4))                     # evicts r0
    out = mb.result(r0)
    assert isinstance(out, ResultEvicted) and out.req_id == r0
    assert mb.result(r0) is None                    # marker pops exactly once
    assert isinstance(mb.result(r1), QueryResult)
    assert isinstance(mb.result(r2), QueryResult)
    assert mb.result(12345) is None                 # unknown id stays None
    assert m.snapshot()["evicted_total"] == 1


def test_no_live_replica_becomes_typed_sheds_and_serving_continues():
    """Satellite of the failover story: an unservable round (NoLiveReplica
    from the backend) must not strand the batch — every member becomes a
    typed shed and later batches serve normally."""
    t, clock = _manual_clock()
    m = ServiceMetrics(clock)
    fail = [True]

    def query_fn(users, n_real):
        if fail[0]:
            raise NoLiveReplica(0, (0, 1))
        return _null_query_fn(users, n_real)

    mb = Microbatcher(query_fn, dim=4, batch_size=2, clock=clock, metrics=m)
    a = mb.submit(np.zeros(4))
    b = mb.submit(np.zeros(4))                      # fires, raises, sheds
    for rid in (a, b):
        out = mb.result(rid)
        assert isinstance(out, RequestShed)
        assert out.reason == "no_live_replica"
    fail[0] = False
    c = mb.submit(np.zeros(4))
    d = mb.submit(np.zeros(4))
    assert isinstance(mb.result(c), QueryResult)
    assert isinstance(mb.result(d), QueryResult)
    assert m.snapshot()["shed_no_live_replica"] == 2


# ------------------------------------------------------- degrade ladder


def test_degrade_ladder_rungs_are_flagged_and_deterministic():
    items = _factors(300, CFG.k, 0)
    users = _factors(6, CFG.k, 1)
    svc = open_retriever(_spec(), items=items)
    full = svc.query(users)
    full_exact = svc.query(users, exact=True)

    # a generous budget never degrades and answers identically
    svc._cost_est = 1.0
    res = svc.query(users, deadline_s=50.0)
    assert not res.degraded and res.degrade_rung is None
    _assert_same(res, full)

    # rung 1 skips the exact re-rank: flagged, equals the non-exact answer
    r1 = svc.query(users, exact=True, deadline_s=0.7)
    assert r1.degraded and r1.degrade_rung == "skip_exact"
    _assert_same(r1, full)
    # ... but a request that never asked for exact loses nothing at rung 1
    r1n = svc.query(users, deadline_s=0.7)
    assert not r1n.degraded
    _assert_same(r1n, full)

    # rung 2 raises the prune threshold one notch
    svc._cost_est = 1.0
    r2 = svc.query(users, deadline_s=0.3)
    assert r2.degraded and r2.degrade_rung == "raise_overlap"
    stricter = open_retriever(_spec(min_overlap=2), items=items)
    _assert_same(r2, stricter.query(users), "raise_overlap == min_overlap+1")

    # rung 3 serves the base segment only (here: delta rows vanish)
    svc.upsert([10_000], _factors(1, CFG.k, 9))
    svc._cost_est = 1.0
    r3 = svc.query(users, deadline_s=0.1)
    assert r3.degraded and r3.degrade_rung == "base_only"
    assert 10_000 not in set(r3.ids.ravel().tolist())

    snap = svc.metrics.snapshot()
    assert snap["degraded_total"] == 3
    assert snap["degraded_skip_exact"] == 1
    assert snap["degraded_raise_overlap"] == 1
    assert snap["degraded_base_only"] == 1
    # degrade counters reach the Prometheus exposition
    prom = snapshot_to_prometheus(snap)
    assert "repro_degraded_total 3" in prom
    assert "repro_shed_total 0" in prom

    ex = svc.query(users, explain=True)
    assert ex.explain["degraded"] is False and ex.explain["degrade_rung"] is None
    svc._cost_est = 1.0
    ex3 = svc.query(users, deadline_s=0.1, explain=True)
    assert ex3.explain["degraded"] is True
    assert ex3.explain["degrade_rung"] == "base_only"


def test_degrade_cost_estimate_recovers_after_a_spike():
    """One pathological cost sample (e.g. a recompile) must not lock the
    ladder at the floor forever: the estimate decays while degrading until
    full service is re-probed."""
    items = _factors(200, CFG.k, 2)
    users = _factors(4, CFG.k, 3)
    svc = open_retriever(_spec(), items=items)
    svc.query(users)                                # healthy estimate
    svc._cost_est = 1e3                             # inject a spike
    degraded_then_recovered = []
    for _ in range(300):
        r = svc.query(users, deadline_s=5.0)
        degraded_then_recovered.append(r.degraded)
        if not r.degraded:
            break
    assert degraded_then_recovered[0] is True       # spike took effect
    assert degraded_then_recovered[-1] is False     # and wore off


# ----------------------------------------------- faults, breaker, hedging


def test_multihost_serves_around_faults_bit_identically():
    items = _factors(300, CFG.k, 0)
    users = _factors(8, CFG.k, 1)
    oracle = open_retriever(_spec(backend="sharded"), items=items)
    want = oracle.query(users)
    fi = FaultInjector("stall=0.4,drop=0.2,hosts=1", seed=5)
    svc = open_retriever(_spec(backend="sharded-multihost"), items=items,
                         faults=fi, qos=QosPolicy(breaker_failures=10**9))
    for i in range(25):
        got = svc.query(users)
        assert not got.degraded
        _assert_same(got, want, f"round {i}")
    assert fi.n_stalls + fi.n_drops > 0             # chaos actually happened
    assert svc.metrics.n_failovers > 0


@pytest.mark.parametrize("qos_on", [True, False], ids=["qos", "no_qos"])
def test_overload_stream_under_faults_is_typed_and_never_wrong(qos_on):
    """Twelve bursts of 16 requests (10 priority-0, 6 priority-1) against
    two batches of 4 served a round, on two replicated hosts whose second
    stalls or runs slow on a seeded share of the rounds.  Every admitted
    request ends answered or with a typed outcome (none lost), and every
    answer not flagged degraded is the fault-free ``sharded`` oracle's.
    With QoS the queue caps and the priority-1 deadline shed the backlog;
    without it the whole backlog is served."""
    items = _factors(300, CFG.k, 13)
    burst, rounds, bs = 16, 12, 4
    users = _factors(rounds * burst, CFG.k, 14)
    want = open_retriever(_spec(), items=items).query(users)
    t, clock = _manual_clock()
    policy = (QosPolicy(queue_caps=(8, 4), deadlines_s=(60.0, 0.05),
                        hedge_factor=3.0) if qos_on else None)
    fi = FaultInjector("stall=0.25,slow=0.25:0.05,hosts=1", seed=0)
    svc = open_retriever(
        _spec("sharded-multihost", batch_size=bs, max_delay_s=60.0),
        items=items, faults=fi, qos=policy, clock=clock)
    mb = svc.batcher
    admitted, admission_sheds = [], 0
    for r in range(rounds):
        mb.batch_size = len(users) + 1          # the burst lands as a backlog
        for j in range(burst):
            row = r * burst + j
            try:
                admitted.append((mb.submit(users[row],
                                           priority=int(j >= 10)), row))
            except RequestShed as shed:
                assert shed.reason == "queue_full"
                admission_sheds += 1
        mb.batch_size = bs
        mb.flush()                              # 8 served, 16 arrived
        mb.flush()
        t[0] += 0.02
    while mb.pending:
        mb.flush()

    kinds = {"exact": 0, "degraded": 0, "shed": 0, "evicted": 0}
    for rid, row in admitted:
        got = mb.result(rid)
        assert got is not None, f"request {rid} lost"
        if isinstance(got, RequestShed):
            kinds["shed"] += 1
        elif isinstance(got, ResultEvicted):
            kinds["evicted"] += 1
        elif got.degraded:
            kinds["degraded"] += 1
        else:
            kinds["exact"] += 1
            np.testing.assert_array_equal(got.ids, want.ids[row])
            np.testing.assert_array_equal(got.scores, want.scores[row])
    assert kinds["exact"] > 0
    assert fi.n_stalls > 0                      # chaos actually happened
    snap = svc.metrics.snapshot()
    if qos_on:
        assert admission_sheds >= 1 and snap["shed_total"] >= 1
        assert snap["n_failovers"] >= 1
    else:
        assert admission_sheds == 0 and snap["shed_total"] == 0
        assert kinds["exact"] + kinds["degraded"] + kinds["evicted"] \
            == rounds * burst


def test_breaker_auto_marks_down_and_probe_recovers():
    t, clock = _manual_clock()
    items = _factors(300, CFG.k, 0)
    users = _factors(8, CFG.k, 1)
    want = open_retriever(_spec(backend="sharded"), items=items).query(users)
    svc = open_retriever(
        _spec(backend="sharded-multihost"), items=items, clock=clock,
        faults=FaultInjector("stall=1.0,hosts=1", seed=0),
        qos=QosPolicy(breaker_failures=2, breaker_probe_s=1.0))
    _assert_same(svc.query(users), want)            # round 1: streak 1
    _assert_same(svc.query(users), want)            # round 2: breaker opens
    assert svc.health.is_open(1)
    assert svc.host_status()["down"] == [1]
    assert svc.metrics.snapshot()["breaker_opens"] == 1
    # fault persists: the due probe fails and backs off exponentially
    t[0] = 1.5
    _assert_same(svc.query(users), want)
    assert svc.health.is_open(1)
    # fault clears: the next due probe closes the breaker (auto mark_up)
    svc.faults = None
    t[0] = 10.0
    _assert_same(svc.query(users), want)
    assert not svc.health.is_open(1)
    assert svc.host_status()["down"] == []
    snap = svc.metrics.snapshot()
    assert snap["breaker_closes"] == 1 and snap["breaker_probes"] == 2
    kinds = [e["kind"] for e in svc.events.tail(100)]
    assert "breaker_open" in kinds and "breaker_close" in kinds


def test_manual_mark_down_is_never_auto_probed():
    t, clock = _manual_clock()
    items = _factors(200, CFG.k, 4)
    users = _factors(4, CFG.k, 5)
    svc = open_retriever(_spec(backend="sharded-multihost"), items=items,
                         clock=clock)
    svc.mark_down(1)
    t[0] = 1e6                                      # any amount of time
    svc.query(users)
    assert svc.host_status()["down"] == [1]         # operator's call stands


def test_every_replica_faulted_raises_no_live_replica():
    items = _factors(200, CFG.k, 6)
    users = _factors(4, CFG.k, 7)
    svc = open_retriever(_spec(backend="sharded-multihost"), items=items,
                         faults=FaultInjector("stall=1.0", seed=0),
                         qos=QosPolicy(breaker_failures=10**9))
    with pytest.raises(NoLiveReplica):
        svc.query(users)


def test_hedged_reads_fire_and_stay_bit_identical():
    t, clock = _manual_clock()
    items = _factors(300, CFG.k, 0)
    users = _factors(8, CFG.k, 1)
    want = open_retriever(_spec(backend="sharded"), items=items).query(users)
    svc = open_retriever(
        _spec(backend="sharded-multihost"), items=items, clock=clock,
        qos=QosPolicy(hedge_factor=2.0, hedge_min_samples=4))
    # manual clock: each host call costs 1ms until the spike is switched
    # on — a latency spike far past the learned p99 triggers the hedge
    spike = [False]
    real_topk = svc.base.slices_topk

    def topk(slice_ids, *a, **kw):
        t[0] += 1.0 if spike[0] else 0.001
        return real_topk(slice_ids, *a, **kw)

    svc.base.slices_topk = topk
    for i in range(10):                             # learn the baseline p99
        _assert_same(svc.query(users), want, f"warm {i}")
    assert svc.metrics.snapshot()["hedge_issued"] == 0
    spike[0] = True
    _assert_same(svc.query(users), want, "spike round")
    spike[0] = False
    _assert_same(svc.query(users), want, "after spike")
    snap = svc.metrics.snapshot()
    assert snap["hedge_issued"] > 0                 # hedges fired ...
    assert snap["hedge_issued"] >= snap["hedge_wins"]
    kinds = [e["kind"] for e in svc.events.tail(200)]
    assert "hedged_read" in kinds


def test_delta_fault_raises_before_mutation():
    items = _factors(200, CFG.k, 8)
    users = _factors(4, CFG.k, 9)
    svc = open_retriever(_spec(), items=items,
                         faults=FaultInjector("delta_error=1.0", seed=0))
    before = svc.query(users)
    with pytest.raises(FaultInjected) as ei:
        svc.upsert([5000], _factors(1, CFG.k, 10))
    assert ei.value.kind == "delta_apply"
    assert svc.n_items == 200 and len(svc.delta) == 0   # atomic: no mutation
    _assert_same(svc.query(users), before)
    with pytest.raises(FaultInjected):
        svc.delete([3])
    assert svc.faults.n_delta_errors == 2


def test_deadline_threads_through_the_batcher_to_the_ladder():
    items = _factors(300, CFG.k, 0)
    svc = open_retriever(_spec(batch_size=2), items=items,
                         qos=QosPolicy(deadlines_s=(1e-9,)))
    svc.query(_factors(2, CFG.k, 1))                # warm the cost estimate
    r0 = svc.batcher.submit(_factors(1, CFG.k, 2)[0])
    r1 = svc.batcher.submit(_factors(1, CFG.k, 3)[0])
    out = svc.batcher.result(r0)
    # a 1ns budget either sheds at flush or answers degraded -- never a
    # silent full-cost answer
    if isinstance(out, QueryResult):
        assert out.degraded and out.degrade_rung in DEGRADE_RUNGS
    else:
        assert isinstance(out, RequestShed)
    assert type(svc.batcher.result(r1)) is type(out)
