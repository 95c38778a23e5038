"""Retrieval-service benchmark: throughput-vs-latency curve, exact vs GAM,
the skewed-catalog compaction scenario (p99 under maintenance), and the
multi-host scenario (collective merge + failover across real processes).

Streams single-user requests through the ``Microbatcher`` front-end at a
sweep of batch sizes, for both the brute-force (``exact=True``) and the
GAM candidate-masked service path of a unified-API ``sharded`` retriever,
and records QPS + p50/p99 per-request latency per point to
``BENCH_service.json`` — the service-tier counterpart of the paper's
retrieval-speedup tables.

The multi-host scenario spawns ``--multihost-procs`` real worker processes
(``jax.distributed`` + gloo CPU collectives), serves the same catalog from
the ``sharded-multihost`` backend (replication 2), marks one host down
mid-stream, and records p50/p99 before/after the failover plus a parity
flag (every answer bit-identical to an in-process single-host ``sharded``
oracle).  Where process spawning is unavailable the same measurement runs
in-process over the simulated placement (``mode`` records which ran).

The compaction scenario builds a SKEWED clustered catalog (hot region,
delete-heavy mutation burst), then replays one fixed arrival process
through a single-server queue twice: once triggering the legacy synchronous
stop-the-world ``compact()`` mid-stream, once the background
``compact(async_=True)`` whose bounded slices ride on the queries.  Latency
is measured from intended ARRIVAL (queueing during the stall counts), so
the sync rebuild shows up as the p99 cliff it really is; the acceptance
number is p99-after-trigger, background strictly below sync.  A follow-up
skew-aware ``repartition()`` records the planned per-shard layout.

The traffic-realism scenario replays one seeded production-shaped stream —
Zipf(1.1) hot-query identities, Zipf item-popularity upserts, a delete
burst and a mid-stream compaction under diurnal inhomogeneous-Poisson
arrivals — over a ``--traffic-items`` (default 100k) compressed catalog,
once with the hot-query result cache off (its answers become the uncached
oracle) and once with it on.  The gate asserts zero silently-wrong cached
answers across the full mutation stream, a nonzero hit rate, and cache-on
p99 strictly below cache-off.

The QoS overload scenario replays one fixed burst arrival process (16
requests/round, mixed priority classes, sustained past serving capacity)
through the service's own microbatcher twice — once under a ``QosPolicy``
(per-class queue caps + deadlines), once with QoS off — while a seeded
``FaultInjector`` stalls one of two multi-host replicas.  Two acceptance
numbers: every request's outcome is exact, *flagged* degraded, or a
*typed* shed (zero lost, zero silently wrong vs a fault-free oracle), and
priority-0 p99 with QoS beats the no-QoS run (admission control sheds the
backlog that would otherwise queue in front of it).

Run:  PYTHONPATH=src python benchmarks/service_bench.py [--items N] [--out F]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from repro.core.mapping import GamConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.retriever import Retriever, RetrieverSpec, open_retriever


def run_point(svc: Retriever, users: np.ndarray, *, exact: bool) -> dict:
    """Push every user row through a fresh microbatcher; measure the stream."""
    from repro.service.metrics import ServiceMetrics
    from repro.service.microbatch import Microbatcher

    spec = svc.spec

    def query_fn(batch_users, n_real=0):
        res = svc.query(batch_users, spec.kappa, exact=exact)
        return res.ids, res.scores

    metrics = ServiceMetrics()
    mb = Microbatcher(query_fn, spec.cfg.k, batch_size=spec.batch_size,
                      max_delay_s=spec.max_delay_s, metrics=metrics)
    # warm the jit cache so the curve measures steady state, not compiles
    query_fn(np.zeros((spec.batch_size, spec.cfg.k), np.float32))
    metrics.reset()

    t0 = time.perf_counter()
    for row in users:
        mb.submit(row)
        mb.poll()
    while mb.pending:
        mb.flush()
    wall = time.perf_counter() - t0
    snap = metrics.snapshot()
    return {
        "batch_size": spec.batch_size,
        "mode": "exact" if exact else "gam",
        "n_requests": int(users.shape[0]),
        "wall_s": wall,
        "qps": users.shape[0] / wall,
        "p50_ms": snap["latency_p50_ms"],
        "p99_ms": snap["latency_p99_ms"],
        "occupancy": snap["occupancy_mean"],
    }


def _stream(svc: Retriever, users: np.ndarray) -> dict:
    """Push rows through the service's OWN batcher (so its tracer and
    queue-wait split are exercised) and return the metrics snapshot."""
    svc.query(np.zeros((svc.spec.batch_size, svc.spec.cfg.k), np.float32))
    svc.metrics.reset()
    for row in users:
        svc.batcher.submit(row)
        svc.batcher.poll()
    while svc.batcher.pending:
        svc.batcher.flush()
    return svc.metrics.snapshot()


def run_overhead_scenario(args) -> dict:
    """Instrumentation overhead: the same stream untraced vs traced at a 1%
    sample rate (the steady-state deployment setting).  The acceptance
    number is the traced/untraced p50 ratio — the noop-span fast path plus
    one RNG draw per batch should be invisible next to a kernel launch."""
    rng = np.random.default_rng(3)
    items = rng.normal(size=(args.items, args.dim)).astype(np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    n_req = max(args.requests, 64)
    users = rng.normal(size=(n_req, args.dim)).astype(np.float32)
    cfg = GamConfig(k=args.dim, scheme="parse_tree", threshold=args.threshold)

    out: dict = {"sample_rate": 0.01, "n_requests": n_req}
    for label, options in (("untraced", ()),
                           ("traced", (("trace_sample", 0.01),))):
        svc = open_retriever(
            RetrieverSpec(cfg=cfg, backend="sharded", n_shards=args.shards,
                          min_overlap=args.min_overlap, kappa=args.kappa,
                          batch_size=8, max_delay_s=5e-3, options=options),
            items=items)
        snap = _stream(svc, users)
        out[label] = {"p50_ms": snap["latency_p50_ms"],
                      "p99_ms": snap["latency_p99_ms"],
                      "qps": snap["qps"]}
    out["p50_overhead_ratio"] = (out["traced"]["p50_ms"]
                                 / max(out["untraced"]["p50_ms"], 1e-9))
    print(f"tracing overhead @1%: p50 {out['untraced']['p50_ms']:.2f}ms -> "
          f"{out['traced']['p50_ms']:.2f}ms "
          f"(ratio {out['p50_overhead_ratio']:.3f})")
    return out


def run_stage_scenario(args) -> dict:
    """Per-stage latency breakdown from a fully sampled trace of the same
    stream: p50 milliseconds spent in queue wait, phi-map, base kernel,
    delta query and top-kappa merge — the attribution the regression gate
    uses to localise a p99 movement."""
    rng = np.random.default_rng(5)
    items = rng.normal(size=(args.items, args.dim)).astype(np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    n_req = max(args.requests, 32)
    users = rng.normal(size=(n_req, args.dim)).astype(np.float32)
    cfg = GamConfig(k=args.dim, scheme="parse_tree", threshold=args.threshold)
    svc = open_retriever(
        RetrieverSpec(cfg=cfg, backend="sharded", n_shards=args.shards,
                      min_overlap=args.min_overlap, kappa=args.kappa,
                      batch_size=8, max_delay_s=5e-3,
                      options=(("trace_sample", 1.0),)),
        items=items)
    # stream some live mutations so the delta stage is non-trivial
    svc.upsert(np.arange(args.items, args.items + 16),
               rng.normal(size=(16, args.dim)).astype(np.float32))
    _stream(svc, users)
    stages: dict[str, list] = {"queue_wait": [], "map": [], "base": [],
                               "delta": [], "merge": []}
    for root in svc.tracer.finished:
        for name, acc in stages.items():
            acc.extend(sp.duration_s for sp in root.find(name)
                       if sp.duration_s is not None)
    out = {name: (float(np.percentile(v, 50)) * 1e3 if v else None)
           for name, v in stages.items()}
    out["n_traces"] = len(svc.tracer.finished)
    print("stage p50 ms: " + "  ".join(
        f"{k}={v:.3f}" for k, v in out.items()
        if isinstance(v, float)))
    return out


def skewed_catalog(n: int, dim: int, rng) -> tuple[np.ndarray, np.ndarray]:
    """Clustered catalog with geometric cluster sizes (one hot region) and
    users concentrated on the hottest clusters — the workload that erodes
    shard balance and block-skip rate on the uniform layout."""
    n_clusters = min(8, max(n, 1))     # tiny catalogs: one item per cluster
    sizes = np.array([2.0 ** -c for c in range(n_clusters)])
    sizes = np.maximum((sizes / sizes.sum() * n).astype(int), 1)
    sizes[0] = max(sizes[0] + n - sizes.sum(), 0)
    centers = rng.normal(size=(n_clusters, dim)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    items = np.concatenate([
        c + 0.05 * rng.normal(size=(s, dim)).astype(np.float32)
        for c, s in zip(centers, sizes)])
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    hot = rng.integers(0, min(2, n_clusters), size=64)  # clusters 0/1 hot
    users = (centers[hot]
             + 0.05 * rng.normal(size=(64, dim)).astype(np.float32))
    users /= np.linalg.norm(users, axis=1, keepdims=True)
    return items, users


def run_compaction_scenario(args) -> dict:
    """p99 during compaction: synchronous stop-the-world vs background."""
    rng = np.random.default_rng(7)
    items, users = skewed_catalog(args.items, args.dim, rng)
    cfg = GamConfig(k=args.dim, scheme="parse_tree", threshold=args.threshold)
    spec = RetrieverSpec(cfg=cfg, backend="sharded", n_shards=args.shards,
                         min_overlap=args.min_overlap, kappa=args.kappa)
    n_req = max(args.requests, 48)
    trigger = n_req // 4
    out: dict = {"n_requests": n_req, "trigger_at": trigger}

    for mode in ("sync", "async"):
        svc = open_retriever(spec, items=items)
        # delete-heavy burst + fresh upserts: the delta compact() must fold
        dead = np.arange(0, args.items, 5)
        svc.delete(dead)
        svc.upsert(np.arange(args.items, args.items + args.items // 8),
                   rng.normal(size=(args.items // 8, args.dim))
                   .astype(np.float32))
        # warm the jit cache — query path AND the maintenance path's fixed
        # slice shape (one aborted background step) — then size the arrival
        # gap off the steady state; compiles are excluded from the curve,
        # matching the bench's stated steady-state policy
        for w in range(3):
            svc.query(users[w % len(users)][None])
        svc.start_compaction()
        svc.compaction_step()
        svc.abort_compaction()
        t0 = time.perf_counter()
        svc.query(users[0][None])
        gap = max(time.perf_counter() - t0, 1e-4) * 1.5
        svc.metrics.reset()

        # single-server queue over one fixed arrival process: latency from
        # intended arrival, so a stop-the-world stall backs requests up
        server_free = 0.0
        lats = []
        for i in range(n_req):
            arrival = i * gap
            if i == trigger:
                if mode == "sync":
                    t0 = time.perf_counter()
                    svc.compact()
                    server_free = max(server_free, arrival) + \
                        (time.perf_counter() - t0)
                else:
                    svc.compact(async_=True)   # slices ride on the queries
            start = max(arrival, server_free)
            t0 = time.perf_counter()
            svc.query(users[i % len(users)][None])
            server_free = start + (time.perf_counter() - t0)
            lats.append(server_free - arrival)
        while svc.maintenance_stats()["compaction"]["active"]:
            svc.compaction_step()
        after = np.asarray(lats[trigger:])
        out[mode] = {
            "p50_ms": float(np.percentile(after, 50)) * 1e3,
            "p99_ms": float(np.percentile(after, 99)) * 1e3,
            "max_ms": float(after.max()) * 1e3,
            "generation": svc.maintenance_stats()["generation"],
            "compact_slices": svc.metrics.n_compact_slices,
        }
        if mode == "async":
            # skew-aware follow-up: record the plan the repartitioner emits
            part = svc.repartition(async_=False)
            out["repartition"] = {
                "shard_skew_before": svc.metrics.last_repartition_skew,
                "lengths": list(part.lengths),
                "bns": list(part.bns),
            }
    out["p99_speedup"] = out["sync"]["p99_ms"] / max(out["async"]["p99_ms"],
                                                     1e-9)
    print(f"compaction p99 after trigger: sync={out['sync']['p99_ms']:.2f}ms "
          f"async={out['async']['p99_ms']:.2f}ms "
          f"(x{out['p99_speedup']:.1f}); repartition bns="
          f"{out['repartition']['bns']}")
    return out


# ------------------------------------------------------- traffic realism


def run_traffic_realism_scenario(args) -> dict:
    """Production-shaped traffic at catalog scale: Zipf(1.1) hot queries +
    diurnal arrivals over a ``--traffic-items`` compressed catalog, cache
    on vs off.

    One seeded :class:`~repro.service.loadgen.LoadGenerator` stream —
    Zipf-skewed reusable query identities, Zipf item-popularity upserts, a
    delete burst and a mid-stream ``compact()`` — replays twice through a
    single-server queue (latency from intended ARRIVAL, so backlog at the
    diurnal peak counts).  The first run has the result cache off and its
    answers are kept as the uncached oracle; the second enables
    ``cache_capacity`` and compares every answer bit-for-bit.  Three
    acceptance numbers ride to the regression gate: ``wrong == 0`` (exact
    invalidation means a cache hit is never stale), hit rate > 0 (the Zipf
    head actually repeats), and cache-on p99 strictly below cache-off (a
    hit costs no device pass, so it drains the peak-hour backlog).

    The catalog uses the compressed posting + int8 slab representation
    (``compress_postings=True, quantize="int8"``) so the default 100k-item
    run fits CI; ``--traffic-items 1000000`` reproduces the 1M-item
    numbers in ``docs/load_testing.md``.
    """
    from repro.service.loadgen import LoadGenerator, LoadProfile

    n_items, dim = args.traffic_items, args.dim
    rng = np.random.default_rng(19)
    items = rng.normal(size=(n_items, dim)).astype(np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    ids = np.arange(n_items, dtype=np.int64)
    cfg = GamConfig(k=dim, scheme="parse_tree", threshold=args.threshold)

    def spec(cache_rows: int) -> RetrieverSpec:
        return RetrieverSpec(cfg=cfg, backend="sharded",
                             n_shards=max(args.shards, 2),
                             min_overlap=args.min_overlap, kappa=args.kappa,
                             compress_postings=True, quantize="int8",
                             rerank_factor=4, cache_capacity=cache_rows)

    # size the arrival process off the measured steady-state query cost:
    # mean rate just under capacity, 4x diurnal peak well over it — the
    # backlog the cache is supposed to absorb
    probe = open_retriever(spec(0), items=items, ids=ids)
    warm = rng.normal(size=(1, dim)).astype(np.float32)
    probe.query(warm)
    t0 = time.perf_counter()
    probe.query(rng.normal(size=(1, dim)).astype(np.float32))
    t_query = max(time.perf_counter() - t0, 1e-4)
    del probe

    n_req = max(args.requests, 64)
    qps = 0.8 / t_query
    profile = LoadProfile(zipf_q=1.1, zipf_items=1.1, n_queries=48,
                          curve="diurnal", qps=qps, peak_ratio=4.0,
                          period_s=n_req / (2.0 * qps), seed=23)
    upsert_every = 12
    delete_at, compact_at = n_req // 2, (3 * n_req) // 4
    dead = ids[1:40:8].copy()           # 5 ids, same burst in both runs

    def run(cache_rows: int) -> tuple[object, list, list]:
        svc = open_retriever(spec(cache_rows), items=items, ids=ids)
        lg = LoadGenerator(profile, dim, item_ids=ids)
        _, qvec = lg.sample_queries(n_req)
        arrivals = lg.arrivals(n_req)
        svc.query(warm)                 # jit warm-up; not a pool query
        server_free, lats, answers = 0.0, [], []
        for i in range(n_req):
            # the seeded mutation stream rides on the same queue: catalog
            # churn occupies the server AND (cache on) bumps the generation
            if i and i % upsert_every == 0:
                uids, ufac = lg.sample_upserts(2)
                t0 = time.perf_counter()
                svc.upsert(uids, ufac)
                server_free = max(server_free, arrivals[i]) + \
                    (time.perf_counter() - t0)
            if i == delete_at:
                t0 = time.perf_counter()
                svc.delete(dead)
                server_free = max(server_free, arrivals[i]) + \
                    (time.perf_counter() - t0)
            if i == compact_at:
                t0 = time.perf_counter()
                svc.compact()
                server_free = max(server_free, arrivals[i]) + \
                    (time.perf_counter() - t0)
            start = max(arrivals[i], server_free)
            t0 = time.perf_counter()
            res = svc.query(qvec[i][None])
            server_free = start + (time.perf_counter() - t0)
            lats.append(server_free - arrivals[i])
            answers.append((res.ids[0].copy(), res.scores[0].copy()))
        return svc, lats, answers

    _, lats_off, oracle = run(0)
    svc_on, lats_on, got = run(4096)

    wrong = sum(1 for (a, b) in zip(oracle, got)
                if not (np.array_equal(a[0], b[0])
                        and np.array_equal(a[1], b[1])))
    cs = svc_on.cache.stats()
    pct = lambda v, q: float(np.percentile(np.asarray(v), q)) * 1e3
    out = {
        "n_items": n_items, "n_requests": n_req,
        "t_query_ms": t_query * 1e3,
        "profile": {"zipf_q": profile.zipf_q, "zipf_items": profile.zipf_items,
                    "n_queries": profile.n_queries, "curve": profile.curve,
                    "qps": profile.qps, "peak_ratio": profile.peak_ratio,
                    "period_s": profile.period_s, "seed": profile.seed},
        "mutations": {"upserts": (n_req - 1) // upsert_every,
                      "deleted_ids": int(dead.size), "compactions": 1},
        "cache_off": {"p50_ms": pct(lats_off, 50), "p99_ms": pct(lats_off, 99)},
        "cache_on": {"p50_ms": pct(lats_on, 50), "p99_ms": pct(lats_on, 99),
                     "hit_rate": cs["hit_rate"], "hits": cs["hits"],
                     "misses": cs["misses"],
                     "invalidations": cs["invalidations"],
                     "evictions": cs["evictions"], "size": cs["size"]},
        "wrong": wrong,
    }
    out["p99_speedup"] = (out["cache_off"]["p99_ms"]
                          / max(out["cache_on"]["p99_ms"], 1e-9))
    print(f"traffic realism @{n_items} items: p99 "
          f"{out['cache_off']['p99_ms']:.1f}ms (cache off) -> "
          f"{out['cache_on']['p99_ms']:.1f}ms (cache on, "
          f"hit rate {cs['hit_rate']:.0%}) x{out['p99_speedup']:.1f}; "
          f"wrong={wrong}/{n_req} invalidations={cs['invalidations']}")
    return out


# ----------------------------------------------------------- QoS overload


def run_qos_overload_scenario(args) -> dict:
    """Burst overload under live fault injection, QoS on vs off.

    One fixed arrival process — ``rounds`` bursts of 16 requests (10
    priority-0, 6 priority-1) against a drain capacity of 8 requests per
    round — feeds the service's own microbatcher over a 2-host replicated
    placement whose second host is stalled by a seeded injector on ~25% of
    rounds.  The QoS run adds per-class queue caps and deadlines; the
    no-QoS run serves the unbounded backlog.  Every admitted request's
    outcome is classified (exact / flagged-degraded / typed shed / lost)
    and every non-degraded answer is checked bit-identical against a
    fault-free single-host oracle — the "never silently wrong" invariant
    the regression gate enforces, alongside p0-p99(QoS) < p0-p99(no QoS).
    """
    from repro.service.faults import FaultInjector
    from repro.service.qos import QosPolicy, RequestShed, ResultEvicted

    rng = np.random.default_rng(13)
    items = rng.normal(size=(args.items, args.dim)).astype(np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    cfg = GamConfig(k=args.dim, scheme="parse_tree", threshold=args.threshold)
    n_shards = max(args.shards, 2)
    burst, rounds, bs = 16, 12, 4
    users = rng.normal(size=(rounds * burst, args.dim)).astype(np.float32)
    oracle = open_retriever(
        RetrieverSpec(cfg=cfg, backend="sharded", n_shards=n_shards,
                      min_overlap=args.min_overlap, kappa=args.kappa),
        items=items)
    oracle.query(users[:1])

    def run(qos_on: bool) -> dict:
        fi = FaultInjector("stall=0.25,slow=0.25:0.05,hosts=1", seed=0)
        svc = open_retriever(
            RetrieverSpec(cfg=cfg, backend="sharded-multihost",
                          n_shards=n_shards, n_hosts=2, replication=2,
                          min_overlap=args.min_overlap, kappa=args.kappa,
                          batch_size=bs, max_delay_s=60.0),
            items=items, faults=fi)
        # warm the jit cache, then size the deadlines off steady state
        warm = rng.normal(size=(bs, args.dim)).astype(np.float32)
        svc.query(warm)
        t0 = time.perf_counter()
        svc.query(warm)
        t_batch = max(time.perf_counter() - t0, 1e-4)
        if qos_on:
            policy = QosPolicy(queue_caps=(8, 4),
                               deadlines_s=(60.0, 5 * t_batch),
                               hedge_factor=3.0)
            svc.qos = policy
            svc.batcher.policy = policy
        svc.metrics.reset()

        mb = svc.batcher
        outcomes = {"served_exact": 0, "served_degraded": 0,
                    "shed_admission": 0, "shed_deadline": 0,
                    "shed_no_live_replica": 0, "evicted": 0,
                    "lost": 0, "wrong": 0}
        admitted: list[tuple[int, int, int]] = []   # (req_id, row, priority)
        row = 0
        for _ in range(rounds):
            # hold the size trigger so the whole burst lands as one backlog,
            # then drain two batches — 8 served vs 16 arriving = overload
            mb.batch_size = len(users) + 1
            for j in range(burst):
                prio = 0 if j < 10 else 1
                try:
                    admitted.append((mb.submit(users[row], priority=prio),
                                     row, prio))
                except RequestShed:
                    outcomes["shed_admission"] += 1
                row += 1
            mb.batch_size = bs
            mb.flush()
            mb.flush()
        while mb.pending:
            mb.flush()

        lats: dict[int, list[float]] = {0: [], 1: []}
        for rid, idx, prio in admitted:
            got = mb.result(rid)
            if got is None:
                outcomes["lost"] += 1
            elif isinstance(got, RequestShed):
                key = ("shed_deadline" if got.reason == "deadline"
                       else "shed_no_live_replica")
                outcomes[key] += 1
            elif isinstance(got, ResultEvicted):
                outcomes["evicted"] += 1
            else:
                lats[prio].append(got.latency_s)
                if got.degraded:
                    outcomes["served_degraded"] += 1
                    continue
                outcomes["served_exact"] += 1
                want = oracle.query(users[idx][None])
                if not (np.array_equal(got.ids, want.ids[0])
                        and np.array_equal(got.scores, want.scores[0])):
                    outcomes["wrong"] += 1
        snap = svc.metrics.snapshot()
        pct = lambda v, q: (float(np.percentile(v, q)) * 1e3 if v else None)
        return {
            "qos": qos_on,
            "t_batch_ms": t_batch * 1e3,
            "outcomes": outcomes,
            "p0_served": len(lats[0]),
            "p1_served": len(lats[1]),
            "p0_p50_ms": pct(lats[0], 50),
            "p0_p99_ms": pct(lats[0], 99),
            "p1_p99_ms": pct(lats[1], 99),
            "counters": {k: snap[k] for k in (
                "shed_total", "shed_queue_full", "shed_deadline",
                "shed_no_live_replica", "evicted_total", "degraded_total",
                "n_failovers", "hedge_issued", "hedge_wins",
                "breaker_opens", "breaker_probes", "breaker_closes")},
            "faults": fi.stats(),
        }

    out = {"burst": burst, "rounds": rounds, "batch_size": bs,
           "qos_on": run(True), "qos_off": run(False)}
    out["p0_p99_improvement"] = (out["qos_off"]["p0_p99_ms"]
                                 / max(out["qos_on"]["p0_p99_ms"], 1e-9))
    on, off = out["qos_on"], out["qos_off"]
    print(f"qos overload: p0 p99 {off['p0_p99_ms']:.2f}ms (no QoS) -> "
          f"{on['p0_p99_ms']:.2f}ms (QoS) x{out['p0_p99_improvement']:.1f}; "
          f"sheds={on['counters']['shed_total']} "
          f"failovers={on['counters']['n_failovers']} "
          f"wrong={on['outcomes']['wrong'] + off['outcomes']['wrong']} "
          f"lost={on['outcomes']['lost'] + off['outcomes']['lost']}")
    return out


# ----------------------------------------------------------- online drift


def run_drift_scenario(args) -> dict:
    """Recall-vs-staleness under concept drift: trainer on vs frozen.

    One seeded drift workload (hot item subset random-walking on the
    sphere) runs against two identical ``sharded`` retrievers.  The frozen
    one keeps its round-0 factors; the online one is fed by
    ``StreamingMF.partial_fit`` each round with re-trained factors pushed
    through the angular-drift-gated ``PushPolicy`` (staleness clock =
    round counter, so the curves are machine-independent).  Per round the
    bench records recall@kappa against the *current* true factors and the
    mean staleness (rounds since push) of the hot set.

    Three invariants ride to the regression gate: trainer-on recall beats
    the frozen index, every checkpointed answer is bit-identical to a
    from-scratch rebuild at the same pushed factors (live mutation is
    never silently wrong), and the angular gate actually suppresses a
    nonzero fraction of offers (the geometry is earning its keep).

    The workload constants are fixed (not scaled by --items/--requests) so
    CI smoke runs compare against the committed baselines.
    """
    from repro.online import (DriftSimulator, OnlineMFConfig, PushPolicy,
                              StreamingMF)

    rounds, staleness_budget, min_cos = 10, 4.0, 0.995
    sim = DriftSimulator(n_users=48, n_items=256, k=args.dim, seed=17,
                         drift=0.2, hot_frac=0.5, events_per_round=2048)
    cfg = GamConfig(k=args.dim, scheme="parse_tree", threshold=args.threshold)
    spec = RetrieverSpec(cfg=cfg, backend="sharded", n_shards=args.shards,
                         min_overlap=args.min_overlap, kappa=args.kappa)
    items0 = sim.items_at_start
    ids0 = np.arange(sim.n_items, dtype=np.int64)
    frozen = open_retriever(spec, items=items0, ids=ids0)
    online = open_retriever(spec, items=items0, ids=ids0)

    trainer = StreamingMF(OnlineMFConfig(k=args.dim, lr=0.5, momentum=0.6,
                                         reg=1e-4, batch=1024, seed=3,
                                         update_users=False))
    trainer.warm_start(u=sim.users, v=items0)
    tick = [0.0]                      # round counter doubles as the clock
    policy = PushPolicy(online, min_cos=min_cos, staleness_s=staleness_budget,
                        clock=lambda: tick[0])
    policy.seed(ids0, items0)
    catalog = {int(i): items0[j].copy() for j, i in enumerate(ids0)}
    last_push = dict.fromkeys(map(int, ids0), 0.0)

    eval_users = sim.users
    curve = []
    wrong = n_checkpoints = 0
    prev_pushed = prev_sup = 0
    for r in range(1, rounds + 1):
        ev = sim.step()
        tick[0] = float(sim.round)
        st = trainer.partial_fit(ev)
        touched = st["touched_items"]
        policy.offer(touched, trainer.item_factors(touched))
        p_ids, p_fac = policy.flush()
        for i, f in zip(p_ids, p_fac):
            catalog[int(i)] = f.copy()
            last_push[int(i)] = tick[0]
        truth = sim.true_topk(args.kappa, eval_users)
        got_on = online.query(eval_users, args.kappa)
        got_fr = frozen.query(eval_users, args.kappa)
        stale_hot = float(np.mean([tick[0] - last_push[int(i)]
                                   for i in sim.hot]))
        curve.append({
            "round": r,
            "recall_online": sim.recall(got_on.ids, truth),
            "recall_frozen": sim.recall(got_fr.ids, truth),
            "staleness_online": stale_hot,
            "staleness_frozen": tick[0],
            "pushed": policy.n_pushed - prev_pushed,
            "suppressed": policy.n_suppressed - prev_sup,
        })
        prev_pushed, prev_sup = policy.n_pushed, policy.n_suppressed
        if r % 3 == 0 or r == rounds:
            # never silently wrong: the live drifted index must answer
            # bit-identically to a from-scratch rebuild at the same
            # pushed factors
            ids = np.asarray(sorted(catalog), np.int64)
            fac = np.stack([catalog[int(i)] for i in ids])
            rebuilt = open_retriever(spec, items=fac, ids=ids)
            want = rebuilt.query(eval_users, args.kappa)
            if not (np.array_equal(got_on.ids, want.ids)
                    and np.array_equal(got_on.scores, want.scores)):
                wrong += 1
            n_checkpoints += 1
    snap = online.metrics.snapshot()
    ps = policy.stats()
    out = {
        "rounds": rounds, "kappa": args.kappa,
        "staleness_budget_rounds": staleness_budget, "min_cos": min_cos,
        "n_items": sim.n_items, "n_hot": int(sim.hot.size),
        "events_per_round": sim.events_per_round,
        "curve": curve,
        "recall_online_mean": float(np.mean([c["recall_online"]
                                             for c in curve])),
        "recall_frozen_mean": float(np.mean([c["recall_frozen"]
                                             for c in curve])),
        "recall_online_final": curve[-1]["recall_online"],
        "recall_frozen_final": curve[-1]["recall_frozen"],
        "staleness_online_final": curve[-1]["staleness_online"],
        "pushed_total": policy.n_pushed,
        "suppressed_total": policy.n_suppressed,
        "suppression_rate": ps["suppression_rate"],
        "push_staleness_p50_rounds": snap["push_staleness_p50_s"],
        "wrong": wrong, "n_parity_checkpoints": n_checkpoints,
        "trainer": trainer.stats(),
    }
    print(f"online drift: recall {out['recall_frozen_final']:.2f} (frozen) "
          f"-> {out['recall_online_final']:.2f} (trainer on) after {rounds} "
          f"rounds; pushed={out['pushed_total']} "
          f"suppressed={out['suppressed_total']} "
          f"(rate {out['suppression_rate']:.0%}); "
          f"parity wrong={wrong}/{n_checkpoints}")
    return out


# ------------------------------------------------------------- multi-host


def _multihost_specs(args) -> tuple[RetrieverSpec, RetrieverSpec]:
    cfg = GamConfig(k=args.dim, scheme="parse_tree", threshold=args.threshold)
    common = dict(cfg=cfg, n_shards=max(args.shards, 2 * args.multihost_procs),
                  min_overlap=args.min_overlap, kappa=args.kappa)
    multi = RetrieverSpec(backend="sharded-multihost",
                          n_hosts=args.multihost_procs,
                          replication=min(2, args.multihost_procs),
                          **common)
    single = RetrieverSpec(backend="sharded", **common)
    return multi, single


def _multihost_measure(args, *, distributed: bool) -> dict:
    """The shared measurement body: serve one fixed query stream from the
    multi-host backend, fail one host halfway, and check every answer
    bit-identical against an in-process single-host oracle."""
    rng = np.random.default_rng(11)
    items = rng.normal(size=(args.items, args.dim)).astype(np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    multi_spec, single_spec = _multihost_specs(args)
    svc = open_retriever(multi_spec, items=items)
    oracle = open_retriever(single_spec, items=items)

    bs = 8
    n_batches = max(args.requests // bs, 12)
    fail_at = n_batches // 2
    warm = rng.normal(size=(bs, args.dim)).astype(np.float32)
    svc.query(warm)
    oracle.query(warm)
    svc.metrics.reset()

    # a single host has no surviving replica to fail over to — the
    # failover leg then just measures the second half of the stream
    fail_host = args.multihost_procs - 1 if args.multihost_procs > 1 else None
    lats, parity = [], True
    for b in range(n_batches):
        users = rng.normal(size=(bs, args.dim)).astype(np.float32)
        if b == fail_at and fail_host is not None:
            svc.mark_down(fail_host)
        t0 = time.perf_counter()
        got = svc.query(users)
        lats.append(time.perf_counter() - t0)
        want = oracle.query(users)
        parity = parity and bool(
            np.array_equal(got.ids, want.ids)
            and np.array_equal(got.scores, want.scores))
    # explain must be pure observation: identical answers with it on —
    # including across the collective merge path and post-failover routing
    probe = rng.normal(size=(bs, args.dim)).astype(np.float32)
    plain = svc.query(probe)
    explained = svc.query(probe, explain=True)
    explain_parity = bool(
        np.array_equal(plain.ids, explained.ids)
        and np.array_equal(plain.scores, explained.scores)
        and explained.explain is not None)
    before = np.asarray(lats[:fail_at]) * 1e3
    after = np.asarray(lats[fail_at:]) * 1e3
    hosts = svc.maintenance_stats()["hosts"]
    return {
        "mode": "processes" if distributed else "simulated",
        "n_hosts": args.multihost_procs,
        "replication": min(2, args.multihost_procs),
        "n_slices": hosts["n_slices"],
        "n_requests": n_batches * bs,
        "parity": parity,
        "explain_parity": explain_parity,
        "p50_ms": float(np.percentile(before, 50)),
        "p99_ms": float(np.percentile(before, 99)),
        "failover": {
            "p50_ms": float(np.percentile(after, 50)),
            "p99_ms": float(np.percentile(after, 99)),
            "n_failovers": hosts["n_failovers"],
            "routing": hosts["routing"],
        },
    }


def _multihost_worker(args) -> None:
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(args.coordinator, args.multihost_procs,
                               args.worker_id)
    res = _multihost_measure(args, distributed=True)
    if jax.process_index() == 0:
        print("MULTIHOST_RESULT " + json.dumps(res), flush=True)


def _spawn_multihost(args) -> dict:
    from repro.launch.procs import free_coordinator, run_workers

    base = [sys.executable, os.path.abspath(__file__),
            "--multihost-worker", "--coordinator", free_coordinator(),
            "--multihost-procs", str(args.multihost_procs),
            "--items", str(args.items), "--dim", str(args.dim),
            "--shards", str(args.shards), "--requests", str(args.requests),
            "--kappa", str(args.kappa), "--threshold", str(args.threshold),
            "--min-overlap", str(args.min_overlap)]
    codes, outs = run_workers(
        [base + ["--worker-id", str(i)]
         for i in range(args.multihost_procs)], capture=True)
    if any(codes):
        raise RuntimeError(f"multihost worker exit codes {codes}")
    for out in outs:
        for line in out.splitlines():
            if line.startswith("MULTIHOST_RESULT "):
                return json.loads(line[len("MULTIHOST_RESULT "):])
    raise RuntimeError("no multihost worker reported a result")


def run_multihost_scenario(args) -> dict:
    # a failed spawn fails the run: it is never replaced by a measurement
    # of something else
    if args.multihost_procs > 1:
        out = _spawn_multihost(args)
    else:
        out = _multihost_measure(args, distributed=False)
    print(f"multihost ({out['mode']}, {out['n_hosts']} hosts): "
          f"p99={out['p99_ms']:.2f}ms, after failover "
          f"p99={out['failover']['p99_ms']:.2f}ms, "
          f"parity={'bit-identical' if out['parity'] else 'DIVERGED'}, "
          f"explain={'pure' if out.get('explain_parity') else 'DIVERGED'}")
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, default=2048)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--kappa", type=int, default=10)
    ap.add_argument("--batch-sizes", type=int, nargs="+",
                    default=[1, 4, 8, 16])
    ap.add_argument("--threshold", type=float, default=0.2)
    ap.add_argument("--min-overlap", type=int, default=2)
    ap.add_argument("--traffic-items", type=int, default=100_000,
                    help="catalog size for the traffic_realism scenario "
                         "(compressed backend; 1000000 reproduces the "
                         "docs/load_testing.md numbers)")
    ap.add_argument("--multihost-procs", type=int, default=2,
                    help="host processes for the multi-host scenario "
                         "(1 = in-process placement only)")
    ap.add_argument("--multihost-worker", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--worker-id", type=int, default=0,
                    help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default="", help=argparse.SUPPRESS)
    ap.add_argument("--out", default="BENCH_service.json")
    args = ap.parse_args(argv)

    if args.multihost_worker:
        _multihost_worker(args)
        return

    rng = np.random.default_rng(0)
    items = rng.normal(size=(args.items, args.dim)).astype(np.float32)
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    users = rng.normal(size=(args.requests, args.dim)).astype(np.float32)
    cfg = GamConfig(k=args.dim, scheme="parse_tree", threshold=args.threshold)

    print("mode,batch_size,qps,p50_ms,p99_ms,occupancy")
    curves = {"exact": [], "gam": []}
    discard_mean = None
    for bs in args.batch_sizes:
        svc = open_retriever(
            RetrieverSpec(cfg=cfg, backend="sharded", n_shards=args.shards,
                          min_overlap=args.min_overlap, kappa=args.kappa,
                          batch_size=bs, max_delay_s=5e-3),
            items=items)
        for exact in (True, False):
            pt = run_point(svc, users, exact=exact)
            curves[pt["mode"]].append(pt)
            print(f"{pt['mode']},{bs},{pt['qps']:.1f},"
                  f"{pt['p50_ms']:.2f},{pt['p99_ms']:.2f},"
                  f"{pt['occupancy']:.2f}")
        res = svc.query(users[:1], args.kappa)  # discard stat at this config
        discard_mean = float(res.discarded_frac.mean())

    stages = run_stage_scenario(args)
    overhead = run_overhead_scenario(args)
    compaction = run_compaction_scenario(args)
    qos_overload = run_qos_overload_scenario(args)
    traffic = run_traffic_realism_scenario(args)
    online_drift = run_drift_scenario(args)
    multihost = run_multihost_scenario(args)

    out = {
        "config": {
            "items": args.items, "dim": args.dim, "shards": args.shards,
            "requests": args.requests, "kappa": args.kappa,
            "threshold": args.threshold, "min_overlap": args.min_overlap,
        },
        "discard_mean": discard_mean,
        "curves": curves,
        "stages": stages,
        "overhead": overhead,
        "compaction": compaction,
        "qos_overload": qos_overload,
        "traffic_realism": traffic,
        "online_drift": online_drift,
        "multihost": multihost,
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
