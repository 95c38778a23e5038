"""Sharded streaming retrieval machinery (the ``sharded`` backend's parts).

The paper's deployment object is an inverted index over phi-mapped factors;
this package holds the building blocks of its serving tier — partitioned
storage, live catalog mutation, and a request front-end.  The facade that
ties them together is the unified-API ``sharded`` backend
(``repro.retriever.sharded.ShardedRetriever``); open it with::

    from repro.retriever import RetrieverSpec, open_retriever
    r = open_retriever(RetrieverSpec(cfg=cfg, backend="sharded",
                                     n_shards=4, min_overlap=2),
                       items=factors, ids=item_ids)

``GamService`` remains as a deprecation shim over that backend for one
release.

Architecture
============

::

    requests ──> Microbatcher ──> ShardedRetriever.query ─┬─> ShardedGamIndex
       (size/deadline coalescing,                         │   (main segment,
        fixed-shape padded batches,                       │    item-axis shards,
        per-request latency)                              │    fused-kernel query,
                                                          │    kill-refreshed
    upsert/delete ──> DeltaSegment  <─────────────────────┤    block metadata)
        (always-queried dense segment;                    └─> merge by
         compact() folds it into the main shards)             (score desc, id asc)
    snapshot()/restore() ──> repro.checkpoint (posting tables, bit-packed
        patterns, block-union metadata, delta catalog — bit-identical restore)
    ServiceMetrics: QPS, p50/p99 latency, occupancy,
                    discard fraction, shard balance

Components
==========

``ShardedGamIndex`` (``sharded_index.py``)
    The compacted main segment.  The id-sorted catalog is cut into
    contiguous shards; each shard owns a dense-bucket posting segment
    (built by the vectorised ``core.inverted_index.build_segment_csr``,
    held in host memory) over local rows.  Queries stream the flat factor
    matrix through the fused ``kernels.gam_retrieve`` kernel, whose item
    axis a ``launch.mesh.make_index_mesh`` mesh splits block-aligned, each
    device's share built on it — catalog size scales with devices.
    ``kill()`` tombstones rows AND refreshes the kernel's block-union/spill
    metadata, so long tombstone streams cannot erode the zero-candidate
    block-skip rate.

``DeltaSegment`` (``delta.py``)
    Streaming ``upsert``/``delete`` land in a small dense segment that every
    query also scores (same candidate semantics, same kernel), so queries
    between compactions return exactly what a fresh rebuild would.

``Microbatcher`` (``microbatch.py``)
    Coalesces single-user queries into fixed-size padded batches (size- or
    deadline-triggered) so one jit-compiled step serves all traffic.

``ServiceMetrics`` (``metrics.py``)
    QPS, latency percentiles, batch occupancy, discard fraction and
    shard-balance counters; surfaced by ``launch/serve.py --service`` and
    ``benchmarks/service_bench.py`` (throughput-vs-latency curve).

``CompactionPlanner`` (``compaction.py``)
    Background compaction as a resumable state machine: the replacement
    main segment is built in bounded slices interleaved with queries
    (map -> per-shard segments -> per-bn-group metadata -> finalize), with
    one atomic generation-tagged swap at the end and a mutation journal
    replayed over it.  Queries answer exactly from (old segment ∪ delta)
    at every intermediate step.

``Partition`` / ``Repartitioner`` (``repartition.py``)
    Skew-aware layout of the id-sorted catalog: variable-length contiguous
    shards and per-shard fused-kernel block widths ``bn``, planned from
    per-item load weights; ``ServiceMetrics`` skew (max/mean candidate
    load) decides when rebalancing is worth a compaction.

``HostPlacement`` / collective merge (``collective.py``)
    The multi-host layer: contiguous shard runs become placement slices
    replicated onto host processes; the deterministic router serves each
    slice from its first live replica, and per-host O(Q*kappa) exported
    accumulators merge under the kernel's (score desc, row asc) total
    order — the ``sharded-multihost`` backend
    (``repro.retriever.multihost``) is bit-identical to single-host
    ``sharded``, including after ``mark_down`` failovers.

``MapCache`` (``repartition.py``)
    Incremental per-item phi-map cache: ``repartition()`` re-maps only
    items whose factors changed since the last plan.

``ResultCache`` (``result_cache.py``)
    Exact hot-query result cache: per-row top-kappa memos keyed on the
    query's raw bytes and generation-tagged so every catalog mutation
    invalidates (stale hit impossible by construction); a hit is the QoS
    ladder's zero-cost rung.  Enabled by
    ``RetrieverSpec(cache_capacity=...)``.

``LoadGenerator`` / ``LoadProfile`` (``loadgen.py``)
    Production-traffic harness: Zipf-skewed reusable query identities,
    Zipf item-popularity upsert streams and diurnal/bursty inhomogeneous
    Poisson arrivals — all seeded and replayable
    (``launch/serve.py --load-profile``, the ``traffic_realism``
    benchmark scenario).  See ``docs/load_testing.md``.
"""
from repro.service.collective import HostPlacement, NoLiveReplica
from repro.service.compaction import CompactionPlanner
from repro.service.delta import DeltaSegment
from repro.service.faults import FaultInjected, FaultInjector, FaultSpec
from repro.service.loadgen import LoadGenerator, LoadProfile, zipf_weights
from repro.service.metrics import ServiceMetrics
from repro.service.result_cache import CachedResult, ResultCache
from repro.service.microbatch import Microbatcher, QueryResult
from repro.service.qos import (DEGRADE_RUNGS, HealthTracker, QosPolicy,
                               RequestShed, ResultEvicted)
from repro.service.repartition import MapCache, Partition, Repartitioner
from repro.service.service import GamService, ServiceConfig
from repro.service.sharded_index import ShardedGamIndex, ShardTopK

__all__ = [
    "CachedResult",
    "CompactionPlanner",
    "DEGRADE_RUNGS",
    "DeltaSegment",
    "FaultInjected",
    "FaultInjector",
    "FaultSpec",
    "GamService",
    "HealthTracker",
    "HostPlacement",
    "LoadGenerator",
    "LoadProfile",
    "MapCache",
    "Microbatcher",
    "NoLiveReplica",
    "Partition",
    "QosPolicy",
    "QueryResult",
    "RequestShed",
    "Repartitioner",
    "ResultCache",
    "ResultEvicted",
    "ServiceConfig",
    "ServiceMetrics",
    "ShardTopK",
    "ShardedGamIndex",
    "zipf_weights",
]
