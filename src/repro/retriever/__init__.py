"""Unified retriever API: one spec, one lifecycle, pluggable backends.

The paper's deployment object — phi-map, inverted index, candidate-only
top-kappa — exists at several scales (brute reference, CPU posting lists,
fused device kernel, sharded streaming service).  This package is the one
door to all of them::

    from repro.retriever import RetrieverSpec, open_retriever

    spec = RetrieverSpec(cfg=GamConfig(k=16, threshold=0.2),
                         backend="sharded", n_shards=4, min_overlap=2)
    r = open_retriever(spec, items=factors)       # build
    r.upsert(new_ids, new_factors)                # stream mutations
    res = r.query(users, kappa=10)                # RetrievalResult
    r.snapshot("catalog.npz")                     # persist (checkpoint/)
    r2 = open_retriever(spec, snapshot="catalog.npz")   # bit-identical

Contract
========

``build / upsert / delete / compact / query / stats / snapshot / restore``
(:class:`Retriever`); results are :class:`RetrievalResult` in catalog-id
space with the total order (score desc, id asc).  Backends that cannot
honour an operation raise the typed :class:`UnsupportedOp` — never a
silently diverging answer.

Backends
========

========== ========================================================
brute       exact scoring of every item (oracle / tiny catalogs)
gam         CPU CSR inverted index (paper-faithful structure)
gam-device  fused ``gam_retrieve`` kernel: bit-packed patterns,
            block skipping, on-chip top-kappa
sharded     item-axis shards + delta segment + microbatcher +
            metrics (the streaming service tier)
sharded-multihost
            the service tier spanning host processes: placement
            slices with replication/failover, cross-host collective
            top-kappa merge — bit-identical to ``sharded``
srp-lsh / superbit-lsh / cro / pca-tree
            §5.1 baselines, build+query only
========== ========================================================

The registry is string-keyed and lazily resolved (same importlib pattern as
``configs/registry.py``); third-party structures join via
:func:`register_backend` without touching any caller.

This module is the canonical home of :class:`RetrievalResult` and
:class:`UnsupportedOp`; ``repro.core`` re-exports the former.
"""
from repro.retriever.api import (
    BACKEND_IDS,
    Retriever,
    RetrieverSpec,
    available_backends,
    open_retriever,
    register_backend,
)
from repro.retriever.types import RetrievalResult, UnsupportedOp

__all__ = [
    "BACKEND_IDS",
    "BaselineRetriever",
    "BruteRetriever",
    "GamIndexRetriever",
    "MultiHostShardedRetriever",
    "RetrievalResult",
    "Retriever",
    "RetrieverSpec",
    "ShardedRetriever",
    "UnsupportedOp",
    "available_backends",
    "open_retriever",
    "register_backend",
]

_LAZY_CLASSES = {
    "BruteRetriever": "repro.retriever.brute",
    "GamIndexRetriever": "repro.retriever.gam",
    "ShardedRetriever": "repro.retriever.sharded",
    "MultiHostShardedRetriever": "repro.retriever.multihost",
    "BaselineRetriever": "repro.retriever.baselines",
}


def __getattr__(name: str):
    # backend classes resolve lazily (PEP 562) so importing the API surface
    # never drags in kernels or the service tier — mirrors the lazy registry
    if name in _LAZY_CLASSES:
        import importlib
        return getattr(importlib.import_module(_LAZY_CLASSES[name]), name)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
