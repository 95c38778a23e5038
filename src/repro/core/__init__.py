"""Core GAM library: the paper's contribution as composable JAX modules.

Canonical exports: ``pattern_overlap`` (and the rest of the mapping/
tessellation toolkit) live HERE; the retrieval lifecycle moved to
``repro.retriever`` (one spec, pluggable backends, snapshot/restore);
``RetrievalResult`` is re-exported from there.
"""
from repro.core.mapping import GamConfig, densify, pattern_overlap, sparse_map
from repro.core.retrieval import (
    RetrievalResult,
    masked_topk,
    recovery_accuracy,
)
from repro.core.tessellation import (
    dary_pattern,
    exhaustive_tess_vector,
    ternary_pattern,
    tess_vector,
    tess_vector_d,
)

__all__ = [
    "GamConfig",
    "densify",
    "pattern_overlap",
    "sparse_map",
    "RetrievalResult",
    "masked_topk",
    "recovery_accuracy",
    "dary_pattern",
    "exhaustive_tess_vector",
    "ternary_pattern",
    "tess_vector",
    "tess_vector_d",
]
