"""Shared retrieval oracle and metric.

The retrievers live behind the unified API in ``repro.retriever`` (one
spec, one lifecycle, pluggable backends, snapshot/restore).  Canonical
here: :func:`masked_topk` (the dense bit-exact oracle the fused kernel is
tested against) and :func:`recovery_accuracy` (the paper's §6 metric).
:class:`RetrievalResult` is re-exported from its home, ``repro.retriever``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.ops import gam_score
from repro.retriever.types import RetrievalResult

__all__ = ["RetrievalResult", "masked_topk", "recovery_accuracy"]


def masked_topk(users: jax.Array, items: jax.Array, masks: jax.Array,
                kappa: int) -> tuple[jax.Array, jax.Array]:
    """Dense masked top-kappa reference path.

    ``users``: (Q, k) f32, ``items``: (N, k) f32, ``masks``: (Q, N) bool.
    Exact inner products via the gam_score kernel where the candidate mask is
    set, NEG elsewhere; ``lax.top_k`` breaks score ties by lowest item row.
    Serving no longer goes through here — the fused ``gam_retrieve`` kernel
    realises the identical (score desc, row asc) order without ever
    materialising the (Q, N) mask/score tensors — but this stays as the
    bit-exact oracle the fused path is tested against.
    """
    scores = gam_score(users, items, masks)
    vals, ids = jax.lax.top_k(scores, kappa)
    return vals, ids.astype(jnp.int32)


def recovery_accuracy(retrieved_ids: np.ndarray, true_ids: np.ndarray) -> np.ndarray:
    """Fraction of the true top-kappa recovered, per query (paper §6 metric).

    Vectorised numpy membership over the (Q, kappa) id arrays; ``-1`` pads on
    either side never count (ids within a row are unique, so the pairwise
    equality reduction is exactly the per-row set intersection size)."""
    ret = np.asarray(retrieved_ids)
    true = np.asarray(true_ids)
    hit = (true[:, :, None] == ret[:, None, :]) & (true >= 0)[:, :, None]
    hit &= (ret >= 0)[:, None, :]
    inter = hit.any(axis=-1).sum(axis=-1)
    denom = np.maximum((true >= 0).sum(axis=-1), 1)
    return inter / denom
