"""Snapshot plumbing shared by the retriever backends.

A snapshot is one ``checkpoint.save_arrays`` file: the backend's queryable
state as named host arrays (posting tables, bit-packed patterns, block-union
metadata, factor matrices, the delta catalog, ...) plus a JSON header that
pins the snapshot format, the backend name and the spec's schema-defining
fields.  ``read_snapshot`` refuses files written by a different backend or
an incompatible mapping schema — restoring into the wrong spec must fail
loudly, never answer queries from the wrong geometry.
"""
from __future__ import annotations

import numpy as np

from repro.checkpoint import load_arrays, save_arrays
from repro.core.mapping import GamConfig
from repro.retriever.api import RetrieverSpec

__all__ = ["read_snapshot", "write_snapshot"]

# v4: the compressed-catalog formats — varint-encoded posting tables
# (``compress_postings``, storage-only: the reader decodes the same CSR
# stream bit-identically, keyed on which arrays are present) and int8
# factor slabs with per-block scales (``quantize``/``rerank_factor``,
# result-bearing: within-backend bitwise score identity pins the scoring
# path).  v3 (adds the optional multi-host placement) and v2 files read
# unchanged — their headers predate the new spec fields, so readers fill
# the uncompressed defaults.  v1 files are still rejected loudly here
# rather than KeyError-ing mid-restore.
SNAPSHOT_FORMAT = "repro.retriever/v4"
_READ_COMPAT = (SNAPSHOT_FORMAT, "repro.retriever/v3", "repro.retriever/v2")

# spec fields that change query RESULTS (not just performance): a snapshot
# taken under one of these must not silently serve under another.
# delta_bucket is result-bearing too — bucket spill turns delta rows into
# unconditional candidates, so a different width changes candidate sets.
# quantize/rerank_factor change the scoring path and the exact-rerank pool,
# so bitwise within-backend score identity requires them to match;
# compress_postings is deliberately absent — it is storage-only.
_RESULT_FIELDS = ("backend", "min_overlap", "bucket", "whiten",
                  "delta_bucket", "quantize", "rerank_factor")

# defaults filled when reading pre-v4 headers that predate a result field
_FIELD_DEFAULTS = {"quantize": "none", "rerank_factor": 4}

# result-equivalent backend upgrades a snapshot may cross: the multi-host
# backend answers bit-identically to single-host ``sharded`` over the same
# catalog, so a ``sharded`` file may scale OUT into a multi-host deployment
# (the reverse stays rejected — scaling in silently would drop placement).
_BACKEND_UPGRADES = {"sharded-multihost": ("sharded",)}


def _cfg_meta(cfg: GamConfig) -> dict:
    return {"k": cfg.k, "scheme": cfg.scheme, "d": cfg.d,
            "threshold": cfg.threshold}


def write_snapshot(path: str, spec: RetrieverSpec,
                   arrays: dict[str, np.ndarray],
                   extra: dict | None = None) -> None:
    header = {
        "format": SNAPSHOT_FORMAT,
        "cfg": _cfg_meta(spec.cfg),
        "spec": {f: getattr(spec, f) for f in _RESULT_FIELDS},
        "state": extra or {},
    }
    save_arrays(path, arrays, header)


def read_snapshot(path: str, spec: RetrieverSpec
                  ) -> tuple[dict[str, np.ndarray], dict]:
    """Load + validate a snapshot against the opening spec -> (arrays,
    backend state dict)."""
    arrays, header = load_arrays(path)
    if header.get("format") not in _READ_COMPAT:
        raise ValueError(f"{path}: not a readable retriever snapshot "
                         f"(format={header.get('format')!r}, "
                         f"readers accept {list(_READ_COMPAT)})")
    if header["cfg"] != _cfg_meta(spec.cfg):
        raise ValueError(
            f"{path}: snapshot mapping schema {header['cfg']} does not match "
            f"spec cfg {_cfg_meta(spec.cfg)}")
    saved = dict(header["spec"])
    for field, default in _FIELD_DEFAULTS.items():
        saved.setdefault(field, default)      # pre-v4 headers
    mine = {f: getattr(spec, f) for f in _RESULT_FIELDS}
    if saved["backend"] in _BACKEND_UPGRADES.get(spec.backend, ()):
        saved["backend"] = spec.backend       # sanctioned scale-out restore
    if saved != mine:
        diff = {f: (saved[f], mine[f]) for f in _RESULT_FIELDS
                if saved[f] != mine[f]}
        raise ValueError(f"{path}: snapshot/spec mismatch (saved, spec): "
                         f"{diff}")
    return arrays, header.get("state", {})
