"""``gam`` and ``gam-device`` backends: the paper's deployment object.

Map item factors with phi once, index the sparsity patterns, answer
top-kappa MIPS by exact-scoring only candidates (pattern overlap >=
``spec.min_overlap``, plus bucket-spill rows):

* ``gam`` — CPU inverted index (CSR posting lists), the paper-faithful
  structure the retrieval-speedup benchmarks time;
* ``gam-device`` — the fused ``kernels.gam_retrieve`` streaming kernel over
  a dense-bucket :class:`DeviceIndex`: candidate overlap from bit-packed
  patterns, zero-candidate blocks skipped, on-chip running top-kappa.

Both are static-catalog structures at heart: ``upsert``/``delete`` rebuild
in O(N) and are supported for API uniformity; live streams belong on the
``sharded`` backend with its delta segment.  ``snapshot``/``restore``
persist the posting table, the bit-packed patterns and the block-union
metadata through ``repro.checkpoint`` so a restored index answers queries
bit-identically without re-deriving anything.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress.postings import CompressedPostings, decode_postings, \
    encode_postings
from repro.core.inverted_index import (CompressedInvertedIndex, DeviceIndex,
                                       InvertedIndex, csr_to_table,
                                       table_to_csr)
from repro.core.mapping import sparse_map
from repro.kernels.gam_retrieve import (RetrievalMeta, build_retrieval_meta,
                                        expand_tile_skips, quantize_meta)
from repro.kernels.gam_score import NEG
from repro.kernels.ops import gam_retrieve
from repro.retriever.api import Retriever, RetrieverSpec
from repro.retriever.snapshot import read_snapshot, write_snapshot
from repro.retriever.types import (RetrievalResult, UnsupportedOp,
                                   dedupe_last_write)

__all__ = ["GamIndexRetriever"]


class GamIndexRetriever(Retriever):
    """phi-map + inverted index + candidate-only scoring, CPU or device."""

    def __init__(self, spec: RetrieverSpec, **_):
        super().__init__(spec)
        self.device = spec.backend == "gam-device"
        self._empty()

    def _empty(self) -> None:
        k = self.spec.cfg.k
        self.ids = np.zeros(0, np.int64)
        self.items = np.zeros((0, k), np.float32)
        self.item_tau = np.zeros((0, k), np.int32)
        self.item_mask = np.zeros((0, k), bool)
        self._scale: np.ndarray | None = None
        self._cpu_index: InvertedIndex | None = None
        self.device_index: DeviceIndex | None = None
        self._items_dev: jax.Array | None = None
        self._retrieve_meta: RetrievalMeta | None = None

    # ------------------------------------------------------------ lifecycle

    def build(self, items, ids=None) -> "GamIndexRetriever":
        spec = self.spec
        items = np.asarray(items, np.float32).reshape(-1, spec.cfg.k)
        ids = (np.arange(items.shape[0], dtype=np.int64) if ids is None
               else np.asarray(ids, np.int64).ravel())
        if len(np.unique(ids)) != ids.size:
            raise ValueError("item ids must be unique")
        if ids.size == 0:
            self._empty()
            return self
        order = np.argsort(ids)
        self.ids, self.items = ids[order], items[order]
        # whiten: the paper's §5/supplement-B.1 non-uniform tessellation for
        # anisotropic factors — equalises tile occupancy without changing the
        # exact scores, which always use the raw factors
        self._scale = (1.0 / (self.items.std(axis=0) + 1e-9)
                       if spec.whiten else None)
        mapped = self.items * self._scale if spec.whiten else self.items
        tau, vals = sparse_map(jnp.asarray(mapped), spec.cfg)
        self.item_tau = np.asarray(tau)
        # the paper's inverted index stores only NON-zero coordinates of
        # phi(v); thresholded coordinates never enter the index
        self.item_mask = np.asarray(vals) != 0.0
        self._cpu_index = None          # CPU CSR index built on first use
        if self.device:
            n = len(self.items)
            self.device_index = DeviceIndex.build(
                self.item_tau, spec.cfg.p, spec.bucket, mask=self.item_mask)
            self._items_dev = jnp.asarray(self.items)
            self._retrieve_meta = build_retrieval_meta(
                self.item_tau, self.item_mask, spec.cfg.p,
                spill_rows=np.asarray(self.device_index.spill),
                bn=spec.bn or min(512, -(-max(n, 1) // 128) * 128),
                factors=self.items if spec.quantize == "int8" else None,
                quantize=spec.quantize)
        return self

    def upsert(self, ids, factors) -> None:
        """O(N + batch) rebuild — supported for contract uniformity; a live
        mutation stream belongs on the ``sharded`` backend's delta tier."""
        ids = np.asarray(ids, np.int64).ravel()
        factors = np.asarray(factors, np.float32).reshape(
            ids.size, self.spec.cfg.k)
        ids, factors = dedupe_last_write(ids, factors)
        keep = ~np.isin(self.ids, ids)
        self.build(np.concatenate([self.items[keep], factors]),
                   np.concatenate([self.ids[keep], ids]))

    def delete(self, ids) -> None:
        keep = ~np.isin(self.ids, np.asarray(ids, np.int64).ravel())
        self.build(self.items[keep], self.ids[keep])

    def compact(self, async_: bool = False) -> None:
        pass                  # rebuilt-on-mutation: never holds a delta

    # ------------------------------------------------------------ queries

    @property
    def index(self) -> InvertedIndex | CompressedInvertedIndex:
        """The paper-faithful posting lists (CPU query path) — flat CSR, or
        the pattern-factored varint encoding when ``spec.compress_postings``
        (answers are bit-identical either way)."""
        if self._cpu_index is None:
            idx = InvertedIndex(self.item_tau, self.spec.cfg.p,
                                mask=self.item_mask)
            self._cpu_index = (idx.compress() if self.spec.compress_postings
                               else idx)
        return self._cpu_index

    def map_queries(self, users: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        users = np.asarray(users, np.float32)
        if self._scale is not None:
            users = users * self._scale
        tau, vals = sparse_map(jnp.asarray(users), self.spec.cfg)
        return np.asarray(tau), np.asarray(vals) != 0.0

    def query(self, users, kappa=None, *, exact=False,
              explain=False) -> RetrievalResult:
        kappa = self.spec.kappa if kappa is None else int(kappa)
        users = np.asarray(users, np.float32)
        if self.n_items == 0:
            q = users.shape[0]
            exp = ({"backend": self.spec.backend, "n_candidates": [0] * q}
                   if explain else None)
            return RetrievalResult(np.full((q, kappa), -1, np.int64),
                                   np.full((q, kappa), -np.inf, np.float32),
                                   np.zeros(q, np.int64), np.zeros(q),
                                   explain=exp)
        if self.device:
            return self._query_device(users, kappa, exact=exact,
                                      explain=explain)
        return self._query_cpu(users, kappa, exact=exact, explain=explain)

    def _query_cpu(self, users: np.ndarray, kappa: int, *,
                   exact: bool, explain: bool = False) -> RetrievalResult:
        q_tau, q_mask = self.map_queries(users)
        n = self.items.shape[0]
        q = users.shape[0]
        ids_out = np.full((q, kappa), -1, np.int64)
        sc_out = np.full((q, kappa), -np.inf, np.float32)
        n_scored = np.zeros(q, np.int64)
        all_rows = np.arange(n, dtype=np.int64)
        for qi in range(q):
            if exact:
                cand = all_rows
            else:
                cand, _ = self.index.query(q_tau[qi], self.spec.min_overlap,
                                           q_mask[qi])
            if cand.size == 0:
                continue
            scores = self.items[cand] @ users[qi]
            kk = min(kappa, cand.size)
            # (score desc, row asc) exactly — the same total order the fused
            # kernel and the brute oracle realise, so score TIES cannot make
            # backends diverge.  cand is ascending, so position order == row
            # order; a tie across the kappa boundary falls back to the
            # stable full sort.
            top = np.argpartition(-scores, kk - 1)[:kk]
            if (scores >= scores[top].min()).sum() > kk:
                top = np.argsort(-scores, kind="stable")[:kk]
            else:
                top = np.sort(top)
                top = top[np.argsort(-scores[top], kind="stable")]
            ids_out[qi, :kk] = self.ids[cand[top]]
            sc_out[qi, :kk] = scores[top]
            n_scored[qi] = cand.size
        exp = None
        if explain:
            exp = {"backend": "gam",
                   "n_candidates": n_scored.tolist()}
        return RetrievalResult(
            ids=ids_out, scores=sc_out, n_scored=n_scored,
            discarded_frac=1.0 - n_scored / n,
            explain=exp,
        )

    def _query_device(self, users: np.ndarray, kappa: int, *,
                      exact: bool, explain: bool = False) -> RetrievalResult:
        """Streaming jit path: one fused gam_retrieve call over the query
        batch — candidate pruning, exact scoring and the top-kappa reduction
        happen on chip, so nothing of size (Q, N) ever reaches HBM."""
        n = self.items.shape[0]
        q = users.shape[0]
        q_tau, q_mask = self.map_queries(users)
        kk = min(kappa, n)
        res = gam_retrieve(jnp.asarray(users), self._items_dev,
                           jnp.asarray(q_tau), jnp.asarray(q_mask),
                           self._retrieve_meta, kk,
                           min_overlap=0 if exact else self.spec.min_overlap,
                           bq=self.spec.bq,
                           rerank_factor=self.spec.rerank_factor)
        vals = np.asarray(res.vals, np.float32)
        rows = np.asarray(res.rows, np.int64)
        empty = vals <= NEG / 2          # slots no candidate could fill
        ids_out = np.full((q, kappa), -1, np.int64)
        sc_out = np.full((q, kappa), -np.inf, np.float32)
        ids_out[:, :kk] = np.where(empty, -1,
                                   self.ids[np.clip(rows, 0, n - 1)])
        sc_out[:, :kk] = np.where(empty, -np.inf, vals)
        blk_counts = np.asarray(res.blk_counts, np.int64)
        n_scored = blk_counts.sum(axis=1)
        exp = None
        if explain:
            # the kernel already surfaces its per-block counts and the
            # block-union prepass decisions — explain re-labels them, it
            # never re-runs or alters the compute
            skips = expand_tile_skips(np.asarray(res.skipped), q,
                                      self.spec.bq)
            exp = {"backend": "gam-device",
                   "n_candidates": n_scored.tolist(),
                   "block_candidates": blk_counts.tolist(),
                   "blocks_skipped": skips.sum(axis=1).tolist(),
                   "n_blocks": int(blk_counts.shape[1])}
        return RetrievalResult(
            ids=ids_out, scores=sc_out, n_scored=n_scored,
            discarded_frac=1.0 - n_scored / n,
            explain=exp,
        )

    def candidate_masks(self, users) -> jax.Array:
        """(Q, N) bool candidate masks on device — fully jit-traceable (the
        serving engine's GamHead jits straight through this)."""
        if not self.device:
            raise UnsupportedOp(self.spec.backend, "candidate_masks",
                                "CPU posting lists never materialise device "
                                "masks; open backend='gam-device'")
        u = jnp.asarray(users, jnp.float32)
        if self._scale is not None:
            u = u * jnp.asarray(self._scale)
        tau, vals = sparse_map(u, self.spec.cfg)
        return self.device_index.batch_candidate_mask(
            tau, self.spec.min_overlap, vals != 0.0)

    # ------------------------------------------------------------ state

    @property
    def n_items(self) -> int:
        return int(self.ids.size)

    def stats(self) -> dict:
        out = super().stats()
        out.update(p=self.spec.cfg.p, device=self.device,
                   bucket=self.spec.bucket, quantize=self.spec.quantize,
                   compress_postings=self.spec.compress_postings)
        if self.device and self.device_index is not None:
            out["n_spill"] = int(self.device_index.spill.shape[0])
            meta = self._retrieve_meta
            if meta is not None and meta.quantize == "int8":
                out["factor_bytes"] = int(np.asarray(meta.factors_q).nbytes
                                          + np.asarray(meta.scales).nbytes)
        if isinstance(self._cpu_index, CompressedInvertedIndex):
            out["index_bytes"] = self._cpu_index.nbytes
            out["n_patterns"] = self._cpu_index.n_patterns
        return out

    def snapshot(self, path: str) -> None:
        arrays = {
            "ids": self.ids, "items": self.items,
            "item_tau": self.item_tau, "item_mask": self.item_mask,
        }
        extra: dict = {}
        if self._scale is not None:
            arrays["scale"] = self._scale
        if not self.device:
            idx = self.index      # posting lists (built if still lazy)
            if isinstance(idx, CompressedInvertedIndex):
                arrays["sp_data"] = idx.slot_patterns.data
                arrays["sp_counts"] = idx.slot_patterns.counts
                arrays["pi_data"] = idx.pattern_items.data
                arrays["pi_counts"] = idx.pattern_items.counts
                extra["codec"] = {"sp_n": int(idx.slot_patterns.n_values),
                                  "pi_n": int(idx.pattern_items.n_values)}
            else:
                arrays["postings"] = idx.postings
                arrays["offsets"] = idx.offsets
        elif self.device_index is not None:
            meta = self._retrieve_meta
            if self.spec.compress_postings:
                # the dense-bucket table re-encoded as delta+group-varint
                # CSR; restore re-densifies bit-identically
                table = np.asarray(self.device_index.table)
                counts = np.asarray(self.device_index.counts)
                cp = encode_postings(*table_to_csr(table, counts))
                arrays["table_data"] = cp.data
                arrays["table_counts"] = cp.counts
                extra["codec"] = {"table_n": int(cp.n_values),
                                  "bucket": int(table.shape[1])}
            else:
                arrays["table"] = self.device_index.table
                arrays["counts"] = self.device_index.counts
            arrays.update(
                spill=self.device_index.spill,
                item_bits_t=meta.item_bits_t,
                block_union=meta.block_union,
                block_spill=meta.block_spill,
                spill8=meta.spill8,
            )
            if meta.quantize == "int8":
                arrays["factors_q"] = meta.factors_q
                arrays["scales"] = meta.scales
            extra["meta"] = {"bn": meta.bn, "words": meta.words,
                             "n_rows": meta.n_rows, "n_pad": meta.n_pad,
                             "quantize": meta.quantize}
        write_snapshot(path, self.spec, arrays, extra)

    def restore(self, path: str) -> "GamIndexRetriever":
        arrays, state = read_snapshot(path, self.spec)
        self._empty()
        if arrays["ids"].size == 0:
            return self
        self.ids = np.asarray(arrays["ids"], np.int64)
        self.items = np.asarray(arrays["items"], np.float32)
        self.item_tau = np.asarray(arrays["item_tau"])
        self.item_mask = np.asarray(arrays["item_mask"], bool)
        self._scale = (np.asarray(arrays["scale"], np.float32)
                       if "scale" in arrays else None)
        p = self.spec.cfg.p
        if not self.device:
            n, k = len(self.ids), self.item_tau.shape[1]
            if "sp_data" in arrays:
                codec = state["codec"]
                self._cpu_index = CompressedInvertedIndex(
                    CompressedPostings(
                        np.asarray(arrays["sp_data"], np.uint8),
                        np.asarray(arrays["sp_counts"], np.int32),
                        int(codec["sp_n"])),
                    CompressedPostings(
                        np.asarray(arrays["pi_data"], np.uint8),
                        np.asarray(arrays["pi_counts"], np.int32),
                        int(codec["pi_n"])),
                    n_items=n, p=p, k=k)
            else:
                idx = InvertedIndex.__new__(InvertedIndex)
                idx.n_items, idx.p, idx.k = n, p, k
                idx.postings = np.asarray(arrays["postings"], np.int32)
                idx.offsets = np.asarray(arrays["offsets"], np.int64)
                self._cpu_index = idx
        else:
            if "table_data" in arrays:
                codec = state["codec"]
                cp = CompressedPostings(
                    np.asarray(arrays["table_data"], np.uint8),
                    np.asarray(arrays["table_counts"], np.int32),
                    int(codec["table_n"]))
                table, counts = csr_to_table(
                    *decode_postings(cp), int(codec["bucket"]),
                    sentinel=len(self.ids))
            else:
                table = np.asarray(arrays["table"])
                counts = np.asarray(arrays["counts"])
            self.device_index = DeviceIndex(
                table=jnp.asarray(table),
                counts=jnp.asarray(counts),
                spill=jnp.asarray(arrays["spill"]),
                n_items=len(self.ids), p=p)
            self._items_dev = jnp.asarray(self.items)
            m = state["meta"]
            self._retrieve_meta = RetrievalMeta(
                item_bits_t=jnp.asarray(arrays["item_bits_t"]),
                block_union=jnp.asarray(arrays["block_union"]),
                block_spill=jnp.asarray(arrays["block_spill"]),
                spill8=jnp.asarray(arrays["spill8"]),
                p=p, words=int(m["words"]), bn=int(m["bn"]),
                n_rows=int(m["n_rows"]), n_pad=int(m["n_pad"]))
            if m.get("quantize", "none") == "int8":
                if "factors_q" in arrays:
                    self._retrieve_meta = dataclasses.replace(
                        self._retrieve_meta, quantize="int8",
                        factors_q=jnp.asarray(arrays["factors_q"], jnp.int8),
                        scales=jnp.asarray(arrays["scales"], jnp.float32))
                else:       # older file written before slabs were persisted
                    self._retrieve_meta = quantize_meta(self._retrieve_meta,
                                                        self.items)
        return self
