"""Inverted index over sparse embeddings (paper §1.1).

Two realisations:

* ``InvertedIndex`` — the paper-faithful CPU structure: CSR posting lists
  (numpy).  ``query`` walks the query's non-zero slots, unions the posting
  lists, and returns candidate ids + overlap counts.  This is what the
  retrieval-speedup benchmarks time.

* ``DeviceIndex`` — the TPU-shaped realisation used inside serving: posting
  lists padded to a fixed bucket width, stored as a dense (p, bucket) int32
  table so the query is gather + bincount, fully jit-able and shardable over
  the item/vocab axis.  Overflowing items (beyond bucket width) are tracked in
  an always-candidate spill list so recall is never silently lost.

* ``CompressedInvertedIndex`` — the memory-bound realisation:
  ``InvertedIndex`` factored through the pattern dictionary (items in one
  tessellation cell share one sparsity pattern, so the index stores
  slot -> pattern-ids and pattern-id -> items instead of slot -> items) with
  both CSR structures delta + group-varint encoded
  (:mod:`repro.compress.postings`).  Queries decode ONLY the touched slots
  and the surviving patterns' item lists, and answer bit-identically to the
  uncompressed ``query`` — ``decompress()`` round-trips the exact CSR.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress.patterns import pattern_dict_encode
from repro.compress.postings import (CodecError, CompressedPostings,
                                     decode_postings, encode_postings)

__all__ = ["CompressedInvertedIndex", "InvertedIndex", "DeviceIndex",
           "build_segment", "build_segment_csr", "candidate_mask_from_table",
           "csr_to_table", "table_to_csr"]


def table_to_csr(table: np.ndarray, counts: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Dense-bucket ``(p, bucket)`` table + per-slot counts -> CSR
    ``(postings, offsets)`` of the REAL (non-pad) entries, ascending within
    each slot (the builder's invariant).  The codec-facing flattening of a
    ``DeviceIndex``/shard segment."""
    table = np.asarray(table)
    counts = np.asarray(counts, np.int64)
    keep = np.arange(table.shape[1])[None, :] < counts[:, None]
    postings = table[keep].astype(np.int64)
    offsets = np.zeros(counts.size + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    return postings, offsets


def csr_to_table(postings: np.ndarray, offsets: np.ndarray, bucket: int,
                 sentinel: int) -> tuple[np.ndarray, np.ndarray]:
    """Inverse of :func:`table_to_csr`: re-densify a CSR into the
    ``(p, bucket)`` sentinel-padded table + counts, bit-identical to the
    original segment (lists must already be bucket-clipped)."""
    offsets = np.asarray(offsets, np.int64)
    counts = np.diff(offsets)
    p = counts.size
    if counts.size and int(counts.max()) > bucket:
        raise ValueError(f"slot length {int(counts.max())} > bucket {bucket}")
    table = np.full((p, bucket), sentinel, np.int32)
    keep = np.arange(bucket)[None, :] < counts[:, None]
    table[keep] = np.asarray(postings, np.int64)
    return table, counts.astype(np.int32)


def candidate_mask_from_table(table: jax.Array, spill: jax.Array,
                              query_indices: jax.Array, query_mask: jax.Array,
                              *, sentinel: int, min_overlap: int) -> jax.Array:
    """(sentinel,) bool candidate mask for ONE query pattern against a
    dense-bucket posting table.

    The single definition of candidate semantics — ``DeviceIndex`` and the
    service's sharded index both call this, which is what keeps their
    results bit-comparable.  ``sentinel`` is both the pad id in ``table``
    and the mask length (items are ids ``0..sentinel-1``); spill entries
    are always candidates, pad entries (id == sentinel) drop out of the
    scatter."""
    rows = table[query_indices]                 # (k, bucket)
    valid = (rows < sentinel) & query_mask[:, None]
    ids = jnp.where(valid, rows, 0)
    overlap = jnp.zeros(sentinel, jnp.int32).at[ids.ravel()].add(
        valid.ravel().astype(jnp.int32))
    mask = overlap >= min_overlap
    return mask.at[spill].set(True, mode="drop")


def _sorted_postings(item_indices: np.ndarray, p: int,
                     mask: np.ndarray | None):
    """Every (slot, item) posting of a segment sorted by slot, items
    ascending within a slot: -> (slots, items, position within the slot's
    list, per-slot list lengths)."""
    item_indices = np.asarray(item_indices)
    n, k = item_indices.shape
    if mask is None:
        mask = np.ones((n, k), bool)
    mask = np.asarray(mask, bool)
    flat_slots = item_indices[mask].astype(np.int64)
    flat_items = np.broadcast_to(
        np.arange(n, dtype=np.int32)[:, None], (n, k)
    )[mask]
    order = np.argsort(flat_slots, kind="stable")
    slots_sorted = flat_slots[order]
    items_sorted = flat_items[order]
    counts_full = np.bincount(slots_sorted, minlength=p)
    starts = np.zeros(p, np.int64)
    np.cumsum(counts_full[:-1], out=starts[1:])
    pos = np.arange(slots_sorted.size, dtype=np.int64) - starts[slots_sorted]
    return slots_sorted, items_sorted, pos, counts_full


def build_segment(item_indices: np.ndarray, p: int, bucket: int,
                  mask: np.ndarray | None = None, sentinel: int | None = None,
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorised scatter build of one dense-bucket posting segment.

    The shared builder behind ``DeviceIndex.build``, the service's index
    shards, and delta-segment compaction.  Returns ``(table, counts, spill)``:

      table:  (p, bucket) int32, padded with ``sentinel`` (default: n_items).
      counts: (p,) int32 posting-list lengths clipped to ``bucket``.
      spill:  sorted int32 ids of items overflowing any bucket.

    Within each posting list entries appear in item order — bit-identical to
    the sequential per-item build, but O(nnz log nnz) numpy instead of an
    O(N*k) Python loop (this is the hot path of ``compact()``).
    """
    if sentinel is None:
        sentinel = np.asarray(item_indices).shape[0]
    slots_sorted, items_sorted, pos, counts_full = _sorted_postings(
        item_indices, p, mask)
    table = np.full((p, bucket), sentinel, dtype=np.int32)
    fit = pos < bucket
    table[slots_sorted[fit], pos[fit]] = items_sorted[fit]
    spill = np.unique(items_sorted[~fit]).astype(np.int32)
    counts = np.minimum(counts_full, bucket).astype(np.int32)
    return table, counts, spill


def build_segment_csr(item_indices: np.ndarray, p: int, bucket: int,
                      mask: np.ndarray | None = None):
    """:func:`build_segment`'s segment in CSR form, without the dense
    (p, bucket) table: -> ``(postings, offsets, counts, spill)``, slot
    ``i``'s list (clipped to ``bucket``) being
    ``postings[offsets[i]:offsets[i + 1]]``.  ``csr_to_table(postings,
    offsets, bucket, sentinel)`` densifies it into ``build_segment``'s
    table bit-for-bit."""
    _, items_sorted, pos, counts_full = _sorted_postings(item_indices, p,
                                                         mask)
    fit = pos < bucket
    counts = np.minimum(counts_full, bucket).astype(np.int32)
    offsets = np.zeros(p + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    spill = np.unique(items_sorted[~fit]).astype(np.int32)
    return items_sorted[fit], offsets, counts, spill


class InvertedIndex:
    """CSR posting lists: for each embedding slot i, the items whose phi is
    non-zero at i."""

    def __init__(self, item_indices: np.ndarray, p: int,
                 mask: np.ndarray | None = None):
        """``item_indices``: (N, k) destination indices tau for each item.
        ``mask``: optional (N, k) bool — only True slots are indexed (the
        paper stores only coordinates where phi(v) is NON-zero, so thresholded
        coordinates never enter the index)."""
        item_indices = np.asarray(item_indices)
        n, k = item_indices.shape
        self.n_items, self.p, self.k = n, p, k
        if mask is None:
            mask = np.ones((n, k), bool)
        mask = np.asarray(mask, bool)
        flat_slots = item_indices[mask]
        flat_items = np.broadcast_to(
            np.arange(n, dtype=np.int32)[:, None], (n, k)
        )[mask]
        order = np.argsort(flat_slots, kind="stable")
        self.postings = flat_items[order]
        counts = np.bincount(flat_slots, minlength=p)
        self.offsets = np.zeros(p + 1, dtype=np.int64)
        np.cumsum(counts, out=self.offsets[1:])

    def posting_list(self, slot: int) -> np.ndarray:
        return self.postings[self.offsets[slot] : self.offsets[slot + 1]]

    def query(self, query_indices: np.ndarray, min_overlap: int = 1,
              mask: np.ndarray | None = None):
        """Candidates for one query: ids whose pattern shares >= min_overlap
        slots with the query's pattern.  Returns (candidate_ids, overlaps).

        Fully vectorised: the query's posting slices are gathered with one
        arange-offset trick and accumulated with a single ``np.add.at`` into
        a dense (n_items,) counter — no per-slot Python loop, which is what
        the paper-faithful retrieval-speedup benchmarks time."""
        q = np.asarray(query_indices)
        if mask is not None:
            q = q[np.asarray(mask, bool)]
        if q.size == 0:
            return np.empty(0, np.int32), np.empty(0, np.int64)
        starts = self.offsets[q]
        lens = self.offsets[q + 1] - starts
        total = int(lens.sum())
        # concatenated posting slices: arange over the total hit count,
        # rebased per slot from its cumulative start to its CSR start
        shift = np.cumsum(lens) - lens
        pos = np.arange(total, dtype=np.int64) + np.repeat(starts - shift,
                                                           lens)
        counts = np.zeros(self.n_items, np.int16)
        np.add.at(counts, self.postings[pos], 1)
        ids = np.nonzero(counts >= min_overlap)[0].astype(np.int32)
        return ids, counts[ids].astype(np.int64)

    def batch_query(self, query_indices: np.ndarray, min_overlap: int = 1,
                    mask: np.ndarray | None = None):
        qs = np.asarray(query_indices)
        return [
            self.query(qs[i], min_overlap, None if mask is None else mask[i])
            for i in range(qs.shape[0])
        ]

    def compress(self) -> "CompressedInvertedIndex":
        """Factor this index through the pattern dictionary and encode both
        CSR halves — see :class:`CompressedInvertedIndex`."""
        return CompressedInvertedIndex.from_inverted(self)

    @property
    def nbytes(self) -> int:
        return int(self.postings.nbytes + self.offsets.nbytes)


def _decode_slot_ranges(cp: CompressedPostings, slots: np.ndarray
                        ) -> tuple[np.ndarray, np.ndarray]:
    """Decode ONLY the requested slots of an encoded CSR stream.

    Deltas restart absolute at every slot boundary, so whole-slot decode is
    self-contained: byte offsets come from the control bytes (cheap vector
    bit ops), the selected values' bytes are gathered, and a per-slot
    segmented cumsum restores the ids.  Returns the concatenated values (in
    request order) and per-slot lengths."""
    slots = np.asarray(slots, np.int64)
    counts = np.asarray(cp.counts, np.int64)
    voff = np.zeros(counts.size + 1, np.int64)
    np.cumsum(counts, out=voff[1:])
    lens = counts[slots]
    total = int(lens.sum())
    if total == 0:
        return np.empty(0, np.int64), lens
    # global value indices of every requested entry (arange-offset trick)
    shift = np.cumsum(lens) - lens
    vidx = np.arange(total, dtype=np.int64) + np.repeat(voff[slots] - shift,
                                                        lens)
    n = int(cp.n_values)
    ngroups = -(-n // 4)
    ctrl = cp.data[:ngroups]
    nb = np.empty((ngroups, 4), np.uint8)
    for j in range(4):
        nb[:, j] = ((ctrl >> (2 * j)) & 3) + 1
    nb = nb.reshape(-1)
    boff = np.zeros(nb.size + 1, np.int64)
    np.cumsum(nb, out=boff[1:])
    base = ngroups + boff[vidx]
    ln = nb[vidx]
    b = np.zeros((total, 4), np.uint8)
    for j in range(4):
        sel = ln > j
        b[sel, j] = cp.data[base[sel] + j]
    d = b.view("<u4").ravel().astype(np.int64)
    # segmented cumsum: the first value of each slot is absolute
    c = np.cumsum(d)
    nz = lens > 0
    starts = shift[nz]
    bases = c[starts] - d[starts]
    return c - np.repeat(bases, lens[nz]), lens


class CompressedInvertedIndex:
    """``InvertedIndex`` factored through shared patterns, varint-encoded.

    Two encoded CSR structures replace the flat posting lists:

      slot_patterns:  slot -> ascending ids of the DISTINCT patterns with
                      that slot set (one entry per occupied cell, not per
                      item).
      pattern_items:  pattern id -> ascending item ids carrying it.

    An item's overlap with a query equals its pattern's overlap, so the
    query path counts pattern hits first (tiny) and expands only the
    patterns that survive ``min_overlap`` — answers are bit-identical to
    :meth:`InvertedIndex.query` while storage shrinks from one posting per
    (item, slot) pair to one per (pattern, slot) pair plus one id per item.
    """

    def __init__(self, slot_patterns: CompressedPostings,
                 pattern_items: CompressedPostings, *, n_items: int, p: int,
                 k: int):
        self.slot_patterns = slot_patterns
        self.pattern_items = pattern_items
        self.n_items = int(n_items)
        self.p = int(p)
        self.k = int(k)

    @property
    def n_patterns(self) -> int:
        return self.pattern_items.p

    @property
    def nbytes(self) -> int:
        return int(self.slot_patterns.nbytes + self.pattern_items.nbytes)

    @classmethod
    def from_inverted(cls, index: InvertedIndex) -> "CompressedInvertedIndex":
        p, n = index.p, index.n_items
        slots = np.repeat(np.arange(p, dtype=np.int64),
                          np.diff(index.offsets))
        items = index.postings.astype(np.int64)
        if np.unique(slots * max(n, 1) + items).size != items.size:
            raise CodecError("duplicate (slot, item) postings cannot be "
                             "pattern-factored")
        words = -(-p // 32)
        bits = np.zeros((n, words), np.uint32)
        np.bitwise_or.at(bits, (items, slots // 32),
                         np.uint32(1) << (slots % 32).astype(np.uint32))
        uniq, inverse = pattern_dict_encode(bits)
        u = uniq.shape[0]
        # slot -> distinct pattern ids (unique (slot, pid) pairs, sorted)
        pid = inverse.astype(np.int64)[items]
        pairs = np.unique(slots * max(u, 1) + pid)
        sp_slots = pairs // max(u, 1)
        sp_counts = np.bincount(sp_slots, minlength=p)
        sp_off = np.zeros(p + 1, np.int64)
        np.cumsum(sp_counts, out=sp_off[1:])
        slot_patterns = encode_postings(pairs % max(u, 1), sp_off)
        # pattern id -> ascending item ids (stable sort keeps item order)
        order = np.argsort(inverse, kind="stable")
        pi_counts = np.bincount(inverse, minlength=u)
        pi_off = np.zeros(u + 1, np.int64)
        np.cumsum(pi_counts, out=pi_off[1:])
        pattern_items = encode_postings(
            np.arange(n, dtype=np.int64)[order], pi_off)
        return cls(slot_patterns, pattern_items, n_items=n, p=p, k=index.k)

    # ------------------------------------------------------------- queries

    def posting_list(self, slot: int) -> np.ndarray:
        pids, _ = _decode_slot_ranges(self.slot_patterns,
                                      np.asarray([slot], np.int64))
        items, _ = _decode_slot_ranges(self.pattern_items, pids)
        return np.sort(items).astype(np.int32)

    def query(self, query_indices: np.ndarray, min_overlap: int = 1,
              mask: np.ndarray | None = None):
        """Bit-identical to :meth:`InvertedIndex.query`, decoding only the
        query's slots and the patterns that survive the overlap gate."""
        q = np.asarray(query_indices)
        if mask is not None:
            q = q[np.asarray(mask, bool)]
        if q.size == 0:
            return np.empty(0, np.int32), np.empty(0, np.int64)
        pids, _ = _decode_slot_ranges(self.slot_patterns,
                                      q.astype(np.int64))
        if pids.size == 0:
            return np.empty(0, np.int32), np.empty(0, np.int64)
        hits = np.bincount(pids, minlength=self.n_patterns)
        sel = np.nonzero(hits >= min_overlap)[0]
        if sel.size == 0:
            return np.empty(0, np.int32), np.empty(0, np.int64)
        items, lens = _decode_slot_ranges(self.pattern_items, sel)
        overlaps = np.repeat(hits[sel], lens)
        order = np.argsort(items, kind="stable")
        return items[order].astype(np.int32), overlaps[order].astype(np.int64)

    def batch_query(self, query_indices: np.ndarray, min_overlap: int = 1,
                    mask: np.ndarray | None = None):
        qs = np.asarray(query_indices)
        return [
            self.query(qs[i], min_overlap, None if mask is None else mask[i])
            for i in range(qs.shape[0])
        ]

    # --------------------------------------------------------------- state

    def decompress(self) -> InvertedIndex:
        """Bit-exact reconstruction of the flat CSR realisation."""
        sp_post, sp_off = decode_postings(self.slot_patterns)
        pi_post, pi_off = decode_postings(self.pattern_items)
        pi_counts = np.diff(pi_off)
        # expand every (slot, pattern) pair into the pattern's item list
        slot_of_pair = np.repeat(np.arange(self.p, dtype=np.int64),
                                 np.diff(sp_off))
        lens = pi_counts[sp_post]
        total = int(lens.sum())
        shift = np.cumsum(lens) - lens
        idx = np.arange(total, dtype=np.int64) + np.repeat(
            pi_off[sp_post] - shift, lens)
        post_items = pi_post[idx]
        post_slots = np.repeat(slot_of_pair, lens)
        order = np.lexsort((post_items, post_slots))
        out = InvertedIndex.__new__(InvertedIndex)
        out.n_items, out.p, out.k = self.n_items, self.p, self.k
        out.postings = post_items[order].astype(np.int32)
        counts = np.bincount(post_slots, minlength=self.p)
        out.offsets = np.zeros(self.p + 1, np.int64)
        np.cumsum(counts, out=out.offsets[1:])
        return out


@dataclasses.dataclass
class DeviceIndex:
    """Dense-bucket inverted index living on device.

    table:  (p, bucket) int32 item ids, padded with n_items (a sentinel id).
    counts: (p,) int32 true posting-list lengths.
    spill:  (n_spill,) int32 ids of items overflowing any bucket — always
            treated as candidates (recall-preserving).
    """

    table: jax.Array
    counts: jax.Array
    spill: jax.Array
    n_items: int
    p: int

    @staticmethod
    def build(item_indices: np.ndarray, p: int, bucket: int = 256,
              mask: np.ndarray | None = None) -> "DeviceIndex":
        item_indices = np.asarray(item_indices)
        n = item_indices.shape[0]
        table, counts, spill = build_segment(item_indices, p, bucket, mask)
        return DeviceIndex(
            table=jnp.asarray(table),
            counts=jnp.asarray(counts),
            spill=jnp.asarray(spill),
            n_items=n,
            p=p,
        )

    def candidate_mask(self, query_indices: jax.Array, min_overlap: int = 1,
                       query_mask: jax.Array | None = None) -> jax.Array:
        """(n_items,) bool — jit-able candidate mask for one query pattern."""
        if query_mask is None:
            query_mask = jnp.ones(query_indices.shape, bool)
        return candidate_mask_from_table(
            self.table, self.spill, query_indices, query_mask,
            sentinel=self.n_items, min_overlap=min_overlap)

    def batch_candidate_mask(self, query_indices: jax.Array, min_overlap: int = 1,
                             query_mask: jax.Array | None = None) -> jax.Array:
        if query_mask is None:
            return jax.vmap(lambda q: self.candidate_mask(q, min_overlap))(
                query_indices
            )
        return jax.vmap(
            lambda q, m: self.candidate_mask(q, min_overlap, m)
        )(query_indices, query_mask)
