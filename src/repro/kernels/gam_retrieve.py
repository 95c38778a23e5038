"""Fused streaming retrieval kernel: block-skipping candidate scoring with
on-chip top-kappa (the GAM serving hot loop).

The dense path (``candidate_mask_from_table`` + ``gam_score`` + ``lax.top_k``)
materialises a (Q, N) bool mask and a (Q, N) score tensor in HBM even though
the paper's whole point is that retrieval cost should be proportional to the
*candidate* set.  This kernel fuses all three stages into one streaming pass
over item blocks so HBM output shrinks to O(Q * kappa):

  * **Candidate overlap on the fly** — each row's sparsity pattern (the tau
    destinations of phi with non-zero value) is packed into ceil(p/32) uint32
    words; pattern overlap is ``popcount(q_bits & item_bits)``, which equals
    the posting-table overlap count exactly (tau destinations are unique per
    row, and bucket overflow only ever *removes* table counts for items that
    are then spill-listed — spill rows are unconditional candidates here as
    in the table path, so the candidate set is bit-identical).  The popcount
    runs only over the words the batch sets: a query sets a handful of
    bits, against 258-629 words at k=64-100, so a batch of 32 sets about
    a fifth of the words or fewer.  Each launch lists the words some query
    sets (up to :data:`COMPACT_WORDS` of them; a batch that sets more runs
    the loop over every word), and the kernel loops over those rows of
    each item block alone.  A word no query sets adds
    ``popcount(0 & x) = 0`` to every overlap, so the overlaps, and
    everything decided from them, are exact.

  * **Block skipping** — a prepass intersects each query's bits with the
    per-block *union* pattern (posting-derived block metadata built at index
    time).  The union popcount upper-bounds every member item's overlap, so a
    (Q_blk, N_blk) tile whose bound is below ``min_overlap`` (and holds no
    spill row) provably has zero candidates and is skipped under ``pl.when``:
    no MXU work, no accumulator merge, no HBM writes for the discarded block.

  * **On-chip top-kappa** — a flash-attention-style running accumulator of
    (score, global row) pairs lives in the revisited output block (VMEM
    resident across the item-block grid axis).  The merge implements the
    total order (score desc, row asc) — exactly ``lax.top_k``'s tie-break
    over the full masked score row — so the ids equal the dense
    ``masked_topk`` path's after empty-slot normalisation, and the scores
    equal it up to float summation order (a dot over a (bq, bn) tile and
    one over the whole (Q, N) product may accumulate in different orders,
    on XLA's CPU backend as on the MXU).

Grid: (Q / bq, N / bn) with the item axis innermost; queries, query bits and
the accumulator stay resident in VMEM while item blocks stream through.

Empty-slot contract: slots with no candidate return ``(NEG, -1)``; callers
never see a fabricated row id for a non-candidate (the dense path instead
returns an arbitrary ``lax.top_k`` index that every consumer immediately
filters on ``score <= NEG / 2`` — both paths are identical post-filter).

Interpret mode (CPU) runs the same candidate/skip semantics but may use a
``lax.top_k``-based merge (``loop_merge=False``); the Mosaic path selects
with argmax steps since sort primitives do not lower to TPU.  An entry
enters a query's pool only if it beats the pool's kappa-th score, which
few entries of a tile do once the pool has filled, so each tile counts
them, takes the largest count over its query block (at most kappa), and
runs that many steps over its beating entries, each inserted into the
sorted accumulator: none on most tiles.  A tile where some query gains
kappa entries runs kappa unrolled steps over the accumulator and the tile
together.  The steps a launch ran come back per query block
(``merge_steps``).  Both merges realise the same total order and are
cross-checked in tests.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.compress.quantize import quantize_int8
from repro.kernels.gam_score import NEG

__all__ = ["RetrievalMeta", "GamRetrieveResult", "RowCapacityError",
           "COMPACT_WORDS", "SKIP_MAP_TILES", "TOPK_EMPTY_ROW",
           "block_unions", "build_retrieval_meta", "effective_bq",
           "expand_tile_skips", "export_topk", "gam_retrieve", "pack_patterns",
           "rerank_pool"]

# Row sentinel for non-candidate tile entries: larger than any real global row
# (catalogs < 2^30 rows — enforced by RowCapacityError at build/assembly
# time) so the (score desc, row asc) tie-break at NEG always prefers an
# accumulator "empty" slot (negative row) over a discarded item.
_NO_ROW = np.int32(1 << 30)

#: Hard structural-row ceiling: rows at or past this value would collide
#: with the `_NO_ROW` tile sentinel and silently corrupt the tie-break.
ROW_CAPACITY = int(_NO_ROW)


class RowCapacityError(ValueError):
    """A catalog layout would push structural rows to >= 2^30, where real
    rows collide with the kernel's ``_NO_ROW`` non-candidate sentinel and
    results silently corrupt.  Raised loudly at ``build_retrieval_meta`` /
    partition-validation time instead."""

    def __init__(self, what: str, rows: int):
        super().__init__(
            f"{what} = {rows} rows exceeds the kernel row capacity "
            f"{ROW_CAPACITY} (2^30): row ids would collide with the "
            f"_NO_ROW sentinel. Shard the catalog across hosts instead.")

# Exported-accumulator sentinel for EMPTY top-kappa slots: the largest int32,
# so it sorts after every real global row (< 2^30 + any shard offset < 2^31)
# under the (score desc, row asc) total order while staying collective-safe
# (int32 survives cross-host all-gathers that would truncate an int64 pad).
TOPK_EMPTY_ROW = np.int32(np.iinfo(np.int32).max)

#: Most (query block, item block) tiles one launch's skip map may hold: the
#: map sits whole in SMEM at 4 bytes a tile, and a v5e has 1 MiB of it.
#: 2^17 tiles is Q=256 over 2^22 rows at bn=256, which compiles for a v5e
#: (tests/test_tpu_compile.py); Q=512 there runs out of SMEM.
SKIP_MAP_TILES = 1 << 17

#: Width of the compacted overlap loop: a launch whose queries set at
#: most this many distinct pattern words loops over a list of them, padded
#: to this width, and others over every word (:func:`_gam_retrieve`).  An
#: index of at most this many words has the full loop only.
COMPACT_WORDS = 128


def effective_bq(q: int, bq: int = 32) -> int:
    """The query-block height the kernel actually tiles with: the requested
    ``bq`` clamped to the padded query count (multiple of 8, minimum 8).
    Single source of the clamp — :func:`_gam_retrieve` tiles with it and
    :func:`expand_tile_skips` inverts the tiling, so the two can never
    disagree about which queries shared a skip row."""
    return max(8, min(int(bq), -(-int(q) // 8) * 8))


def expand_tile_skips(skipped, q: int, bq: int = 32) -> np.ndarray:
    """(q_blocks, n_blocks) kernel skip map -> (q, n_blocks) per-query bool.

    The block-union prepass decides skips per QUERY BLOCK (all ``bq`` rows
    of a tile share the decision); this repeats each decision across its
    block's real query rows so ``explain`` can report, per query, which
    item blocks the prepass pruned.  Pure host-side numpy on an existing
    kernel output — the compute path is untouched.
    """
    sk = np.asarray(skipped, bool)
    return np.repeat(sk, effective_bq(q, bq), axis=0)[:q]


def export_topk(vals, rows, *, offset: int = 0
                ) -> tuple[np.ndarray, np.ndarray]:
    """Accumulator export: kernel-local (vals, rows) -> merge-ready arrays.

    Maps shard/group-local accumulator rows to GLOBAL rows by ``offset`` and
    pins empty slots (score <= NEG, row -1) to :data:`TOPK_EMPTY_ROW`, so
    any number of exported accumulators — per-bn-group launches on one host,
    or per-host accumulators gathered by a cross-host collective — merge
    under one ``lexsort((rows, -scores))`` into exactly the kernel's
    (score desc, row asc) total order.  Output is (Q, kappa) f32 scores and
    (Q, kappa) int32 global rows (int32 on purpose: the multi-host merge
    all-gathers these, and int32 is exact under default-precision jax).
    """
    scores = np.asarray(vals, np.float32)
    r = np.asarray(rows, np.int64)
    r = np.where((r < 0) | (scores <= NEG / 2), int(TOPK_EMPTY_ROW),
                 r + int(offset))
    return scores, r.astype(np.int32)


# --------------------------------------------------------------- metadata


@dataclasses.dataclass(frozen=True)
class RetrievalMeta:
    """Posting-derived block metadata the fused kernel streams against.

    Built once at index time by :func:`build_retrieval_meta`; the per-item
    pattern bitsets replace the (p, bucket) posting table on the query path,
    and the per-block unions drive the zero-candidate tile skip.
    """

    item_bits_t: jax.Array   # (words, n_pad) uint32 — packed patterns, transposed
    block_union: jax.Array   # (n_blocks, words) uint32 — OR of member patterns
    block_spill: jax.Array   # (n_blocks,) bool — block holds a spill row
    spill8: jax.Array        # (1, n_pad) int8 — per-row unconditional-candidate flag
    p: int                   # pattern-space dimensionality
    words: int               # ceil(p / 32)
    bn: int                  # item-block width (grid tile on the item axis)
    n_rows: int              # structural rows of the factor array served
    n_pad: int               # n_rows rounded up to a multiple of bn
    quantize: str = "none"            # "none" | "int8"
    factors_q: jax.Array | None = None  # (n_pad, k) int8 quantized factors
    scales: jax.Array | None = None     # (1, n_blocks) f32 dequant scales

    @property
    def n_blocks(self) -> int:
        return self.n_pad // self.bn


#: rows :func:`pack_patterns` scatters at a time
_PACK_ROWS = 1 << 16


def pack_patterns(tau: np.ndarray, mask: np.ndarray, p: int,
                  out: np.ndarray | None = None, col: int = 0) -> np.ndarray:
    """(n, k) tau destinations + non-zero mask -> (ceil(p/32), n) uint32
    bitsets, packed straight into the kernel's ``item_bits_t`` layout (one
    column per row): row ``i`` lands in column ``col + i`` of ``out`` (a
    C-contiguous array, zeros where nothing is packed yet).  Rows go
    ``_PACK_ROWS`` at a time, so the scatter's temporaries stay small
    whatever ``n`` is, and runs of disjoint columns may be packed from
    several threads."""
    tau = np.asarray(tau)
    mask = np.asarray(mask, bool)
    n = tau.shape[0]
    if out is None:
        out = np.zeros((-(-p // 32), n), np.uint32)
    flat, width = out.reshape(-1), out.shape[1]
    for lo in range(0, n, _PACK_ROWS):
        r, j = np.nonzero(mask[lo:lo + _PACK_ROWS])
        t = tau[lo:lo + _PACK_ROWS][r, j].astype(np.int64)
        np.bitwise_or.at(flat, (t >> 5) * width + (col + lo) + r,
                         np.uint32(1) << (t & 31).astype(np.uint32))
    return out


def block_unions(bits_t: np.ndarray, bn: int) -> np.ndarray:
    """(words, n) packed patterns, n a multiple of ``bn`` -> the
    (n / bn, words) OR of each ``bn``-row block's patterns."""
    words = bits_t.shape[0]
    return np.ascontiguousarray(np.bitwise_or.reduce(
        bits_t.reshape(words, -1, bn), axis=2).T)


def _pack_patterns_jnp(tau: jax.Array, mask: jax.Array, words: int) -> jax.Array:
    """Query-side packing, jit-traceable (tau destinations unique per row, so
    scatter-add of distinct powers of two equals bitwise OR)."""
    q, k = tau.shape
    word = tau.astype(jnp.int32) // 32
    bit = (tau % 32).astype(jnp.uint32)
    vals = jnp.where(mask, jnp.left_shift(jnp.uint32(1), bit), jnp.uint32(0))
    rows = jnp.broadcast_to(jnp.arange(q)[:, None], (q, k))
    return jnp.zeros((q, words), jnp.uint32).at[rows, word].add(vals)


def quantize_meta(meta: RetrievalMeta, factors) -> RetrievalMeta:
    """Attach an int8 factor slab + per-block scales to existing metadata.

    ``factors``: (m, k) f32 with m <= meta.n_pad; rows past m quantize as
    zeros (structural pads).  One f32 scale per ``bn``-row kernel block, so
    the scale rides the same grid axis as its factor tile."""
    f = np.asarray(factors, np.float32)
    if f.ndim != 2 or f.shape[0] > meta.n_pad:
        raise ValueError(f"factors shape {f.shape} does not fit "
                         f"n_pad={meta.n_pad}")
    fp = np.zeros((meta.n_pad, f.shape[1]), np.float32)
    fp[: f.shape[0]] = f
    q, scales = quantize_int8(fp, block=meta.bn)
    return dataclasses.replace(
        meta, quantize="int8", factors_q=jnp.asarray(q),
        scales=jnp.asarray(scales.reshape(1, -1)))


def build_retrieval_meta(tau: np.ndarray, mask: np.ndarray, p: int, *,
                         n_rows: int | None = None,
                         spill_rows: np.ndarray | None = None,
                         bn: int = 256, factors: np.ndarray | None = None,
                         quantize: str = "none") -> RetrievalMeta:
    """Build the kernel's block metadata for ``n_rows`` structural rows.

    ``tau``/``mask``: (n, k) patterns of the *real* rows, which must occupy
    rows 0..n-1 of the served factor array (structural pad rows n..n_rows-1
    carry empty patterns and can only become candidates via ``min_overlap=0``
    + an ``alive`` mask, which callers with pad rows must supply).
    ``spill_rows``: global row ids that are unconditional candidates (posting
    bucket overflow — same recall-preserving semantics as ``DeviceIndex``).
    ``quantize="int8"`` additionally quantizes ``factors`` (required then)
    into a per-block-scaled int8 slab the kernel decodes in its inner loop.
    """
    if quantize not in ("none", "int8"):
        raise ValueError(f"unknown quantize mode {quantize!r}")
    tau = np.asarray(tau)
    mask = np.asarray(mask, bool)
    n = tau.shape[0]
    n_rows = n if n_rows is None else int(n_rows)
    if n_rows < n:
        raise ValueError(f"n_rows={n_rows} < {n} pattern rows")
    words = -(-p // 32)
    bn = max(8, min(int(bn), -(-max(n_rows, 1) // 8) * 8))
    n_blocks = -(-max(n_rows, 1) // bn)
    n_pad = n_blocks * bn
    if n_pad > ROW_CAPACITY:     # before any O(n_pad) allocation
        raise RowCapacityError("padded catalog (n_pad)", n_pad)
    bits_t = np.zeros((words, n_pad), np.uint32)
    if n:
        pack_patterns(tau, mask, p, out=bits_t)
    spill = np.zeros(n_pad, bool)
    if spill_rows is not None and np.asarray(spill_rows).size:
        spill[np.asarray(spill_rows, np.int64)] = True
    meta = RetrievalMeta(
        item_bits_t=jnp.asarray(bits_t),
        block_union=jnp.asarray(block_unions(bits_t, bn)),
        block_spill=jnp.asarray(spill.reshape(n_blocks, bn).any(axis=1)),
        spill8=jnp.asarray(spill.astype(np.int8)[None, :]),
        p=int(p), words=words, bn=bn, n_rows=n_rows, n_pad=n_pad,
    )
    if quantize == "int8":
        if factors is None:
            raise ValueError("quantize='int8' requires the factor slab")
        meta = quantize_meta(meta, factors)
    return meta


# ----------------------------------------------------------------- kernel


def _overlap(qb, ibT, *, words, fused_words):
    """Pattern-set intersection sizes: (bq, words) x (words, bn) -> (bq, bn)."""
    if fused_words:
        # one vectorised op over all words (interpret / XLA-friendly)
        inter = qb[:, None, :] & jnp.transpose(ibT)[None, :, :]
        return jnp.sum(jax.lax.population_count(inter).astype(jnp.int32),
                       axis=-1)
    # word-at-a-time 2D ops (Mosaic-friendly layouts)
    ov = jnp.zeros((qb.shape[0], ibT.shape[1]), jnp.int32)
    for w in range(words):
        ov = ov + jax.lax.population_count(
            qb[:, w:w + 1] & ibT[w:w + 1, :]).astype(jnp.int32)
    return ov


def _overlap_listed(qb, ib_ref, word_ref, *, fused_words):
    """:func:`_overlap` over the rows of the item block that the launch's
    word list (SMEM) names: ``qb`` (bq, COMPACT_WORDS) holds the listed
    words' query columns.  A listed slot that no query sets is zero in
    every query row and adds ``popcount(0 & x) = 0``, so the sum is the
    full pattern overlap.  The loop stays unrolled over every slot: on a
    v5e a rolled loop, or one that skips the unused slots, cost more per
    word than the slots it saves."""
    if fused_words:
        return _overlap(qb, ib_ref[...][word_ref[...]], words=qb.shape[1],
                        fused_words=True)
    ov = jnp.zeros((qb.shape[0], ib_ref.shape[1]), jnp.int32)
    for w in range(qb.shape[1]):
        ov = ov + jax.lax.population_count(
            qb[:, w:w + 1] & ib_ref[pl.ds(word_ref[w], 1), :]
        ).astype(jnp.int32)
    return ov


def _merge_topk(acc_s, acc_r, tile_s, tile_r, *, kappa, loop_merge):
    """Running top-kappa merge under the total order (score desc, row asc)
    -> (scores, rows, steps).

    Accumulator invariant (maintained by both merges): entries sorted by that
    order, rows pairwise distinct, NEG "empty" slots carry negative rows that
    beat the _NO_ROW sentinels of discarded items on score ties.

    ``steps`` is the most tile entries any query row takes into its top
    kappa, capped at kappa: the selection steps the Mosaic merge runs.
    Only an entry above its row's kappa-th score can enter (an accumulator
    entry holds a smaller row than any of this tile's, so one that only
    ties the kappa-th loses), and of those at most kappa.
    """
    beat = tile_s > jnp.min(acc_s, axis=1, keepdims=True)
    steps = jnp.minimum(jnp.max(jnp.sum(beat.astype(jnp.int32), axis=1)),
                        kappa)

    def concat():
        return (jnp.concatenate([acc_s, tile_s], axis=1),
                jnp.concatenate([acc_r, tile_r], axis=1))

    if not loop_merge:
        # lax.top_k breaks score ties by position; accumulator entries precede
        # the tile and hold strictly smaller rows on ties (earlier blocks),
        # and tile columns are ascending-row — so position order == row order.
        cat_s, cat_r = concat()
        new_s, idx = jax.lax.top_k(cat_s, kappa)
        return new_s, jnp.take_along_axis(cat_r, idx, axis=1), steps
    # Mosaic path (sort ops don't lower to TPU): ``steps`` selection steps,
    # zero on most tiles.  Rows are pairwise distinct, so removing an entry
    # by its row erases exactly one.

    def select_all():
        # a tile where some row gains kappa entries: kappa argmax steps over
        # the accumulator and the tile, unrolled (a v5e runs an unrolled
        # step in about four fifths of the time of a step of the loop below)
        cat_s, cat_r = concat()
        sel_s, sel_r = [], []
        for _ in range(kappa):
            best = jnp.max(cat_s, axis=1, keepdims=True)
            row = jnp.min(
                jnp.where(cat_s == best, cat_r, _NO_ROW + jnp.int32(1)),
                axis=1, keepdims=True)
            sel_s.append(best)
            sel_r.append(row)
            cat_s = jnp.where(cat_r == row, -jnp.inf, cat_s)
        return jnp.concatenate(sel_s, axis=1), jnp.concatenate(sel_r, axis=1)

    def insert_beating():
        # each step takes a row's best remaining beating entry (max score,
        # then min row) and inserts it after the accumulator entries that
        # beat or tie it, which hold smaller rows; the tail shifts one slot
        # and the last entry drops.  A row that has run out draws -inf,
        # which lands past the last slot.
        slot = jax.lax.broadcasted_iota(jnp.int32, acc_s.shape, 1)

        def step(_, carry):
            cand_s, new_s, new_r = carry
            best = jnp.max(cand_s, axis=1, keepdims=True)
            row = jnp.min(jnp.where(cand_s == best, tile_r, _NO_ROW), axis=1,
                          keepdims=True)
            cand_s = jnp.where(tile_r == row, -jnp.inf, cand_s)
            pos = jnp.sum((new_s >= best).astype(jnp.int32), axis=1,
                          keepdims=True)
            new_s = jnp.where(slot < pos, new_s, jnp.where(
                slot == pos, best, pltpu.roll(new_s, 1, 1)))
            new_r = jnp.where(slot < pos, new_r, jnp.where(
                slot == pos, row, pltpu.roll(new_r, 1, 1)))
            return cand_s, new_s, new_r

        _, new_s, new_r = jax.lax.fori_loop(
            0, steps, step, (jnp.where(beat, tile_s, -jnp.inf), acc_s, acc_r))
        return new_s, new_r

    new_s, new_r = jax.lax.cond(steps == kappa, select_all, insert_beating)
    return new_s, new_r, steps


def _kernel(skip_ref, *refs, kappa, min_overlap, bn, n_blocks, words,
            loop_merge, fused_words, compact=False, quantized=False):
    if compact:
        # the compacted loop's word list (SMEM) follows the skip map
        word_ref, *refs = refs
    u_ref, qb_ref, v_ref, *rest = refs
    if quantized:
        # the int8 factor tile's per-block scales (SMEM) precede the bit refs
        (sc_ref, ib_ref, sp_ref, al_ref, vals_ref, rows_ref, cnt_ref,
         steps_ref) = rest
    else:
        sc_ref = None
        ib_ref, sp_ref, al_ref, vals_ref, rows_ref, cnt_ref, steps_ref = rest
    j = pl.program_id(1)
    bq = u_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        vals_ref[...] = jnp.full((bq, kappa), NEG, jnp.float32)
        # distinct negative sentinel rows: deterministic NEG-tie resolution
        rows_ref[...] = -1 - jax.lax.broadcasted_iota(jnp.int32, (bq, kappa), 1)
        steps_ref[...] = jnp.zeros((bq, 1), jnp.int32)

    cnt_ref[...] = jnp.zeros((bq, 1), jnp.int32)

    @pl.when(skip_ref[pl.program_id(0) * n_blocks + j] == 0)
    def _tile():
        if compact:
            ov = _overlap_listed(qb_ref[...], ib_ref, word_ref,
                                 fused_words=fused_words)
        else:
            ov = _overlap(qb_ref[...], ib_ref[...], words=words,
                          fused_words=fused_words)
        cand = ((ov >= min_overlap) | (sp_ref[...] != 0)) & (al_ref[...] != 0)
        cnt_ref[...] = jnp.sum(cand.astype(jnp.int32), axis=1, keepdims=True)
        v = v_ref[...]
        if quantized:
            # in-loop decode: int8 tile * its block's scale (one SMEM scalar)
            v = v.astype(jnp.float32) * sc_ref[j]
        scores = jax.lax.dot_general(
            u_ref[...], v,
            dimension_numbers=(((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        col = jax.lax.broadcasted_iota(jnp.int32, (bq, bn), 1)
        tile_s = jnp.where(cand, scores, NEG)
        tile_r = jnp.where(cand, j * bn + col, _NO_ROW + col)
        new_s, new_r, steps = _merge_topk(
            vals_ref[...], rows_ref[...], tile_s, tile_r, kappa=kappa,
            loop_merge=loop_merge)
        vals_ref[...] = new_s
        rows_ref[...] = new_r
        steps_ref[...] += steps


class GamRetrieveResult(NamedTuple):
    vals: jax.Array        # (Q, kappa) f32 exact scores, NEG in empty slots
    rows: jax.Array        # (Q, kappa) int32 global rows, -1 in empty slots
    blk_counts: jax.Array  # (Q, n_blocks) int32 candidates per item block
    skipped: jax.Array     # (q_blocks, n_blocks) bool — tiles never scored
    loop_words: jax.Array  # (q_blocks,) int32 pattern words the overlap
                           # loop ran over (the listed words, or all)
    merge_steps: jax.Array  # (q_blocks,) int32 selection steps the merge
                            # ran over the item blocks (kappa a tile at most)


def _launch(q_bits, word_idx, union, ibT, up, fp, extra, extra_specs,
            bspill, spill8, al8, *, kappa, min_overlap, bq, bn, interpret,
            loop_merge):
    """The block prepass and the kernel over ``q_bits``' pattern words:
    every word of ``ibT`` (words, n_pad) when ``word_idx`` is None, else
    ``q_bits`` (qp, w) and ``union`` (nb, w) hold the words ``word_idx``
    (w,) names.  -> padded (vals, rows, counts), the (qp / bq, nb) skip
    map and the (qp, 1) merge steps (each query block's in all its rows)."""
    qp, k = up.shape
    nb, words = union.shape

    # ---- block prepass: union popcount upper-bounds member overlap --------
    # (a pad query row sets no bits, so it marks no block possible that the
    # real rows of its query block do not)
    ub = jnp.sum(jax.lax.population_count(
        q_bits[:, None, :] & union[None, :, :]).astype(jnp.int32), axis=-1)
    possible = (ub >= min_overlap) | bspill[None, :]            # (qp, nb)
    skip = jnp.logical_not(
        possible.reshape(qp // bq, bq, nb).any(axis=1)).astype(jnp.int32)

    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    compact = word_idx is not None
    kernel = pl.pallas_call(
        partial(_kernel, kappa=kappa, min_overlap=min_overlap, bn=bn,
                n_blocks=nb, words=words, loop_merge=loop_merge,
                fused_words=interpret, compact=compact,
                quantized=bool(extra)),
        grid=(qp // bq, nb),
        in_specs=[
            smem,
            *([smem] if compact else []),
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, words), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
            *extra_specs,
            # every word of the item block; a compacted launch reads the
            # rows word_idx names from it
            pl.BlockSpec((ibT.shape[0], bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=(
            pl.BlockSpec((bq, kappa), lambda i, j: (i, 0)),
            pl.BlockSpec((bq, kappa), lambda i, j: (i, 0)),
            pl.BlockSpec((pl.squeezed, bq, 1), lambda i, j: (j, i, 0)),
            pl.BlockSpec((bq, 1), lambda i, j: (i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((qp, kappa), jnp.float32),
            jax.ShapeDtypeStruct((qp, kappa), jnp.int32),
            jax.ShapeDtypeStruct((nb, qp, 1), jnp.int32),
            jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        ),
        interpret=interpret,
    )
    # inside a ``lax.cond`` branch too, the kernel's custom call keeps the
    # program's name (``%_gam_retrieve.<n>``), which profile readers match
    with jax.named_scope("_gam_retrieve"):
        vals, rows, cnt, steps = kernel(skip.reshape(-1),
                                        *([word_idx] if compact else []), up,
                                        q_bits, fp, *extra, ibT, spill8, al8)
    return vals, rows, cnt, skip, steps


@partial(jax.jit, static_argnames=("kappa", "min_overlap", "bq", "bn",
                                   "words", "n_pad", "interpret",
                                   "loop_merge"))
def _gam_retrieve(users, factors, scales, q_tau, q_mask, alive, ibT, union,
                  bspill, spill8, *, kappa, min_overlap, bq, bn, words, n_pad,
                  interpret, loop_merge):
    """One fused launch.  ``scales`` is None on the f32 path; on the int8
    path ``factors`` is the quantized (n_pad, k) slab, ``scales`` its
    (1, n_blocks) per-block dequant scales, and ``kappa`` the rerank POOL
    width (the caller re-ranks the pool against exact f32 rows).

    Active-word compaction: the overlap loop and the block prepass run
    over the pattern words some query of the batch sets, not over all
    ``words``.  The launch counts the words the padded batch sets; when
    they are at most :data:`COMPACT_WORDS` (one ``lax.cond`` in this
    program, so no host sync and no program per batch) it lists them
    (SMEM), gathers their columns of the query bits and of ``union``, and
    zeroes the query columns of the list's unused slots.  The kernel then
    loops over the listed rows of each item block, which it streams in
    whole.  (Gathering ``ibT``'s rows in HBM instead costs more than it
    saves: a row is one sublane of every (8, 128) tile.)  A word no query
    sets adds ``popcount(0 & x) = 0`` to every overlap, and each set word
    is listed once, so every overlap, and with it the candidates, scores,
    counts and skip map, is the full loop's, bit for bit.  A batch that
    sets more words runs the full loop, and an index of at most
    :data:`COMPACT_WORDS` words compiles the full loop alone.

    Tiling for Mosaic: the skip map and the scales are whole 1-D SMEM
    arrays indexed by grid position (a (1, 1) block of a 2-D array breaks
    the (8, 128) block rule), and the per-block candidate counts come out
    as (n_blocks, Q, 1) so each grid step writes one (bq, 1) column block.
    The skip map takes 4 bytes per (query block, item block) tile;
    :func:`gam_retrieve` splits a batch so it stays within
    :data:`SKIP_MAP_TILES`.
    """
    q, k = users.shape
    bq = effective_bq(q, bq)
    qp = -(-q // bq) * bq

    q_bits = jnp.pad(_pack_patterns_jnp(q_tau, q_mask, words),
                     ((0, qp - q), (0, 0)))
    up = jnp.pad(users.astype(jnp.float32), ((0, qp - q), (0, 0)))
    al8 = jnp.pad(alive.astype(jnp.int8), (0, n_pad - alive.shape[0]))[None, :]
    if scales is not None:
        fp = factors
        extra = [scales.reshape(-1)]
        extra_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)]
    else:
        fp = factors.astype(jnp.float32)
        if fp.shape[0] != n_pad:      # a zero-width pad would still copy
            fp = jnp.pad(fp, ((0, n_pad - fp.shape[0]), (0, 0)))
        extra, extra_specs = [], []

    launch = partial(_launch, ibT=ibT, up=up, fp=fp, extra=extra,
                     extra_specs=extra_specs, bspill=bspill, spill8=spill8,
                     al8=al8, kappa=kappa, min_overlap=min_overlap, bq=bq,
                     bn=bn, interpret=interpret, loop_merge=loop_merge)
    active = jnp.any(q_bits != 0, axis=0)        # the words the batch sets
    n_active = jnp.sum(active, dtype=jnp.int32)

    def full():
        return launch(q_bits, None, union)

    def compacted():
        idx = jnp.nonzero(active, size=COMPACT_WORDS, fill_value=0)[0]
        used = jnp.arange(COMPACT_WORDS) < n_active
        return launch(jnp.where(used, q_bits[:, idx], jnp.uint32(0)),
                      idx, union[:, idx])

    if words <= COMPACT_WORDS:
        vals, rows, cnt, skip, steps = full()
        width = jnp.int32(words)
    else:
        fits = n_active <= COMPACT_WORDS
        vals, rows, cnt, skip, steps = jax.lax.cond(fits, compacted, full)
        width = jnp.where(fits, COMPACT_WORDS, words)

    vals = vals[:q]
    rows = jnp.where(vals <= NEG / 2, -1, rows[:q])
    return GamRetrieveResult(vals, rows, cnt[:, :q, 0].T, skip == 1,
                             jnp.full((qp // bq,), width, jnp.int32),
                             steps[::bq, 0])


def rerank_pool(pool_res: GamRetrieveResult, users, factors,
                kappa: int) -> GamRetrieveResult:
    """Exact f32 re-rank of a quantized-score candidate pool.

    For every query the pool's surviving rows are re-scored against the
    exact factor rows with the SAME host matvec the CPU oracle uses, then
    the top-``kappa`` are selected under the kernel's (score desc, row asc)
    total order — so whenever the pool covers the true top-``kappa`` (the
    ``rerank_factor`` sizing question), the answer is bit-identical to the
    dense oracle."""
    rows_p = np.asarray(pool_res.rows)
    vals_p = np.asarray(pool_res.vals, np.float32)
    # only the pool's rows leave the device, never the whole f32 slab
    pool_f = np.asarray(jnp.take(jnp.asarray(factors),
                                 jnp.asarray(np.maximum(rows_p, 0)), axis=0),
                        np.float32)
    un = np.asarray(users, np.float32)
    qn = un.shape[0]
    out_s = np.full((qn, kappa), NEG, np.float32)
    out_r = np.full((qn, kappa), -1, np.int32)
    empty_key = np.int64(TOPK_EMPTY_ROW)
    for qi in range(qn):
        valid = (rows_p[qi] >= 0) & (vals_p[qi] > NEG / 2)
        ex = np.full(rows_p.shape[1], NEG, np.float32)
        if valid.any():
            ex[valid] = pool_f[qi][valid] @ un[qi]
        key_rows = np.where(valid, rows_p[qi].astype(np.int64), empty_key)
        order = np.lexsort((key_rows, -ex))[:kappa]
        out_s[qi] = ex[order]
        out_r[qi] = np.where(key_rows[order] == empty_key, -1,
                             rows_p[qi][order])
    return GamRetrieveResult(jnp.asarray(out_s), jnp.asarray(out_r),
                             pool_res.blk_counts, pool_res.skipped,
                             pool_res.loop_words, pool_res.merge_steps)


def gam_retrieve(users: jax.Array, factors: jax.Array, q_tau: jax.Array,
                 q_mask: jax.Array, meta: RetrievalMeta, kappa: int, *,
                 min_overlap: int = 1, alive: jax.Array | None = None,
                 bq: int = 32, interpret: bool = False,
                 loop_merge: bool | None = None,
                 rerank_factor: int = 4,
                 rerank: bool = True) -> GamRetrieveResult:
    """Fused candidate-pruned top-kappa MIPS over ``meta.n_rows`` items.

    ``users``: (Q, k) f32 query factors; ``factors``: (n_rows, k) f32 item
    factors (structural pad rows zero); ``q_tau``/``q_mask``: (Q, k) mapped
    query patterns; ``alive``: optional (n_rows,) bool (dead rows are never
    candidates); ``min_overlap=0`` makes every alive row a candidate (the
    exact/brute-force path through the same kernel).  ``loop_merge`` forces
    the Mosaic selection-loop merge (defaults to the faster ``lax.top_k``
    merge under ``interpret``); both realise the identical total order.

    With ``meta.quantize == "int8"`` the kernel streams ``meta.factors_q``
    (decoded in-loop from per-block scales) and keeps a top-``kappa *
    rerank_factor`` pool, which is then re-ranked against the exact f32
    ``factors`` rows — ``factors`` becomes the exact re-rank store and is
    never shipped through the kernel launch.  With ``rerank=False`` the
    pool comes back as launched (quantized scores, ``kappa *
    rerank_factor`` wide), so a caller with several launches can queue
    them all before :func:`rerank_pool` waits on each.

    A batch whose skip map would pass :data:`SKIP_MAP_TILES` runs as
    several launches over consecutive query rows.

    Scores come from an f32 ``dot_general`` at ``Precision.HIGHEST`` (full
    f32 passes on the MXU, not one bf16 pass).  Invariant against the dense
    oracle: the same ids under (score desc, row asc); scores equal up to
    float summation order.
    """
    factors = jnp.asarray(factors)
    if factors.shape[0] != meta.n_rows:
        raise ValueError(
            f"factors rows {factors.shape[0]} != meta.n_rows {meta.n_rows}")
    if alive is None:
        alive = jnp.ones((meta.n_rows,), bool)
    if loop_merge is None:
        loop_merge = not interpret
    q = int(np.shape(users)[0])
    step = max(1, SKIP_MAP_TILES // meta.n_blocks) * effective_bq(q, bq)
    if q > step:
        parts = [gam_retrieve(
            users[i:i + step], factors, q_tau[i:i + step],
            q_mask[i:i + step], meta, kappa, min_overlap=min_overlap,
            alive=alive, bq=bq, interpret=interpret, loop_merge=loop_merge,
            rerank_factor=rerank_factor, rerank=rerank)
            for i in range(0, q, step)]
        return GamRetrieveResult(*(jnp.concatenate(xs) for xs in zip(*parts)))
    if meta.quantize == "int8":
        kappa = int(kappa)
        pool = max(kappa, min(kappa * max(1, int(rerank_factor)),
                              meta.n_pad))
        pool_res = _gam_retrieve(
            jnp.asarray(users), meta.factors_q, meta.scales,
            jnp.asarray(q_tau), jnp.asarray(q_mask, bool),
            jnp.asarray(alive), meta.item_bits_t, meta.block_union,
            meta.block_spill, meta.spill8,
            kappa=pool, min_overlap=int(min_overlap), bq=int(bq),
            bn=meta.bn, words=meta.words, n_pad=meta.n_pad,
            interpret=bool(interpret), loop_merge=bool(loop_merge))
        return (rerank_pool(pool_res, users, factors, kappa) if rerank
                else pool_res)
    return _gam_retrieve(
        jnp.asarray(users), factors, None, jnp.asarray(q_tau),
        jnp.asarray(q_mask, bool), jnp.asarray(alive), meta.item_bits_t,
        meta.block_union, meta.block_spill, meta.spill8,
        kappa=int(kappa), min_overlap=int(min_overlap), bq=int(bq),
        bn=meta.bn, words=meta.words, n_pad=meta.n_pad,
        interpret=bool(interpret), loop_merge=bool(loop_merge))
