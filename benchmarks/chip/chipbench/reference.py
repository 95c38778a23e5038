"""Plain reference of the served retrieval semantics, independent of the
program: numpy only, nothing imported from ``repro``.

The semantics (the paper's geometry-aware mapping, Algorithm 2 with the
parse-tree counter map of supplement B.2):

* threshold: coordinates with ``|z| < threshold`` (compared in float32, as
  the served path compares) are zeroed and leave the sparsity pattern;
* ternary pattern: sort ``|z|`` descending, support = the top ``t*`` where
  ``t*`` maximises ``cumsum / sqrt(t)``; sign of ``z`` on the support;
* parse-tree destination of coordinate ``j`` (0-based, ``jj = j + 1``):
  ``k*jj`` for ``+1``, ``k*(k + jj)`` for ``-1``, and the previous
  destination plus one for ``0`` (the first destination before any
  non-zero is ``jj``);
* the pattern of a row is the set of destinations of its non-zero
  coordinates; an item is a candidate for a query when the two patterns
  share at least ``min_overlap`` destinations;
* the answer is the ``kappa`` best candidates by inner product, ties broken
  by the lower catalog id.

Everything is computed in float64.  A row whose ``t*`` is within
:data:`AMBIGUOUS_RTOL` of another ``t`` is *ambiguous*: float32 rounding on
the device may pick the other support, so the comparison accepts either
candidacy for it.
"""
from __future__ import annotations

import numpy as np

#: relative margin of the tessellation argmax under which a row's pattern
#: may legitimately differ between float32 (served) and float64 (here)
AMBIGUOUS_RTOL = 1e-5


def gam_patterns(z: np.ndarray, threshold: float):
    """(n, k) factors -> the pattern of every row, as flat ``(rows, slots)``
    arrays (row-major, one entry per coordinate past the threshold), and
    ambiguity flags (n,) bool.

    Only coordinates past the threshold carry a destination, and the
    support of the ternary pattern lies among them, so the work is over
    those entries alone."""
    z = np.asarray(z, np.float32)
    n, k = z.shape
    thr = np.float32(threshold)
    keep = np.abs(z) >= thr if threshold else np.ones(z.shape, bool)
    rows, cols = np.nonzero(keep)
    vals = z[rows, cols].astype(np.float64)
    m = rows.size
    if m == 0:
        return rows.astype(np.int64), cols.astype(np.int64), np.zeros(n, bool)
    counts = np.bincount(rows, minlength=n)
    starts = np.zeros(n + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    # rank of each entry within its row by |z| descending (stable by column)
    by_mag = np.lexsort((cols, -np.abs(vals), rows))
    rank = np.empty(m, np.int64)
    rank[by_mag] = np.arange(m) - starts[rows[by_mag]]
    mag = np.abs(vals[by_mag])
    cs = np.cumsum(mag)
    row_base = np.concatenate([[0.0], cs])[starts[rows[by_mag]]]
    zs = (cs - row_base) / np.sqrt(rank[by_mag] + 1.0)
    nz_rows = np.flatnonzero(counts)
    seg = starts[nz_rows]
    best = np.maximum.reduceat(zs, seg)
    best_of = np.zeros(n)
    best_of[nz_rows] = best
    srt_rows = rows[by_mag]
    is_best = zs == best_of[srt_rows]
    # t*: the first (lowest-rank) position reaching the maximum
    pos = np.where(is_best, rank[by_mag], k)
    t_star = np.full(n, -1, np.int64)
    t_star[nz_rows] = np.minimum.reduceat(pos, seg)
    others = np.where(rank[by_mag] == t_star[srt_rows], -np.inf, zs)
    second = np.full(n, -np.inf)
    second[nz_rows] = np.maximum.reduceat(others, seg)
    amb = (best_of - second) <= AMBIGUOUS_RTOL * best_of
    amb &= counts > 1
    # destinations, in column order within each row
    support = rank <= t_star[rows]
    flat = np.arange(m)
    last = np.maximum.accumulate(np.where(support, flat, -1))
    same_row = (last >= 0) & (rows[np.maximum(last, 0)] == rows)
    last_col = np.where(same_row, cols[np.maximum(last, 0)], -1)
    last_sign = np.where(same_row, np.sign(vals[np.maximum(last, 0)]), 0)
    base = np.where(last_sign > 0, k * (last_col + 1),
                    k * (k + last_col + 1))
    slots = np.where(last_col < 0, cols + 1, base + (cols - last_col))
    return rows.astype(np.int64), slots.astype(np.int64), amb


class PostingIndex:
    """Destination -> sorted item rows (the paper's inverted index)."""

    def __init__(self, rows: np.ndarray, slots: np.ndarray, n: int):
        order = np.lexsort((rows, slots))
        self.slots = np.asarray(slots)[order]
        self.rows = np.asarray(rows, np.int64)[order]
        self.n = int(n)
        uniq, start = np.unique(self.slots, return_index=True)
        ends = np.append(start[1:], self.slots.size)
        self._span = dict(zip(uniq.tolist(), zip(start.tolist(),
                                                  ends.tolist())))

    def candidates(self, q_slots, min_overlap: int) -> np.ndarray:
        """Sorted rows sharing at least ``min_overlap`` destinations with
        a query whose pattern is ``q_slots``."""
        parts = [self.rows[a:b] for a, b in
                 (self._span[s] for s in np.asarray(q_slots).tolist()
                  if s in self._span)]
        if not parts:
            return np.zeros(0, np.int64)
        rows, counts = np.unique(np.concatenate(parts), return_counts=True)
        return rows[counts >= min_overlap]


def split_rows(rows: np.ndarray, slots: np.ndarray, n: int) -> list:
    """Flat ``(rows, slots)`` -> one array of slots per row."""
    bounds = np.searchsorted(rows, np.arange(n + 1))
    return [slots[bounds[i]:bounds[i + 1]] for i in range(n)]


def longest_posting_per_shard(rows: np.ndarray, slots: np.ndarray, n: int,
                              n_shards: int) -> int:
    """Longest posting list of any shard under the even contiguous cut of
    the id-sorted catalog: the bucket width below which a shard spills."""
    cap = -(-n // n_shards)
    shard = rows // cap
    key = shard * (int(slots.max(initial=0)) + 1) + slots
    return int(np.bincount(key).max(initial=0)) if key.size else 0


def topk(scores: np.ndarray, rows: np.ndarray, kappa: int):
    """Best ``kappa`` of (scores, rows) by score desc, row asc."""
    order = np.lexsort((rows, -scores))[:kappa]
    return scores[order], rows[order]


def emulate_dot(q: np.ndarray, x: np.ndarray, precision: str) -> np.ndarray:
    """Inner products of rows ``x`` with ``q`` computed as a lower
    precision would: ``"f64"`` (the reference), ``"bf16x3"`` (three bf16
    passes, what a TPU computes for float32 at ``high``) or ``"bf16"``
    (operands rounded to bf16, float32 sums)."""
    if precision == "f64":
        return np.asarray(x, np.float64) @ np.asarray(q, np.float64)
    if precision == "bf16":
        return (_bf16(x).astype(np.float32)
                @ _bf16(q).astype(np.float32)).astype(np.float64)
    if precision == "bf16x3":
        xh, qh = _bf16(x), _bf16(q)
        xl = _bf16(np.asarray(x, np.float32) - xh)
        ql = _bf16(np.asarray(q, np.float32) - qh)
        s = xh @ qh + xh @ ql + xl @ qh
        return s.astype(np.float32).astype(np.float64)
    raise ValueError(f"unknown precision {precision!r}")


def _bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), as float32."""
    b = np.ascontiguousarray(a, np.float32).view(np.uint32)
    rounded = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return rounded.view(np.float32)


def map_catalog(z: np.ndarray, threshold: float, *, threads: int = 8,
                chunk: int = 1 << 16):
    """:func:`gam_patterns` over row chunks on a few threads (numpy's sorts
    release the interpreter lock), rows offset back to catalog rows."""
    from concurrent.futures import ThreadPoolExecutor

    starts = list(range(0, z.shape[0], chunk))
    with ThreadPoolExecutor(threads) as ex:
        parts = list(ex.map(lambda lo: gam_patterns(z[lo:lo + chunk],
                                                    threshold), starts))
    rows = np.concatenate([r + lo for lo, (r, _, _) in zip(starts, parts)])
    slots = np.concatenate([s for _, s, _ in parts])
    amb = np.concatenate([a for _, _, a in parts])
    return rows, slots, amb


def batch_work(index: PostingIndex, slots_per_query, min_overlap: int, *,
               dim: int, row_bytes: int, pool_rows: int):
    """The work the retrieval semantics require of one batch, however it
    is implemented: ``(bytes, flops)``.

    Bytes: every row that is a candidate for some query of the batch, read
    once at the width it is stored in plus a 4-byte id, and for a
    quantized catalog the float32 rows of each query's re-rank pool.
    Flops: ``2 * dim`` per (query, candidate) pair."""
    cands = [index.candidates(s, min_overlap) for s in slots_per_query]
    union = np.unique(np.concatenate(cands)) if cands else np.zeros(0)
    n_pairs = sum(c.size for c in cands)
    nbytes = union.size * (row_bytes + 4) \
        + len(cands) * pool_rows * dim * 4
    return float(nbytes), float(2 * dim * n_pairs)
