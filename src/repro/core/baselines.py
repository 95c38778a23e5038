"""Baselines the paper compares against (§5.1, §6).

* SRP-LSH      — sign-random-projection hashing [Charikar '02]; L boosted
                 tables (the paper's footnote 7: candidates are the union over
                 L independent hash instances).
* SuperBit-LSH — orthogonalised random projections [Ji et al. '12].
* CRO          — concomitant rank-order hashing [Eshghi & Rajaram '08]:
                 hash = indices of the top-l projections (an l-ary code).
* PCA-tree     — median splits along principal eigenvectors [Verma et al. '09].

All expose ``query(users, kappa) -> RetrievalResult`` like the ``gam``
backend, with candidate extraction by exact hash/leaf match (tree-based
lookup, per §5.1 — Hamming-ranking against every item would defeat the
purpose).
"""
from __future__ import annotations

from collections import defaultdict

import numpy as np

from repro.retriever.types import RetrievalResult

__all__ = ["SrpLsh", "SuperBitLsh", "CroHash", "PcaTree"]


def _score_candidates(items, users, cand_per_q, kappa):
    n, q = items.shape[0], users.shape[0]
    ids_out = np.full((q, kappa), -1, np.int64)
    sc_out = np.full((q, kappa), -np.inf, np.float32)
    n_scored = np.zeros(q, np.int64)
    for qi in range(q):
        cand = cand_per_q[qi]
        if cand.size == 0:
            continue
        scores = items[cand] @ users[qi]
        kk = min(kappa, cand.size)
        top = np.argpartition(-scores, kk - 1)[:kk]
        order = np.argsort(-scores[top])
        ids_out[qi, :kk] = cand[top[order]]
        sc_out[qi, :kk] = scores[top[order]]
        n_scored[qi] = cand.size
    return RetrievalResult(ids_out, sc_out, n_scored, 1.0 - n_scored / n)


class _HashRetriever:
    """Shared machinery: L hash tables, candidates = union of exact-bucket hits."""

    def __init__(self, items: np.ndarray, n_tables: int, seed: int):
        self.items = np.asarray(items, np.float32)
        self.rng = np.random.default_rng(seed)
        self.n_tables = n_tables
        self.tables: list[dict] = []
        for t in range(n_tables):
            codes = self._hash(self.items, t)
            buckets: dict = defaultdict(list)
            for i, c in enumerate(codes):
                buckets[c].append(i)
            self.tables.append({c: np.array(v, np.int64) for c, v in buckets.items()})

    def _hash(self, x: np.ndarray, t: int) -> list:
        raise NotImplementedError

    def query(self, users: np.ndarray, kappa: int) -> RetrievalResult:
        users = np.asarray(users, np.float32)
        cands = []
        for qi in range(users.shape[0]):
            hit: set = set()
            for t in range(self.n_tables):
                code = self._hash(users[qi : qi + 1], t)[0]
                hit.update(self.tables[t].get(code, ()))
            cands.append(np.fromiter(sorted(hit), np.int64, len(hit)))
        return _score_candidates(self.items, users, cands, kappa)


class SrpLsh(_HashRetriever):
    """Sign random projection: b random hyperplanes per table -> b-bit code."""

    def __init__(self, items, n_bits: int = 8, n_tables: int = 4, seed: int = 0):
        self.n_bits = n_bits
        k = items.shape[1]
        self._planes = np.random.default_rng(seed).normal(
            size=(n_tables, k, n_bits)
        ).astype(np.float32)
        super().__init__(items, n_tables, seed)

    def _hash(self, x, t):
        bits = (x @ self._planes[t]) >= 0
        return [tuple(row) for row in bits]


class SuperBitLsh(SrpLsh):
    """SRP with orthogonalised hyperplanes (QR per table)."""

    def __init__(self, items, n_bits: int = 8, n_tables: int = 4, seed: int = 0):
        super().__init__(items, n_bits, n_tables, seed)
        k = items.shape[1]
        rng = np.random.default_rng(seed + 1)
        planes = []
        for _ in range(n_tables):
            g = rng.normal(size=(k, max(n_bits, 1)))
            qmat, _ = np.linalg.qr(g)
            planes.append(qmat[:, :n_bits])
        self._planes = np.stack(planes).astype(np.float32)
        _HashRetriever.__init__(self, items, n_tables, seed)


class CroHash(_HashRetriever):
    """Concomitant rank-order statistics: hash = sorted indices of the top-l
    of m random Gaussian projections."""

    def __init__(self, items, n_proj: int = 16, top_l: int = 2, n_tables: int = 4,
                 seed: int = 0):
        self.n_proj, self.top_l = n_proj, top_l
        k = items.shape[1]
        self._proj = np.random.default_rng(seed).normal(
            size=(n_tables, k, n_proj)
        ).astype(np.float32)
        super().__init__(items, n_tables, seed)

    def _hash(self, x, t):
        z = x @ self._proj[t]
        top = np.argpartition(-z, self.top_l - 1, axis=1)[:, : self.top_l]
        return [tuple(sorted(row)) for row in top]


class PcaTree:
    """Recursive median splits along principal eigenvectors; candidates are the
    query's leaf."""

    def __init__(self, items: np.ndarray, depth: int = 4, seed: int = 0):
        self.items = np.asarray(items, np.float32)
        self.depth = depth
        self._leaves: dict[tuple, np.ndarray] = {}
        self._splits: dict[tuple, tuple[np.ndarray, float]] = {}
        self._build((), np.arange(self.items.shape[0], dtype=np.int64))

    def _build(self, path, ids):
        if len(path) == self.depth or ids.size <= 4:
            self._leaves[path] = ids
            return
        x = self.items[ids]
        xc = x - x.mean(0)
        # principal eigenvector via a few power iterations (cheap, deterministic)
        v = np.ones(x.shape[1], np.float32)
        cov = xc.T @ xc
        for _ in range(32):
            v = cov @ v
            v /= np.linalg.norm(v) + 1e-30
        proj = x @ v
        med = float(np.median(proj))
        self._splits[path] = (v, med)
        left = proj <= med
        self._build(path + (0,), ids[left])
        self._build(path + (1,), ids[~left])

    def _leaf(self, u: np.ndarray) -> np.ndarray:
        path: tuple = ()
        while path in self._splits:
            v, med = self._splits[path]
            path = path + (0 if float(u @ v) <= med else 1,)
        return self._leaves.get(path, np.empty(0, np.int64))

    def query(self, users: np.ndarray, kappa: int) -> RetrievalResult:
        users = np.asarray(users, np.float32)
        cands = [self._leaf(users[qi]) for qi in range(users.shape[0])]
        return _score_candidates(self.items, users, cands, kappa)
