"""The comparison that decides ``correct``.

Every answer the window produced is judged against the plain reference
(:mod:`chipbench.reference`), or a sample of them drawn from the seed.
For one request with served ids ``i_j`` and scores ``s_j`` (slot ``j``),
the reference computes the candidate set ``C`` and the exact float64
scores ``r(.)``, and its own answer ``r*_j``.  The request's gap is the
largest over its slots of

* ``|s_j - r(i_j)|``: how far the served score lies off the exact score of
  the id served, and
* ``r*_j - r(i_j)``: how far the served id lies below the reference's
  answer at that slot,

divided by ``|q| * max|x|`` (so it reads as an error in cosine units).
An id that is no candidate, a duplicate id, or an empty slot where a
candidate exists gives :data:`WRONG`.  ``answer_gap`` is the largest gap
of the sample.  A row whose tessellation is ambiguous in float32 (see
``reference.AMBIGUOUS_RTOL``) is a candidate if the program served it; a
query whose own pattern is ambiguous is left out of the sample.
"""
from __future__ import annotations

import numpy as np

from chipbench import reference as ref

#: the gap of an answer that holds a wrong id or misses one
WRONG = 1e9


class Reference:
    def __init__(self, items: np.ndarray, config: dict):
        self.items = np.asarray(items, np.float32)
        g = config["gam"]
        self.threshold = float(g["threshold"])
        self.min_overlap = int(g["min_overlap"])
        self.kappa = int(g["kappa"])
        rows, slots, amb = ref.map_catalog(self.items, self.threshold)
        self.index = ref.PostingIndex(rows, slots, self.items.shape[0])
        self.ambiguous = np.flatnonzero(amb)
        self.norm_max = float(np.linalg.norm(self.items, axis=1).max())

    def query_patterns(self, q: np.ndarray):
        rows, slots, amb = ref.gam_patterns(q, self.threshold)
        return ref.split_rows(rows, slots, q.shape[0]), amb

    def candidates(self, slots) -> np.ndarray:
        c = self.index.candidates(slots, self.min_overlap)
        return c[~np.isin(c, self.ambiguous)]

    def answer(self, q: np.ndarray, slots, precision: str = "f64"):
        """The reference's own top-kappa, its scores computed at
        ``precision`` (the control computes below the served precision)."""
        c = self.index.candidates(slots, self.min_overlap)
        s = ref.emulate_dot(q, self.items[c], precision)
        sc, ids = ref.topk(s, c, self.kappa)
        out_i = np.full(self.kappa, -1, np.int64)
        out_s = np.full(self.kappa, -np.inf, np.float32)
        out_i[:ids.size], out_s[:ids.size] = ids, sc
        return out_i, out_s

    def gap(self, q: np.ndarray, slots, ids: np.ndarray,
            scores: np.ndarray) -> float:
        ids = np.asarray(ids, np.int64)
        real = ids[ids >= 0]
        if np.unique(real).size != real.size:
            return WRONG
        c = np.union1d(self.candidates(slots),
                       real[np.isin(real, self.ambiguous)])
        s64 = ref.emulate_dot(q, self.items[c], "f64")
        r_s, _ = ref.topk(s64, c, self.kappa)
        scale = float(np.linalg.norm(q)) * self.norm_max
        worst = 0.0
        for j, i in enumerate(ids.tolist()):
            if i < 0:
                if j < r_s.size:
                    return WRONG
                continue
            k = np.searchsorted(c, i)
            if k == c.size or c[k] != i or j >= r_s.size:
                return WRONG
            true = s64[k]
            worst = max(worst, abs(float(scores[j]) - true), r_s[j] - true)
        return worst / scale if scale > 0 else worst


def sample(n: int, size: int, rng: np.random.Generator) -> np.ndarray:
    return np.sort(rng.choice(n, size=min(size, n), replace=False))


def judge(refc: Reference, queries: np.ndarray, ids: np.ndarray,
          scores: np.ndarray) -> tuple[float, int]:
    """-> (largest gap over the given answers, queries left out as
    ambiguous)."""
    slots, amb = refc.query_patterns(queries)
    worst, skipped = 0.0, 0
    for r in range(queries.shape[0]):
        if amb[r]:
            skipped += 1
            continue
        worst = max(worst, refc.gap(queries[r], slots[r], ids[r],
                                    scores[r]))
    return worst, skipped


def control(refc: Reference, queries: np.ndarray, precision: str):
    """The reference put in the program's place, at ``precision``."""
    slots, _ = refc.query_patterns(queries)
    out = [refc.answer(queries[r], slots[r], precision)
           for r in range(queries.shape[0])]
    return (np.stack([i for i, _ in out]), np.stack([s for _, s in out]))
