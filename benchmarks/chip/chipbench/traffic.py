"""The one traffic generator: a traffic file's parameters -> a schedule.

A traffic file (``benchmarks/chip/traffic/<name>.json``) holds:

* ``loop``: ``"open"``, requests due on a schedule whether or not earlier
  ones have finished (the only loop the window drives);
* ``rate_qps``: the arrival rate;
* ``queries``: ``{"kind": "fresh"}``, a new vector per request, or
  ``{"kind": "zipf", "pool": n, "s": s}``, identities drawn with
  probability proportional to ``rank ** -s`` from a pool of ``n`` vectors
  (the Zipf weights of the program's ``service/loadgen.py``, copied so the
  yardstick cannot move with it).

A schedule has exactly ``round(rate_qps * seconds)`` arrivals, placed as
a Poisson process conditioned on that count: sorted uniform times over the
window.  So every seed offers the same amount of work, in another order.
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class Schedule:
    n: int                      # requests the schedule holds
    due: np.ndarray             # (n,) seconds from the window's start
    qidx: np.ndarray            # (n,) row of the query matrix per request
    n_rows: int                 # query rows the schedule needs


def zipf_weights(n: int, s: float) -> np.ndarray:
    """Normalised Zipf pmf over ranks 1..n: p(r) ~ r^-s (s=0: uniform)."""
    w = np.arange(1, n + 1, dtype=np.float64) ** -float(s)
    return w / w.sum()


def plan(traffic: dict, seconds: float, rng: np.random.Generator
         ) -> Schedule:
    if traffic["loop"] != "open":
        raise ValueError(f"unknown loop {traffic['loop']!r}; known: open")
    n = int(round(float(traffic["rate_qps"]) * seconds))
    due = np.sort(rng.uniform(0.0, seconds, size=n))
    q = traffic["queries"]
    if q["kind"] == "fresh":
        qidx, n_rows = np.arange(n), n
    elif q["kind"] == "zipf":
        n_rows = int(q["pool"])
        qidx = rng.choice(n_rows, size=n, p=zipf_weights(n_rows, q["s"]))
    else:
        raise ValueError(f"unknown query kind {q['kind']!r}")
    return Schedule(n, due, np.asarray(qidx, np.int64), n_rows)
