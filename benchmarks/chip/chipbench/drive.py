"""The measured window: requests into the service's microbatcher, as a
client of ``launch/serve.py --service`` would send them, and what came
back.

The microbatcher is synchronous: ``submit`` queues one row and, at the
batch size, runs the batch before it returns; ``poll`` runs the batches
whose oldest row has waited past the deadline.  A flush serves the queue
in arrival order (one priority class), so after each call the harness
knows which of its queued requests were answered: the oldest ones, as
many as the queue shrank by.  Their answer time is the end of that call.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

from chipbench.traffic import Schedule

clock = time.perf_counter


@dataclasses.dataclass
class Record:
    """Per request: times in seconds from the window's start."""
    due: np.ndarray          # when it was due
    submit: np.ndarray       # when the harness handed it to submit()
    done: np.ndarray         # when its answer was out (nan: none)
    queue_wait: np.ndarray   # the program's own queue-wait reading
    ids: np.ndarray          # (n, kappa) catalog ids, -1 pads
    scores: np.ndarray       # (n, kappa)
    answered: np.ndarray     # bool
    issued: int = 0          # requests handed to the service
    window_s: float = 0.0
    in_window: int = 0       # batches answered before the window closed
    batches: list = dataclasses.field(default_factory=list)
    tiles_skipped: list = dataclasses.field(default_factory=list)

    @staticmethod
    def empty(n: int, kappa: int) -> "Record":
        nan = np.full(n, np.nan)
        return Record(nan.copy(), nan.copy(), nan.copy(), nan.copy(),
                      np.full((n, kappa), -1, np.int64),
                      np.full((n, kappa), -np.inf, np.float32),
                      np.zeros(n, bool))


class _Tracker:
    """Which queued requests each batcher call answered."""

    def __init__(self, svc, rec: Record, t0: float, traced: bool,
                 result_type):
        self.b = svc.batcher
        self.svc = svc
        self.rec = rec
        self.t0 = t0
        self.traced = traced
        self.result_type = result_type
        self.queued: list[int] = []
        self.rid: dict[int, int] = {}

    def after(self, i: int | None) -> None:
        """Account for one call; ``i`` is the request it submitted."""
        pend = self.b.pending
        if i is not None:
            if pend == len(self.queued):         # answered at submit (cache)
                self._complete([i])
                return
            self.queued.append(i)
        n_done = len(self.queued) - pend
        if n_done <= 0:
            return
        done, self.queued = self.queued[:n_done], self.queued[n_done:]
        bs = self.b.batch_size
        for k in range(0, len(done), bs):
            self.rec.batches.append(np.asarray(done[k:k + bs], np.int64))
        if self.traced:
            st = self.svc._last_query_stats
            if "tiles_skipped_frac" in st:
                self.rec.tiles_skipped.append(float(st["tiles_skipped_frac"]))
        self._complete(done)

    def _complete(self, reqs) -> None:
        t = clock() - self.t0
        for j in reqs:
            out = self.b.result(self.rid.pop(j))
            if isinstance(out, self.result_type):
                self.rec.done[j] = t
                self.rec.queue_wait[j] = out.queue_wait_s
                self.rec.ids[j] = out.ids
                self.rec.scores[j] = out.scores
                self.rec.answered[j] = True


def run_window(svc, queries: np.ndarray, sched: Schedule, seconds: float,
               *, kappa: int, traced: bool, result_type) -> Record:
    """Drive one window of ``seconds`` and drain what it left queued."""
    import jax

    rec = Record.empty(sched.n, kappa)
    ann = (jax.profiler.TraceAnnotation if traced
           else lambda name: contextlib.nullcontext())
    b = svc.batcher
    delay = b.max_delay_s
    t0 = clock()
    tr = _Tracker(svc, rec, t0, traced, result_type)
    i = 0
    n = sched.n
    with ann("bench.window"):
        while True:
            now = clock() - t0
            if now >= seconds:
                break
            if i < n and sched.due[i] <= now:
                _submit(b, tr, rec, queries, sched, i, t0, ann)
                i += 1
                continue
            # the deadline trigger, called when the oldest queued row is due
            # to fire (the batcher re-checks on its own clock)
            if tr.queued and now - rec.submit[tr.queued[0]] >= delay:
                with ann("bench.poll"):
                    b.poll()
                tr.after(None)
    rec.window_s = clock() - t0
    rec.in_window = len(rec.batches)
    # requests due inside the window but not yet sent go now, late
    while i < n and sched.due[i] < seconds:
        _submit(b, tr, rec, queries, sched, i, t0, ann)
        i += 1
    with ann("bench.drain"):
        while b.pending:
            b.flush()
    tr.after(None)
    rec.issued = i
    return rec


def _submit(b, tr, rec, queries, sched, i, t0, ann) -> None:
    rec.due[i] = sched.due[i]
    rec.submit[i] = clock() - t0
    with ann("bench.submit"):
        tr.rid[i] = b.submit(queries[sched.qidx[i]])
    tr.after(i)
