"""Per-layer metrics from the program's own spans, on the profiler's clock.

The program mirrors every sampled span of its tracer
(``repro.obs.tracing``) as a ``jax.profiler.TraceAnnotation`` named
``repro.<span>``, nested as the spans nest::

    repro.request_batch > repro.flush > repro.cache_lookup, repro.query,
    repro.cache_fill;  repro.query > repro.map, repro.base, repro.delta,
    repro.merge;  repro.base / repro.delta > repro.gam_retrieve (the
    enqueue), repro.device_wait, repro.rerank

:func:`load` reads those host events from the traced run's
``.xplane.pb``, where ``run.py`` has the profiler write it, once per run.
Every reader keeps only the events that start inside the harness's
``bench.window`` span, so the warm-up batches never count, and "per batch"
means per ``repro.query`` event in the window.  A child is counted in the
parent whose interval holds it (one thread serves every batch).  Run on a
program without the annotations, every reader here finds nothing and
returns ``None``.
"""
from __future__ import annotations

import bisect
import dataclasses

import numpy as np

from chipbench import trace as tr
from chipbench.cell import ROOT

#: where run.py has the profiler write the traced run's trace
TRACE_DIR = ROOT / ".bench_out" / "trace"
#: prefix of the program's profiler annotations
PREFIX = "repro."


def load(path) -> list[tuple[str, float, float]]:
    """(name, start_ns, dur_ns) of every ``repro.*`` host event."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    return [(e.name, float(e.start_ns), float(e.duration_ns))
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(PREFIX)]


@dataclasses.dataclass
class ProgramSpans:
    trace: tr.Trace                     # the harness's reduction of the run
    events: dict                        # name -> sorted [(start_ns, end_ns)]

    @staticmethod
    def of(trace: tr.Trace, host) -> "ProgramSpans":
        events: dict = {}
        for name, s, d in host:
            events.setdefault(name[len(PREFIX):], []).append((s, s + d))
        return ProgramSpans(trace, {k: sorted(v) for k, v in events.items()})

    def in_window(self, name: str) -> list[tuple[float, float]]:
        lo, hi = tr.window(self.trace)
        return [(s, e) for s, e in self.events.get(name, ()) if lo <= s < hi]

    def nested_s(self, parent: str, child: str) -> np.ndarray:
        """Per ``parent`` event in the window, the seconds of the ``child``
        events inside it."""
        kids = self.events.get(child, [])
        starts = [s for s, _ in kids]
        out = []
        for ps, pe in self.in_window(parent):
            i = bisect.bisect_left(starts, ps)
            j = bisect.bisect_right(starts, pe)
            out.append(sum(e - s for s, e in kids[i:j] if e <= pe) / 1e9)
        return np.asarray(out, np.float64)

    def durations_s(self, name: str) -> np.ndarray:
        return np.asarray([e - s for s, e in self.in_window(name)]) / 1e9

    def per_batch_ms(self, parent: str, children) -> float | None:
        """Seconds of the ``children`` inside ``parent`` events of the
        window, per ``query`` event of the window, in ms; ``None`` when
        the window holds no query or none of the children."""
        n = len(self.in_window("query"))
        if not n or not any(self.in_window(c) for c in children):
            return None
        total = sum(float(self.nested_s(parent, c).sum()) for c in children)
        return total / n * 1e3

    def idle_in_query_pct(self) -> float | None:
        """Share of the window in which the host is inside a ``query`` and
        no device operation runs, averaged over the chips, in %."""
        lo, hi = tr.window(self.trace)
        if hi <= lo or not self.trace.ops:
            return None
        q = tr.union((max(s, lo), min(e, hi))
                     for s, e in self.events.get("query", ()) if e > lo
                     and s < hi)
        if not q:
            return None
        q_len = sum(b - a for a, b in q)
        idle = [q_len - overlap(q, tr.union(tr._clip(evs, lo, hi)))
                for evs in self.trace.ops.values()]
        return float(np.mean(idle)) / (hi - lo) * 100.0


def overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    tot = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            tot += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


def of(ctx) -> ProgramSpans | None:
    """The run's program spans, read from its trace file once per run and
    kept on the run's context."""
    if ctx.trace is None:
        return None
    spans = getattr(ctx, "program_spans", None)
    if spans is None:
        try:
            host = load(tr.find_xplane(TRACE_DIR))
        except FileNotFoundError:
            host = []
        spans = ctx.program_spans = ProgramSpans.of(ctx.trace, host)
    return spans


# ------------------------------------------------------------ the readers


def device_wait_ms(ctx):
    """Host time blocked on the device's result, per batch."""
    ps = of(ctx)
    return None if ps is None else ps.per_batch_ms("query", ["device_wait"])


def query_host_ms(ctx):
    """The ``query`` span less the device waits inside it, per batch."""
    ps = of(ctx)
    if ps is None or not ps.in_window("query"):
        return None
    return float(np.mean(ps.durations_s("query")
                         - ps.nested_s("query", "device_wait")) * 1e3)


def rerank_ms(ctx):
    """The int8 pool's exact re-rank on the host, per batch."""
    ps = of(ctx)
    return None if ps is None else ps.per_batch_ms("query", ["rerank"])


def cache_ms(ctx):
    """The result cache's lookup and fill around the query, per batch."""
    ps = of(ctx)
    return None if ps is None else ps.per_batch_ms(
        "request_batch", ["cache_lookup", "cache_fill"])


def batch_host_ms(ctx):
    """The microbatcher's ``request_batch`` less its ``query``, per
    batch."""
    ps = of(ctx)
    if ps is None or not ps.in_window("query") \
            or not ps.in_window("request_batch"):
        return None
    n = len(ps.in_window("query"))
    rb = ps.durations_s("request_batch").sum()
    return float((rb - ps.nested_s("request_batch", "query").sum())
                 / n * 1e3)


def idle_in_query_pct(ctx):
    ps = of(ctx)
    return None if ps is None else ps.idle_in_query_pct()
