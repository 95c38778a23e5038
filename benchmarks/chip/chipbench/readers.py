"""What each per-layer metric reads.  Every file under
``benchmarks/chip/metrics/`` names one of these as its ``read``; a reader
returns ``None`` when the run holds nothing for it to read, and the metric
is then left out of the result line.

``ctx`` is the run's :class:`chipbench.context.Context`.
"""
from __future__ import annotations

import numpy as np

from chipbench import trace as tr

#: the program that serves one batch's base launch (``jit`` of the
#: program's ``_gam_retrieve``)
RETRIEVE_PROGRAM = "jit__gam_retrieve"


def _p50_ms(x) -> float | None:
    x = np.asarray(x, np.float64)
    x = x[np.isfinite(x)]
    return float(np.median(x) * 1e3) if x.size else None


def gen_lag_p50_ms(ctx):
    """How late the load generator handed requests to the service."""
    r = ctx.record
    return _p50_ms((r.submit - r.due)[:r.issued])


def queue_wait_p50_ms(ctx):
    """The microbatcher's own queue-wait reading per answered request."""
    r = ctx.record
    return _p50_ms(r.queue_wait[:r.issued][r.answered[:r.issued]])


def query_ms(ctx):
    """Mean host time of the retriever's ``query`` span per batch."""
    d = [s.duration_s for root in ctx.spans for s in root.find("query")
         if s.duration_s is not None]
    return float(np.mean(d) * 1e3) if d else None


def tiles_skipped_pct(ctx):
    """Share of (query block, item block) tiles the block-union prepass
    skipped, per batch, averaged."""
    t = ctx.record.tiles_skipped
    return float(np.mean(t) * 100.0) if t else None


def slowest_per_batch(ctx, runs, n: int | None = None):
    """Each batch's device time on its slowest chip, ns.  ``runs`` are one
    list per chip (``trace.program_events``, ``trace.op_events``); on a
    cell of several chips each batch launches once on every chip, and the
    k-th run of each chip served the k-th batch.  ``None`` unless as many
    chips as the cell's ran, each as often (``n`` times, where given)."""
    runs = [r for r in runs if r]
    counts = {len(r) for r in runs}
    if len(runs) != ctx.chips or len(counts) != 1 \
            or (n is not None and counts != {n}):
        return None
    return np.max([[d for _, d in r] for r in runs], axis=0)


def retrieve_device_ms(ctx):
    """Device time per run of the retrieval program, on the slowest chip
    of each batch."""
    if ctx.trace is None:
        return None
    d = slowest_per_batch(
        ctx, tr.program_events(ctx.trace, RETRIEVE_PROGRAM))
    return None if d is None else float(np.mean(d) / 1e6)


def device_idle_pct(ctx):
    if ctx.trace is None:
        return None
    w = tr.window_s(ctx.trace)
    return (1.0 - tr.busy_s(ctx.trace) / w) * 100.0 if w > 0 else None


def is_kernel_op(name: str) -> bool:
    """The Mosaic kernel's operation inside the retrieval program: the
    ``tpu_custom_call`` the trace names after the jitted ``_gam_retrieve``
    (``%_gam_retrieve.1 = (...) custom-call(...)``)."""
    return name.startswith("%_gam_retrieve") and "tpu_custom_call" in name


def gam_retrieve_roofline(ctx):
    """Least time the cell's chips could take for the semantic work of a
    sample of batches, over the kernel's device time for the same batches,
    in %.  The work is the whole batch's, spread over ``chips`` times one
    chip's peaks; a batch's kernel time is its slowest chip's.

    The k-th kernel run of each chip inside the window served the k-th
    batch answered inside it; when a chip's count differs the reading is
    left out."""
    if ctx.trace is None:
        return None
    n = ctx.record.in_window
    slowest = slowest_per_batch(ctx, tr.op_events(ctx.trace, is_kernel_op),
                                n)
    if slowest is None:
        return None
    hbm = ctx.chips * ctx.peaks["hbm_bytes_per_s"]
    ops = ctx.chips * ctx.flops_peak
    pick = ctx.sample_batches(n)
    t_min, t_kernel = 0.0, 0.0
    for k in pick:
        nbytes, flops = ctx.batch_work(ctx.record.batches[k])
        t_min += max(nbytes / hbm, flops / ops)
        t_kernel += slowest[k] / 1e9
    return t_min / t_kernel * 100.0 if t_kernel > 0 else None
