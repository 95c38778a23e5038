"""From the first per-chip launch's start to the last one's end (the
program's launch spans inside its query span), mean per batch in the
window, ms (cells on several chips)."""
import bisect

import numpy as np

from chipbench.program_spans import of


def read(ctx):
    ps = of(ctx)
    if ps is None:
        return None
    launches = ps.events.get("launch", [])
    starts = [s for s, _ in launches]
    spans = []
    for qs, qe in ps.in_window("query"):
        inside = [(s, e) for s, e in launches[
            bisect.bisect_left(starts, qs):bisect.bisect_right(starts, qe)]
            if e <= qe]
        if inside:
            spans.append(max(e for _, e in inside)
                         - min(s for s, _ in inside))
    return float(np.mean(spans)) / 1e6 if spans else None
