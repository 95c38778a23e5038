"""Reduction of a profiler trace to device metrics.

Stage one, :func:`load`, reads the ``.xplane.pb`` the JAX profiler wrote
and keeps three lists of ``(name, start_ns, dur_ns)`` events: the device's
operations (the ``XLA Ops`` line of each TPU plane), its programs (the
``XLA Modules`` line) and the host spans the harness opened with
``jax.profiler.TraceAnnotation`` (names starting with ``bench.``).  Stage
two is plain arithmetic on those lists, and is what the tests check on a
small recorded trace:

* busy time: the union of the operations' intervals inside the window,
  averaged over the chips;
* idle gaps: the complement of the busy union inside the window, each
  named by the harness span that covers most of it, summed by name and
  averaged over the chips;
* per-operation device time, summed by name and averaged over the chips;
* the runs of a program named by prefix, or of the operations a
  predicate picks, on each chip: a cell on several chips runs one launch
  per chip for each batch, and the readers pair them up by their order.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import re
from pathlib import Path

#: harness span that covers the measured window
WINDOW = "bench.window"
#: name of idle time during which the harness was in none of its calls
NO_CALL = "harness between calls (no request due)"
#: per chip, [(start_ns, dur_ns)] in time order
Runs = list[list[tuple[float, float]]]


@dataclasses.dataclass
class Trace:
    ops: dict          # device plane -> [(name, start_ns, dur_ns)]
    modules: dict      # device plane -> [(name, start_ns, dur_ns)]
    host: list         # [(name, start_ns, dur_ns)] harness spans

    def to_json(self) -> dict:
        return {"ops": self.ops, "modules": self.modules, "host": self.host}

    @staticmethod
    def from_json(d: dict) -> "Trace":
        return Trace({k: [tuple(e) for e in v] for k, v in d["ops"].items()},
                     {k: [tuple(e) for e in v]
                      for k, v in d["modules"].items()},
                     [tuple(e) for e in d["host"]])


def find_xplane(log_dir: str | Path) -> Path:
    found = sorted(glob.glob(str(Path(log_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return Path(found[-1])


def load(path: str | Path) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    ops, modules, host = {}, {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
                if dest is not None:
                    dest.setdefault(plane.name, []).extend(
                        (e.name, float(e.start_ns), float(e.duration_ns))
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events
                            if e.name.startswith("bench."))
    return Trace(ops, modules, host)


def window(tr: Trace) -> tuple[float, float]:
    spans = [(s, s + d) for n, s, d in tr.host if n == WINDOW]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    return max(spans, key=lambda w: w[1] - w[0])


def _clip(events, lo: float, hi: float) -> list[tuple[float, float]]:
    out = []
    for _, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((a, b))
    return out


def union(intervals) -> list[tuple[float, float]]:
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return [(a, b) for a, b in merged]


def busy_s(tr: Trace) -> float:
    """Seconds in which an operation ran, averaged over the chips."""
    lo, hi = window(tr)
    per_chip = [sum(b - a for a, b in union(_clip(evs, lo, hi)))
                for evs in tr.ops.values()]
    return sum(per_chip) / len(per_chip) / 1e9 if per_chip else 0.0


def window_s(tr: Trace) -> float:
    lo, hi = window(tr)
    return (hi - lo) / 1e9


def short_name(name: str) -> str:
    """An operation's HLO text -> its name and result type, without
    layouts: ``%copy.11 f32[292864,64]``."""
    m = _HLO.match(name)
    return f"{m.group(1)} {m.group(2)}" if m else name[:80]


_HLO = re.compile(r"^(%\S+) = \(?([a-z0-9]+\[[0-9,]*\])")


def op_seconds(tr: Trace) -> list[tuple[str, float]]:
    """Device time per operation inside the window, by short name, largest
    first, averaged over the chips."""
    lo, hi = window(tr)
    tot: dict[str, float] = {}
    for evs in tr.ops.values():
        for name, s, d in evs:
            a, b = max(s, lo), min(s + d, hi)
            if b > a:
                k = short_name(name)
                tot[k] = tot.get(k, 0.0) + (b - a) / 1e9
    n = max(len(tr.ops), 1)
    return sorted(((k, v / n) for k, v in tot.items()),
                  key=lambda kv: -kv[1])


def idle_gaps(tr: Trace) -> list[tuple[str, float]]:
    """Idle device seconds inside the window, by the harness span that
    covers most of each gap, largest first, averaged over the chips."""
    lo, hi = window(tr)
    if not tr.ops:
        return []
    calls = sorted((s, s + d, n) for n, s, d in tr.host if n != WINDOW)
    starts = [c[0] for c in calls]
    tot: dict[str, float] = {}
    for evs in tr.ops.values():
        busy = union(_clip(evs, lo, hi))
        gaps, t = [], lo
        for a, b in busy:
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        for a, b in gaps:
            best, best_ov = NO_CALL, 0.0
            i = bisect.bisect_right(starts, b)
            for s, e, n in reversed(calls[max(0, i - 64):i]):
                ov = min(b, e) - max(a, s)
                if ov > best_ov:
                    best, best_ov = n, ov
            tot[best] = tot.get(best, 0.0) + (b - a) / 1e9
    n = len(tr.ops)
    return sorted(((k, v / n) for k, v in tot.items()),
                  key=lambda kv: -kv[1])


def _runs(tr: Trace, events: dict, keep) -> Runs:
    lo, hi = window(tr)
    return [sorted((s, d) for n, s, d in evs
                   if keep(n) and s >= lo and s + d <= hi)
            for evs in events.values()]


def program_events(tr: Trace, prefix: str) -> Runs:
    """Per chip: (start_ns, dur_ns) of every run of the programs whose
    name starts with ``prefix``, inside the window, in time order."""
    return _runs(tr, tr.modules, lambda n: n.startswith(prefix))


def op_events(tr: Trace, match) -> Runs:
    """Per chip: (start_ns, dur_ns) of the operations whose name
    satisfies ``match``, inside the window, in time order."""
    return _runs(tr, tr.ops, match)


def save(tr: Trace, path: str | Path) -> None:
    Path(path).write_text(json.dumps(tr.to_json()))
