"""The readers of the program's own spans against hand-worked and brute
arithmetic, on small synthetic traces: the harness's window, device
operations, and nested ``repro.*`` host events as the program emits them."""
import types

import numpy as np
import pytest

from chipbench import program_spans as ps
from chipbench import trace as tr

#: one batch before the window, two inside it, one after it (ns)
HOST = [
    ("repro.request_batch", 500, 2500), ("repro.query", 600, 2300),
    ("repro.device_wait", 1000, 1000),
    ("repro.request_batch", 3000, 4000), ("repro.flush", 3050, 3850),
    ("repro.cache_lookup", 3100, 200), ("repro.query", 3400, 3100),
    ("repro.map", 3400, 200), ("repro.base", 3600, 2600),
    ("repro.gam_retrieve", 3650, 50), ("repro.device_wait", 3700, 2200),
    ("repro.rerank", 5900, 200), ("repro.merge", 6200, 200),
    ("repro.cache_fill", 6500, 300),
    ("repro.request_batch", 7500, 3000), ("repro.query", 7600, 2400),
    ("repro.device_wait", 8000, 1500),
    ("repro.request_batch", 11500, 1000), ("repro.query", 11600, 400),
    ("repro.device_wait", 11700, 200), ("repro.rerank", 11900, 50),
]
OPS = [("k", 1500.0, 1000.0), ("k", 4000.0, 2000.0), ("k", 9000.0, 3000.0)]


def harness_trace(ops=OPS, lo=1000.0, hi=11000.0):
    return tr.Trace(ops={"/device:TPU:0": list(ops)}, modules={},
                    host=[("bench.window", lo, hi - lo),
                          ("bench.poll", 2000.0, 500.0)])


@pytest.fixture
def ctx(monkeypatch):
    """A run's context whose trace file holds ``HOST``."""
    monkeypatch.setattr(ps, "load", lambda path: [
        (n, float(s), float(d)) for n, s, d in HOST])
    monkeypatch.setattr(ps.tr, "find_xplane", lambda d: "run.xplane.pb")
    return types.SimpleNamespace(trace=harness_trace())


def test_query_host_is_query_less_its_device_waits(ctx):
    # in the window: queries of 3,100 and 2,400 ns holding waits of 2,200
    # and 1,500 ns
    assert ps.device_wait_ms(ctx) == pytest.approx((2200 + 1500) / 2 / 1e6)
    assert ps.query_host_ms(ctx) == pytest.approx(
        ((3100 - 2200) + (2400 - 1500)) / 2 / 1e6)
    assert ps.query_host_ms(ctx) + ps.device_wait_ms(ctx) == pytest.approx(
        (3100 + 2400) / 2 / 1e6)


def test_events_outside_the_window_are_dropped(ctx):
    # the batches starting at 500 ns and at 11,500 ns are not counted: the
    # re-rank at 11,900 ns would otherwise raise the sum, and the queries
    # of 2,300 and 400 ns would change the mean
    assert ps.rerank_ms(ctx) == pytest.approx(200 / 2 / 1e6)
    assert ps.cache_ms(ctx) == pytest.approx((200 + 300) / 2 / 1e6)
    assert ps.batch_host_ms(ctx) == pytest.approx(
        ((4000 - 3100) + (3000 - 2400)) / 2 / 1e6)
    spans = ps.of(ctx)
    assert spans.in_window("query") == [(3400.0, 6500.0), (7600.0, 10000.0)]


def test_idle_in_query_by_hand(ctx):
    # idle while in a query: 1000-1500, 2500-2900, 3400-4000, 6000-6500,
    # 7600-9000 of a 10,000 ns window
    assert ps.idle_in_query_pct(ctx) == pytest.approx(34.0)
    assert ps.idle_in_query_pct(ctx) <= (
        1 - tr.busy_s(ctx.trace) / tr.window_s(ctx.trace)) * 100


def timeline(intervals, lo, hi, step_ns=100.0):
    """Brute mask over the window at 100 ns resolution."""
    n = int(np.ceil((hi - lo) / step_ns))
    mask = np.zeros(n, bool)
    for s, e in intervals:
        a = int(np.floor((max(s, lo) - lo) / step_ns))
        b = int(np.ceil((min(e, hi) - lo) / step_ns))
        if b > a:
            mask[a:b] = True
    return mask


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_idle_in_query_equals_a_brute_timeline(seed):
    rng = np.random.default_rng(seed)
    lo, hi = 10_000.0, 210_000.0

    def intervals(n, longest):
        s = rng.integers(0, 2_300, n) * 100.0
        return [(a, a + rng.integers(1, longest) * 100.0) for a in s]

    chips = {f"/device:TPU:{c}": [("op", a, b - a)
                                  for a, b in intervals(40, 60)]
             for c in range(2)}
    queries = intervals(25, 90)
    trace = tr.Trace(ops=chips, modules={},
                     host=[("bench.window", lo, hi - lo)])
    spans = ps.ProgramSpans.of(
        trace, [("repro.query", a, b - a) for a, b in queries])
    q = timeline(queries, lo, hi)
    brute = np.mean([(q & ~timeline([(s, s + d) for _, s, d in evs],
                                    lo, hi)).mean()
                     for evs in chips.values()]) * 100
    assert spans.idle_in_query_pct() == pytest.approx(brute, rel=1e-9)


def test_a_program_without_annotations_leaves_every_reader_empty(
        monkeypatch):
    monkeypatch.setattr(ps, "load", lambda path: [])
    monkeypatch.setattr(ps.tr, "find_xplane", lambda d: "run.xplane.pb")
    ctx = types.SimpleNamespace(trace=harness_trace())
    for read in (ps.device_wait_ms, ps.query_host_ms, ps.rerank_ms,
                 ps.cache_ms, ps.batch_host_ms, ps.idle_in_query_pct):
        assert read(ctx) is None
    assert ps.device_wait_ms(types.SimpleNamespace(trace=None)) is None


def test_the_trace_file_is_read_once_per_run(monkeypatch):
    reads = []
    monkeypatch.setattr(ps, "load", lambda path: reads.append(path) or [])
    monkeypatch.setattr(ps.tr, "find_xplane", lambda d: "run.xplane.pb")
    ctx = types.SimpleNamespace(trace=harness_trace())
    for _ in range(3):
        ps.query_host_ms(ctx)
    assert len(reads) == 1
    ps.query_host_ms(types.SimpleNamespace(trace=harness_trace()))
    assert len(reads) == 2


def test_load_reads_the_annotations_of_a_cpu_profile(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("bench.window"):
        with TraceAnnotation("repro.query"):
            with TraceAnnotation("repro.device_wait"):
                jax.numpy.ones(8).block_until_ready()
    jax.profiler.stop_trace()
    events = ps.load(tr.find_xplane(tmp_path))
    names = [n for n, _, _ in events]
    assert sorted(names) == ["repro.device_wait", "repro.query"]
    (_, qs, qd), = [e for e in events if e[0] == "repro.query"]
    (_, ws, wd), = [e for e in events if e[0] == "repro.device_wait"]
    assert qs <= ws and ws + wd <= qs + qd
