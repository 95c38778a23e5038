"""Observability layer: streaming histograms, tracing, events, exporters
(tests for src/repro/obs/ and the ServiceMetrics rebuild on top of it)."""
import glob
import json
import math

import jax
import numpy as np
import pytest
from conftest import CFG, unit_factors

from repro.obs import (
    NOOP_SPAN,
    NOOP_TRACER,
    EventJournal,
    JsonlMetricsWriter,
    LogHistogram,
    Tracer,
    histogram_to_prometheus,
    snapshot_to_prometheus,
)
from repro.retriever import RetrieverSpec, open_retriever
from repro.service import ServiceMetrics


def _manual_clock(start=100.0):
    t = [start]
    return t, lambda: t[0]


# ----------------------------------------------------------- LogHistogram


def test_histogram_quantile_tracks_np_percentile():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=-6.0, sigma=1.5, size=4000)   # latency-ish
    h = LogHistogram.latency()
    h.record_many(vals)
    bound = math.sqrt(h.bucket_ratio) - 1.0
    for p in (1, 10, 25, 50, 75, 90, 99, 99.9):
        exact = np.percentile(vals, p, method="lower")
        approx = h.percentile(p)
        assert abs(approx - exact) / exact <= bound + 1e-12, (p, approx, exact)


def test_histogram_underflow_overflow_and_mean():
    h = LogHistogram(lo=1e-3, hi=1.0, bins=16)
    h.record_many([0.0, -0.5, 1e-4, 0.01, 5.0, 700.0])
    assert h.n == 6
    assert h.counts[0] == 3                     # <= lo underflow slot
    assert h.counts[-1] == 2                    # > hi overflow slot
    # the mean is exact (running sum), untouched by bucketing
    np.testing.assert_allclose(h.mean, np.mean([0.0, -0.5, 1e-4, 0.01,
                                                5.0, 700.0]))
    # edge-bucket representatives stay inside the observed range
    assert h.quantile(0.0) == -0.5
    assert h.quantile(1.0) == 700.0


def test_histogram_bucket_edges_land_in_range():
    """Exact bucket edges: ``[lo, hi]`` is in-range by contract.

    Regression: ``searchsorted(side="left")`` puts ``v == lo`` at index 0,
    so exact-lo recordings silently fell into the underflow slot (and out
    of the quantile error bound) until record_many lifted them into the
    first bucket."""
    h = LogHistogram(lo=1e-3, hi=1.0, bins=16)
    h.record_many(h.edges)                  # every edge, lo and hi included
    assert h.counts[0] == 0                 # lo is NOT underflow
    assert h.counts[-1] == 0                # hi is NOT overflow
    # edges are upper-inclusive: edges[i] -> bucket i, plus lo -> bucket 1
    expected = np.ones(h.bins, np.int64)
    expected[0] = 2
    np.testing.assert_array_equal(h.counts[1:-1], expected)
    # one ulp outside the range still lands in the out-of-range slots
    h2 = LogHistogram(lo=1e-3, hi=1.0, bins=16)
    h2.record_many([np.nextafter(1e-3, 0.0), np.nextafter(1.0, 2.0)])
    assert h2.counts[0] == 1 and h2.counts[-1] == 1
    assert h2.counts[1:-1].sum() == 0
    # single-shot record() goes through the same path
    h3 = LogHistogram(lo=1e-3, hi=1.0, bins=16)
    h3.record(1e-3)
    assert h3.counts[0] == 0 and h3.counts[1] == 1


def test_histogram_empty_and_single():
    h = LogHistogram.fraction()
    assert h.n == 0 and h.mean is None and h.quantile(0.5) is None
    h.record(0.25)
    assert h.n == 1
    np.testing.assert_allclose(h.quantile(0.5), 0.25,
                               rtol=math.sqrt(h.bucket_ratio) - 1)


def test_histogram_merge_associative_commutative():
    rng = np.random.default_rng(1)
    parts = [rng.lognormal(-5, 1, size=200) for _ in range(3)]

    def hist(vals):
        h = LogHistogram.latency()
        h.record_many(vals)
        return h

    a, b, c = (hist(p) for p in parts)
    left = hist(parts[0]).merge(hist(parts[1])).merge(hist(parts[2]))
    right = hist(parts[0]).merge(hist(parts[1]).merge(hist(parts[2])))
    swapped = hist(parts[2]).merge(hist(parts[0])).merge(hist(parts[1]))
    one_shot = hist(np.concatenate(parts))
    for other in (right, swapped, one_shot):
        np.testing.assert_array_equal(left.counts, other.counts)
        np.testing.assert_allclose(left.sum, other.sum)
        assert left.vmin == other.vmin and left.vmax == other.vmax
    # the originals were not mutated by building the merge trees
    assert a.n == b.n == c.n == 200


def test_histogram_merge_layout_mismatch_raises():
    with pytest.raises(ValueError, match="layouts differ"):
        LogHistogram.latency().merge(LogHistogram.fraction())


def test_histogram_serialization_round_trip():
    h = LogHistogram.fraction()
    h.record_many([0.1, 0.5, 0.9, 0.0])
    d = json.loads(json.dumps(h.to_dict()))        # through JSON, as shipped
    h2 = LogHistogram.from_dict(d)
    np.testing.assert_array_equal(h.counts, h2.counts)
    assert (h.sum, h.vmin, h.vmax) == (h2.sum, h2.vmin, h2.vmax)
    assert h.quantile(0.5) == h2.quantile(0.5)
    empty = LogHistogram.from_dict(LogHistogram.latency().to_dict())
    assert empty.n == 0 and empty.quantile(0.5) is None


@pytest.mark.slow
def test_histogram_properties_hypothesis():
    """Property: for any sample split, merged quantiles equal one-shot
    quantiles exactly, and every quantile is within the bucket bound of
    np.percentile(method='lower')."""
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings
    from hypothesis import strategies as st

    finite = st.floats(min_value=1e-7, max_value=1e4, allow_nan=False)

    @settings(max_examples=60, deadline=None)
    @given(st.lists(finite, min_size=1, max_size=120),
           st.lists(finite, max_size=120), st.floats(0.0, 1.0))
    def check(xs, ys, q):
        a, b, c = (LogHistogram.latency() for _ in range(3))
        a.record_many(xs)
        b.record_many(ys)
        c.record_many(xs + ys)
        merged = a.merge(b)
        np.testing.assert_array_equal(merged.counts, c.counts)
        assert merged.quantile(q) == c.quantile(q)
        exact = float(np.percentile(np.asarray(xs + ys), q * 100,
                                    method="lower"))
        bound = math.sqrt(c.bucket_ratio) - 1.0
        assert abs(c.quantile(q) - exact) <= exact * bound + 1e-12

    check()


# ----------------------------------------------------------------- Tracer


def test_tracer_nesting_and_attrs():
    t, clock = _manual_clock()
    tr = Tracer(clock=clock, host=3)
    with tr.trace("query", q=4) as root:
        t[0] += 1.0
        with tr.span("map"):
            t[0] += 0.5
        with tr.span("base") as sp:
            t[0] += 2.0
            sp.set(n_groups=2)
        root.set(kappa=10)
    assert not tr.active
    [fin] = tr.finished
    assert fin.name == "query" and fin.trace_id == 0 and fin.host == 3
    assert fin.attrs == {"q": 4, "kappa": 10}
    assert [c.name for c in fin.children] == ["map", "base"]
    assert fin.duration_s == pytest.approx(3.5)
    base, = fin.find("base")
    assert base.duration_s == pytest.approx(2.0)
    assert base.attrs == {"n_groups": 2}
    assert base.trace_id == fin.trace_id


def test_tracer_sampling_deterministic_and_id_aligned():
    done = []
    for _ in range(2):                        # same seed -> same decisions
        tr = Tracer(sample_rate=0.3, seed=7)
        kept = []
        for i in range(50):
            with tr.trace("r") as sp:
                if sp is not NOOP_SPAN:
                    kept.append(sp.trace_id)
        assert tr.n_started == 50
        assert tr.n_sampled == len(kept)
        # ids advance for EVERY root: the sampled subset keeps global ids
        assert kept == [f.trace_id for f in tr.finished]
        assert 0 < len(kept) < 50
        done.append(kept)
    assert done[0] == done[1]
    # rate 0 never samples but still advances ids (SPMD alignment)
    tr0 = Tracer(sample_rate=0.0)
    for _ in range(5):
        with tr0.trace("r"):
            pass
    assert tr0.n_started == 5 and tr0.n_sampled == 0 and not tr0.finished


def test_tracer_span_outside_trace_is_noop():
    tr = Tracer()
    with tr.span("orphan") as sp:
        assert sp is NOOP_SPAN
    assert not tr.finished
    tr.record_span("orphan", 0.0, 1.0)         # silently dropped too
    with tr.trace_or_span("direct"):           # no open trace -> root
        with tr.trace_or_span("inner"):        # open trace -> child
            pass
    [fin] = tr.finished
    assert fin.name == "direct"
    assert [c.name for c in fin.children] == ["inner"]


def test_tracer_exception_safety():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.trace("boom"):
            with tr.span("child"):
                raise RuntimeError("x")
    assert not tr.active                       # stack fully unwound
    [fin] = tr.finished                        # root still closed + retained
    assert fin.t1 is not None and fin.children[0].t1 is not None


def test_tracer_record_span_and_export(tmp_path):
    t, clock = _manual_clock()
    tr = Tracer(clock=clock, host=1, max_traces=2)
    for i in range(3):                         # deque bound: oldest evicted
        with tr.trace("req", i=i):
            tr.record_span("queue_wait", t[0] - 0.25, t[0])
            t[0] += 1.0
    assert [f.attrs["i"] for f in tr.finished] == [1, 2]
    path = tmp_path / "traces.jsonl"
    assert tr.export_jsonl(str(path)) == 2
    rows = [json.loads(line) for line in path.read_text().splitlines()]
    assert [r["trace_id"] for r in rows] == [1, 2]
    assert rows[0]["host"] == 1
    [qw] = rows[0]["children"]
    assert qw["name"] == "queue_wait"
    assert qw["duration_s"] == pytest.approx(0.25)
    stats = tr.stats()
    assert stats["n_started"] == 3 and stats["n_retained"] == 2


def test_noop_tracer_contract():
    with NOOP_TRACER.trace("a") as sp:
        assert sp is NOOP_SPAN
        sp.set(anything=1)                     # accepted, dropped
    with NOOP_TRACER.span("b") as sp:
        assert sp is NOOP_SPAN
    with NOOP_TRACER.trace_or_span("c") as sp:
        assert sp is NOOP_SPAN
    NOOP_TRACER.record_span("d", 0.0, 1.0)
    assert NOOP_TRACER.active is False


# ------------------------------------------- the tracer on the served path


def _served(tracer=None, *, quantize="none", cache=0):
    """A small ``sharded`` service: two shards and a delta of two rows."""
    spec = RetrieverSpec(cfg=CFG, backend="sharded", n_shards=2,
                         min_overlap=1, kappa=5, batch_size=4, bucket=512,
                         quantize=quantize, cache_capacity=cache)
    r = open_retriever(spec, items=unit_factors(256, CFG.k, 20),
                       tracer=tracer)
    r.upsert([1000, 1001], unit_factors(2, CFG.k, 21))
    return r


def _batch(r, seed):
    """One full microbatch (the size trigger fires it) -> its results."""
    rids = [r.batcher.submit(u) for u in unit_factors(4, CFG.k, seed)]
    return [r.batcher.result(i) for i in rids]


def _profile(log_dir, fn):
    """Run ``fn`` under the JAX profiler -> [(plane, line, name, t0, t1)]
    of every host event it recorded."""
    from jax.profiler import ProfileData

    jax.profiler.start_trace(str(log_dir))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    pd = ProfileData.from_file(path)
    return [(plane.name, line.name, e.name, e.start_ns,
             e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events]


def _names(span):
    return [span.name] + [n for c in span.children for n in _names(c)]


def test_profiler_mirrors_the_span_tree(tmp_path):
    tracer = Tracer()
    r = _served(tracer, quantize="int8", cache=64)
    _batch(r, 30)                        # programs compile before the profile
    events = [e for e in _profile(tmp_path, lambda: _batch(r, 31))
              if e[2].startswith("repro.")]
    assert {(plane, line) for plane, line, *_ in events} == \
        {("/host:CPU", "python")}
    # one annotation per span of the batch's tree, post-hoc queue_wait aside,
    # named by the span alone (its attributes stay on the Span)
    root = tracer.finished[-1]
    assert sorted(n for _, _, n, _, _ in events) == sorted(
        "repro." + n for n in _names(root) if n != "queue_wait")

    def only(name, within=None):
        found = [(t0, t1) for _, _, n, t0, t1 in events
                 if n == "repro." + name and (within is None or
                                              within[0] <= t0 <= within[1])]
        assert found, name
        return found[0]

    outer = only("request_batch")
    for name in ("flush", "query", "base", "device_wait"):
        inner = only(name, outer)
        assert outer[0] <= inner[0] and inner[1] <= outer[1], name
        outer = inner
    base = only("base")
    assert only("rerank", base)[1] <= base[1]
    cache = only("cache_lookup")
    assert cache[1] <= only("query")[0]


def test_noop_and_unsampled_traces_emit_no_annotation(tmp_path):
    from jax.profiler import TraceAnnotation

    unsampled = Tracer(sample_rate=0.0)

    def run():
        with NOOP_TRACER.trace("a"), NOOP_TRACER.span("b"):
            pass
        with unsampled.trace("root"), unsampled.span("child"):
            pass
        with Tracer().span("orphan"):     # a span outside any trace
            pass
        with TraceAnnotation("marker"):
            pass

    names = [n for _, _, n, _, _ in _profile(tmp_path, run)]
    assert "marker" in names
    assert not [n for n in names if n.startswith("repro.")]


@pytest.mark.parametrize("quantize", ["none", "int8"])
@pytest.mark.parametrize("cache", [0, 64], ids=["no_cache", "cache"])
def test_span_tree_of_a_served_batch(quantize, cache):
    tracer = Tracer()
    r = _served(tracer, quantize=quantize, cache=cache)
    _batch(r, 40)
    root = tracer.finished[-1]
    assert [c.name for c in root.children] == ["queue_wait", "flush"]
    [flush] = root.children[1:]
    assert [c.name for c in flush.children] == (
        ["cache_lookup", "query", "cache_fill"] if cache else ["query"])
    if not cache:
        assert not [n for n in _names(root) if n.startswith("cache")]
    [query] = flush.find("query")
    assert [c.name for c in query.children] == ["map", "base", "delta",
                                                "merge"]
    [base] = query.find("base")
    [delta] = query.find("delta")
    launches = len(base.find("gam_retrieve"))
    assert launches >= 1
    # every launch is waited on, and an int8 pool re-ranked, once
    assert len(base.find("device_wait")) == launches
    assert len(delta.find("device_wait")) == 1
    reranks = [len(base.find("rerank")), len(delta.find("rerank"))]
    assert reranks == ([launches, 1] if quantize == "int8" else [0, 0])
    assert all(s.t1 is not None for s in root.find("device_wait"))


def test_a_cache_hit_batch_gives_the_single_cache_span():
    tracer = Tracer()
    r = _served(tracer, cache=64)
    u = unit_factors(4, CFG.k, 41)
    r.query(u)                                    # fills the cache
    with tracer.trace("request"):                 # as a caller's root
        hit = r.query(u)
    root = tracer.finished[-1]
    assert [c.name for c in root.children] == ["cache_lookup", "query"]
    assert _names(root.children[1]) == ["query", "cache"]
    assert r.cache.stats()["hits"] == 4 and hit.explain is None
    r.query(u)                                    # a direct call: no lookup
    assert _names(tracer.finished[-1]) == ["query", "cache"]


@pytest.mark.parametrize("quantize", ["none", "int8"])
def test_tracing_leaves_the_answers_unchanged(quantize):
    on = _served(Tracer(), quantize=quantize, cache=64)
    off = _served(None, quantize=quantize, cache=64)
    assert off.tracer is NOOP_TRACER
    for seed in (50, 51):
        for a, b in zip(_batch(on, seed), _batch(off, seed)):
            np.testing.assert_array_equal(a.ids, b.ids)
            np.testing.assert_array_equal(a.scores, b.scores)
    u = unit_factors(3, CFG.k, 52)
    for exact in (False, True):
        a, b = on.query(u, exact=exact), off.query(u, exact=exact)
        np.testing.assert_array_equal(a.ids, b.ids)
        np.testing.assert_array_equal(a.scores, b.scores)
    roots = [sp.name for sp in on.tracer.finished]
    # the empty service's build and the catalog's, then the four requests
    assert roots[:2] == ["build", "build"] and len(roots) == 6


# ----------------------------------------------------------- EventJournal


def test_event_journal_bounded_and_dumpable(tmp_path):
    t, clock = _manual_clock()
    j = EventJournal(capacity=4, clock=clock, host=2)
    for i in range(7):
        t[0] += 1.0
        j.emit("phase", step=i)
    assert len(j) == 4 and j.n_emitted == 7
    assert [e["seq"] for e in j.tail()] == [3, 4, 5, 6]   # oldest first
    assert [e["step"] for e in j.tail(2)] == [5, 6]
    assert all(e["kind"] == "phase" and e["host"] == 2 for e in j.tail())
    path = tmp_path / "events.jsonl"
    assert j.dump_jsonl(str(path), append=False) == 4

    class Buf:
        text = ""

        def write(self, s):
            self.text += s

    buf = Buf()
    assert j.dump_jsonl(buf) == 4              # write()-ables work (stderr)
    assert [json.loads(x)["seq"] for x in buf.text.splitlines()] == \
        [json.loads(x)["seq"] for x in path.read_text().splitlines()]


def test_event_journal_rejects_degenerate_capacity():
    with pytest.raises(ValueError):
        EventJournal(capacity=0)


# -------------------------------------------------------------- exporters


def test_histogram_prometheus_exposition():
    h = LogHistogram(lo=1e-3, hi=1.0, bins=4)
    h.record_many([0.0, 0.002, 0.05, 0.9, 3.0])   # under, 2 in, 1 top, over
    text = histogram_to_prometheus("svc_latency_seconds", h, help_text="lat")
    lines = text.splitlines()
    assert lines[0] == "# HELP svc_latency_seconds lat"
    assert lines[1] == "# TYPE svc_latency_seconds histogram"
    buckets = [ln for ln in lines if "_bucket" in ln]
    assert len(buckets) == h.bins + 1             # finite edges + +Inf
    # cumulative counts: underflow folds into the first finite bucket,
    # overflow only into +Inf, +Inf equals the total count
    counts = [int(b.rsplit(" ", 1)[1]) for b in buckets]
    assert counts == sorted(counts)
    assert counts[-1] == h.n == 5
    assert counts[-2] == 4                        # all but the overflow value
    assert f"svc_latency_seconds_count {h.n}" in lines
    assert any(ln.startswith("svc_latency_seconds_sum ") for ln in lines)


def test_snapshot_prometheus_gauges_and_skips():
    text = snapshot_to_prometheus(
        {"qps": 12.5, "latency_p50_ms": None, "parity": True,
         "host_load": [3, 4], "mode": "gam"},
        {"latency_seconds": LogHistogram.latency()})
    assert "repro_qps 12.5" in text
    assert "latency_p50_ms" not in text           # None -> absent, not zero
    assert "repro_parity" not in text             # bools are not gauges
    assert 'repro_host_load{index="0"} 3' in text
    assert 'repro_host_load{index="1"} 4' in text
    assert "mode" not in text                     # strings skipped
    assert "# TYPE repro_latency_seconds histogram" in text


def test_jsonl_metrics_writer_interval(tmp_path):
    t, clock = _manual_clock()
    path = tmp_path / "metrics.jsonl"
    w = JsonlMetricsWriter(str(path), clock=clock, interval_s=1.0)
    h = LogHistogram.fraction()
    h.record(0.5)
    assert w.maybe_write(lambda: {"qps": 1.0}, lambda: {"occupancy": h})
    assert not w.maybe_write(lambda: {"qps": 2.0})     # interval not elapsed
    t[0] += 1.5
    assert w.maybe_write(lambda: {"qps": 3.0})
    rows = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert [r["qps"] for r in rows] == [1.0, 3.0]
    assert rows[0]["histograms"]["occupancy"]["counts"] == \
        h.to_dict()["counts"]
    assert w.n_written == 2


# ---------------------------------------------- ServiceMetrics on histograms


def test_service_metrics_split_and_merge():
    t, clock = _manual_clock()
    a, b = ServiceMetrics(clock), ServiceMetrics(clock)
    a.record_batch(2, 4, [0.010, 0.012], queue_waits_s=[0.008, 0.010],
                   service_s=0.002)
    b.record_batch(1, 4, [0.030], queue_waits_s=[0.028], service_s=0.002)
    b.record_query_stats(discard_fracs=[0.5])
    whole = ServiceMetrics(clock)
    whole.record_batch(2, 4, [0.010, 0.012], queue_waits_s=[0.008, 0.010],
                       service_s=0.002)
    whole.record_batch(1, 4, [0.030], queue_waits_s=[0.028], service_s=0.002)
    whole.record_query_stats(discard_fracs=[0.5])
    merged = a.merge(b)
    s_m, s_w = merged.snapshot(), whole.snapshot()
    for key in ("n_requests", "n_batches", "latency_p50_ms",
                "latency_p99_ms", "queue_wait_p50_ms", "service_p50_ms",
                "occupancy_mean", "discard_mean"):
        assert s_m[key] == s_w[key], key
    assert s_m["n_requests"] == 3
    np.testing.assert_allclose(s_m["queue_wait_p50_ms"], 10.0, rtol=0.05)
    np.testing.assert_allclose(s_m["service_p50_ms"], 2.0, rtol=0.05)


def test_service_metrics_snapshot_has_split_keys():
    m = ServiceMetrics()
    snap = m.snapshot()
    for key in ("queue_wait_p50_ms", "queue_wait_p99_ms",
                "service_p50_ms", "service_p99_ms",
                "push_staleness_p50_s", "push_staleness_p99_s"):
        assert key in snap and snap[key] is None    # empty -> None, not 0
    assert set(m.histograms()) == {"latency_seconds", "queue_wait_seconds",
                                   "service_seconds", "occupancy", "discard",
                                   "push_staleness_seconds"}
    m.record_push(3, 2, staleness_s=[0.5, 1.0, 2.0])
    snap = m.snapshot()
    assert snap["push_total"] == 3 and snap["push_suppressed"] == 2
    assert snap["push_flushes"] == 1
    np.testing.assert_allclose(snap["push_staleness_p50_s"], 1.0, rtol=0.05)
    other = ServiceMetrics()
    other.record_push(1, 0, staleness_s=[4.0])
    merged = m.merge(other)
    assert merged.snapshot()["push_total"] == 4
