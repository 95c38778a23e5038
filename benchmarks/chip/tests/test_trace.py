"""The trace reduction against brute arithmetic, on 60 ms of a trace
recorded on a TPU v5e (the closed-loop lastfm64 window; its events were cut
to the slice, and the harness's window span set to cover it)."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import trace as tr

DATA = Path(__file__).parent / "data" / "trace_lastfm64_60ms.json"


@pytest.fixture(scope="module")
def rec():
    return tr.Trace.from_json(json.loads(DATA.read_text()))


def timeline(events, lo, hi, step_ns=100.0):
    """Brute busy mask over the window at 100 ns resolution."""
    n = int(np.ceil((hi - lo) / step_ns))
    busy = np.zeros(n, bool)
    for _, s, d in events:
        a = int(np.floor((max(s, lo) - lo) / step_ns))
        b = int(np.ceil((min(s + d, hi) - lo) / step_ns))
        if b > a:
            busy[a:b] = True
    return busy


def test_the_recorded_trace_has_what_the_readers_use(rec):
    assert list(rec.ops) == ["/device:TPU:0"]
    assert tr.window_s(rec) == pytest.approx(0.06)
    assert tr.program_events(rec, "jit__gam_retrieve")
    assert any(n.startswith("bench.submit") for n, _, _ in rec.host)


def test_busy_union_equals_a_brute_timeline(rec):
    lo, hi = tr.window(rec)
    brute = timeline(rec.ops["/device:TPU:0"], lo, hi).mean() * (hi - lo)
    assert tr.busy_s(rec) * 1e9 == pytest.approx(brute, rel=2e-3)
    assert 0 < tr.busy_s(rec) < tr.window_s(rec)


def test_idle_gaps_cover_the_rest_of_the_window(rec):
    gaps = tr.idle_gaps(rec)
    total = sum(s for _, s in gaps)
    assert total == pytest.approx(tr.window_s(rec) - tr.busy_s(rec),
                                  rel=1e-9)
    assert [s for _, s in gaps] == sorted((s for _, s in gaps),
                                          reverse=True)


def test_op_seconds_sum_to_at_least_the_busy_time(rec):
    ops = tr.op_seconds(rec)
    # operations may overlap, so their sum bounds the union from above
    assert sum(s for _, s in ops) >= tr.busy_s(rec) * (1 - 1e-9)
    assert ops[0][0].startswith("%_gam_retrieve")


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]


def test_idle_gap_named_by_the_covering_call():
    t = tr.Trace(
        ops={"/device:TPU:0": [("a", 0.0, 10.0), ("b", 40.0, 10.0)]},
        modules={},
        host=[("bench.window", 0.0, 100.0), ("bench.submit", 12.0, 20.0),
              ("bench.poll", 60.0, 5.0)])
    gaps = dict(tr.idle_gaps(t))
    assert gaps["bench.submit"] == pytest.approx(30e-9)
    assert gaps["bench.poll"] == pytest.approx(50e-9)
    assert tr.busy_s(t) == pytest.approx(20e-9)


def test_short_names():
    assert tr.short_name("%copy.11 = f32[292864,64]{1,0:T(8,128)} copy(x)") \
        == "%copy.11 f32[292864,64]"
    assert tr.short_name("%_gam_retrieve.1 = (f32[32,40]{1,0}, s32[32,40]) "
                         "custom-call") == "%_gam_retrieve.1 f32[32,40]"


def test_each_program_run_holds_one_kernel_run(rec):
    from chipbench import readers

    kernels = tr.op_events(rec, readers.is_kernel_op)
    programs = tr.program_events(rec, readers.RETRIEVE_PROGRAM)
    assert len(kernels) == len(programs) > 0
    for (ks, kd), (ps, pd) in zip(kernels, programs):
        assert ps <= ks and ks + kd <= ps + pd
