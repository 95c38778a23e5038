"""The trace reduction against brute arithmetic, on 60 ms of a trace
recorded on a TPU v5e (the closed-loop lastfm64 window; its events were cut
to the slice, and the harness's window span set to cover it)."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import program_spans, readers
from chipbench import trace as tr
from chipbench.context import Context

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
    [runs] = tr.program_events(rec, "jit__gam_retrieve")
    assert runs
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
    kernels = tr.op_events(rec, readers.is_kernel_op)
    programs = tr.program_events(rec, readers.RETRIEVE_PROGRAM)
    assert len(kernels) == len(programs) == len(rec.ops)
    for chip_k, chip_p in zip(kernels, programs):
        assert len(chip_k) == len(chip_p) > 0
        for (ks, kd), (ps, pd) in zip(chip_k, chip_p):
            assert ps <= ks and ks + kd <= ps + pd


class Record:
    """The part of ``drive.Record`` the trace readers use."""

    def __init__(self, n_batches: int, batch_size: int = 32):
        self.in_window = n_batches
        self.batches = [np.arange(k * batch_size, (k + 1) * batch_size)
                        for k in range(n_batches)]


PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def batch_work(reqs):
    """A made-up amount of work per batch, different for each batch."""
    return float(reqs[0] + 1) * 1e6, float(reqs[0] + 7) * 3e9


def context(trace, n_batches, chips):
    return Context({}, None, Record(n_batches), [], trace, PEAKS,
                   PEAKS["bf16_flops_per_s"], batch_work,
                   np.random.default_rng(5), chips)


def recorded_query_spans(rec):
    """Host ``repro.query`` events around each program run, 0.1 ms wider
    on either side: the recorded trace holds none of the program's own."""
    [runs] = tr.program_events(rec, readers.RETRIEVE_PROGRAM)
    return program_spans.ProgramSpans.of(
        rec, [("repro.query", s - 1e5, d + 2e5) for s, d in runs])


#: every reader of the device trace on the recorded one-chip trace, as the
#: harness gave it before it read several chips (exact, to the last bit)
ONE_CHIP_READINGS = {
    "busy_s": (lambda rec: tr.busy_s(rec), 0.025724369),
    "window_s": (lambda rec: tr.window_s(rec), 0.06),
    "op_seconds": (lambda rec: tr.op_seconds(rec)[:3], [
        ("%_gam_retrieve.1 f32[32,10]", 0.023585507),
        ("%copy.11 f32[292864,64]", 0.0017486029999999998),
        ("%fusion u32[8256]", 9.058099999999999e-05)]),
    "idle_gaps": (lambda rec: tr.idle_gaps(rec), [
        ("bench.submit", 0.027392579999999934),
        ("harness between calls (no request due)", 0.0068830510000000115)]),
    "retrieve_device_ms": (
        lambda rec: readers.retrieve_device_ms(context(rec, 4, 1)),
        6.2232905),
    "gam_retrieve_roofline": (
        lambda rec: readers.gam_retrieve_roofline(context(rec, 4, 1)),
        14.385546494091825),
    "device_idle_pct": (
        lambda rec: readers.device_idle_pct(context(rec, 4, 1)),
        57.12605166666667),
    "idle_in_query_pct": (
        lambda rec: recorded_query_spans(rec).idle_in_query_pct(),
        1.3353866666666667),
}


@pytest.mark.parametrize("reader", sorted(ONE_CHIP_READINGS))
def test_one_chip_readings_are_unchanged(rec, reader):
    read, value = ONE_CHIP_READINGS[reader]
    assert read(rec) == value


#: kernel and program durations (ns) of three batches on four chips, each
#: batch's slowest on another chip
KERNEL_NS = [[9.0e6, 9.5e6, 9.2e6],
             [9.1e6, 9.0e6, 9.9e6],
             [9.8e6, 9.1e6, 9.0e6],
             [9.2e6, 9.3e6, 9.1e6]]
PROGRAM_EXTRA_NS = 3e5
KERNEL_OP = ('%_gam_retrieve.1 = (f32[32,10]{1,0}, s32[32,10]{1,0}) '
             'custom-call(...), custom_call_target="tpu_custom_call"')


def four_chip_trace(drop=None):
    """Three batches, each launched once on every chip 20 ms apart, the
    chips' planes in no order; ``drop`` = (chip, batch) leaves that run
    out."""
    ops, modules = {}, {}
    for c in (2, 0, 3, 1):
        plane = f"/device:TPU:{c}"
        ops[plane], modules[plane] = [], []
        for k, d in enumerate(KERNEL_NS[c]):
            if (c, k) == drop:
                continue
            start = 1e6 + k * 2e7 + c * 1e4
            modules[plane].append(("jit__gam_retrieve(1)", start,
                                   d + PROGRAM_EXTRA_NS))
            ops[plane] += [("%copy.1 = f32[8]{0} copy(x)", start, 1e5),
                           (KERNEL_OP, start + 1e5, d)]
    return tr.Trace(ops, modules, [("bench.window", 0.0, 7e7),
                                   ("bench.submit", 5e5, 2e7)])


def test_four_chips_read_the_slowest_chip_of_each_batch():
    t = four_chip_trace()
    slowest = np.max(KERNEL_NS, axis=0)
    runs = tr.op_events(t, readers.is_kernel_op)
    assert sorted([d for _, d in chip] for chip in runs) == sorted(KERNEL_NS)
    ctx = context(t, 3, 4)
    assert readers.retrieve_device_ms(ctx) == pytest.approx(
        np.mean(slowest + PROGRAM_EXTRA_NS) / 1e6, rel=1e-12)
    t_min = sum(max(b / (4 * PEAKS["hbm_bytes_per_s"]),
                    f / (4 * PEAKS["bf16_flops_per_s"]))
                for b, f in map(batch_work, ctx.record.batches))
    assert readers.gam_retrieve_roofline(ctx) == pytest.approx(
        t_min / (slowest.sum() / 1e9) * 100.0, rel=1e-12)
    # a cell of one chip finds four chips' launches and reads nothing
    assert readers.gam_retrieve_roofline(context(t, 3, 1)) is None
    # idle time is averaged over the chips, as busy time is
    gaps = sum(s for _, s in tr.idle_gaps(t))
    assert gaps == pytest.approx(tr.window_s(t) - tr.busy_s(t), rel=1e-12)


def test_a_chip_with_a_missing_run_leaves_the_kernel_readings_out():
    ctx = context(four_chip_trace(drop=(2, 1)), 3, 4)
    assert readers.gam_retrieve_roofline(ctx) is None
    assert readers.retrieve_device_ms(ctx) is None
