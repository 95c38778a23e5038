"""The readers of the four-chip cell's own layers, the per-chip launches
and the host's merge of them, against hand-worked arithmetic on a
synthetic trace, and silent on a trace of one chip."""
import json
import types
from pathlib import Path

import pytest

import run
from chipbench import program_spans as ps
from chipbench import trace as tr

DATA = Path(__file__).parent / "data" / "trace_lastfm64_60ms.json"

#: one batch before the window and two inside it, each launching on four
#: chips one after another and merging the four answers (ns)
HOST = [
    ("repro.query", 500, 900),
    ("repro.launch", 550, 40), ("repro.launch", 600, 40),
    ("repro.launch", 650, 40), ("repro.launch", 700, 40),
    ("repro.group_merge", 1300, 50),
    ("repro.query", 2000, 3000),
    ("repro.launch", 2100, 100), ("repro.gam_retrieve", 2150, 20),
    ("repro.launch", 2300, 120), ("repro.launch", 2500, 90),
    ("repro.launch", 2700, 150),
    ("repro.group_merge", 4600, 300),
    ("repro.query", 6000, 2000),
    ("repro.launch", 6050, 80), ("repro.launch", 6150, 80),
    ("repro.launch", 6250, 80), ("repro.launch", 6350, 200),
    ("repro.group_merge", 7700, 100),
]


def synthetic(monkeypatch, host):
    monkeypatch.setattr(ps, "load", lambda path: [
        (n, float(s), float(d)) for n, s, d in host])
    monkeypatch.setattr(ps.tr, "find_xplane", lambda d: "run.xplane.pb")
    trace = tr.Trace(ops={f"/device:TPU:{c}": [] for c in range(4)},
                     modules={}, host=[("bench.window", 1000.0, 9000.0)])
    return types.SimpleNamespace(trace=trace)


def test_launch_fanout_by_hand(monkeypatch):
    # in the window: launches from 2,100 to 2,850 ns and from 6,050 to
    # 6,550 ns; the batch at 500 ns started before the window
    ctx = synthetic(monkeypatch, HOST)
    read = run.load_reader("launch_fanout_ms.steady")
    assert read(ctx) == pytest.approx((750 + 500) / 2 / 1e6)


def test_group_merge_by_hand(monkeypatch):
    ctx = synthetic(monkeypatch, HOST)
    read = run.load_reader("group_merge_ms.steady")
    assert read(ctx) == pytest.approx((300 + 100) / 2 / 1e6)


def test_a_launch_past_its_query_is_not_counted(monkeypatch):
    # a launch that ends after its query ends belongs to no batch
    host = HOST + [("repro.launch", 7900, 500)]
    ctx = synthetic(monkeypatch, host)
    read = run.load_reader("launch_fanout_ms.steady")
    assert read(ctx) == pytest.approx((750 + 500) / 2 / 1e6)


@pytest.mark.parametrize("name", ["launch_fanout_ms.steady",
                                  "group_merge_ms.steady"])
def test_silent_on_the_recorded_one_chip_trace(monkeypatch, name):
    """The recorded one-chip trace holds no launch and no merge: nothing
    to read, with or without the program's query spans."""
    rec = tr.Trace.from_json(json.loads(DATA.read_text()))
    read = run.load_reader(name)

    def missing(d):
        raise FileNotFoundError(d)

    monkeypatch.setattr(ps.tr, "find_xplane", missing)
    assert read(types.SimpleNamespace(trace=rec)) is None
    [runs] = tr.program_events(rec, "jit__gam_retrieve")
    spans = ps.ProgramSpans.of(
        rec, [("repro.query", s - 1e5, d + 2e5) for s, d in runs])
    assert spans.in_window("query")
    assert read(types.SimpleNamespace(trace=rec,
                                      program_spans=spans)) is None
    assert read(types.SimpleNamespace(trace=None)) is None
