"""The check that decides ``correct``, driven through a whole run at a
small size on the CPU with the chip look skipped: a sound run passes, the
control fails, and a run with the served path broken underneath fails."""
import json

import numpy as np
import pytest

import run
from chipbench import check
from chipbench.cell import HERE, load_cell

#: shrunken copies of the real configurations: same widths, few rows
SMALL = {"lastfm64": 3000, "glove100-int8": 4000}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding BENCHMARK.json and the two configurations cut to
    a few thousand rows (narrow buckets suffice there)."""
    root = tmp_path_factory.mktemp("bench")
    (root / "benchmarks/chip/configs").mkdir(parents=True)
    (root / "benchmarks/chip/traffic").mkdir(parents=True)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((HERE.parents[1] / c["file"]).read_text())
        cfg["n_items"] = SMALL[c["name"]]
        cfg["service"]["bucket"] = 512
        (root / c["file"]).write_text(json.dumps(cfg))
    (root / "benchmarks/chip/traffic/small-open.json").write_text(json.dumps(
        {"loop": "open", "rate_qps": 300, "queries": {"kind": "fresh"}}))
    bench["workloads"] = [
        {"name": f"{c['name']}.small", "config": c["name"],
         "traffic": "small-open", "chips": 1}
        for c in bench["configs"]]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def one_run(root, workload, fault=None, capsys=None):
    cell = load_cell(workload, root)
    env = run.open_device(cell, require_chip=False)
    return run.run_cell(cell, 2 ** 40 + 3, 1.5, False, env, fault=fault)


def wrap_base_query(svc, edit):
    base_query = svc.base.query

    def broken(*a, **kw):
        res = base_query(*a, **kw)
        edit(res)
        return res
    svc.base.query = broken


def half_batch_left_out(svc):
    """Every other row of every batch goes unanswered by the kernel (so a
    batch of two requests or more loses some, however full it is)."""
    def edit(res):
        rows, scores = np.array(res.rows), np.array(res.scores)
        rows[1::2], scores[1::2] = -1, -3.0e38
        res.rows, res.scores = rows, scores
    wrap_base_query(svc, edit)


def answer_altered(svc):
    """The best id of every answer is replaced where the kernel made it."""
    def edit(res):
        rows = np.array(res.rows)
        live = rows[:, 0] >= 0
        rows[live, 0] = (rows[live, 0] + 1) % svc.base.partition.n
        res.rows = rows
    wrap_base_query(svc, edit)


@pytest.mark.parametrize("config", sorted(SMALL))
def test_sound_run_is_correct_and_the_control_is_not(root, config):
    out, seen = one_run(root, f"{config}.small")
    gap = out["check"]["answer_gap"]
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 300
    refc, qs = seen["reference"], seen["queries"]
    cfg = load_cell(f"{config}.small", root).config
    ids, scores = check.control(refc, qs, cfg["check"]["control"])
    control_gap, _ = check.judge(refc, qs, ids, scores)
    assert control_gap > gap["limit"] > gap["value"]


@pytest.mark.parametrize("fault", [half_batch_left_out, answer_altered])
@pytest.mark.parametrize("config", sorted(SMALL))
def test_a_broken_served_path_is_not_correct(root, config, fault):
    out, _ = one_run(root, f"{config}.small", fault=fault)
    assert not out["correct"]
    assert out["check"]["answer_gap"]["value"] == check.WRONG


def answers_dropped(svc):
    """Every tenth answer of the window never reaches its client."""
    result = svc.batcher.result

    def dropping(rid):
        out = result(rid)
        return None if rid > 200 and rid % 10 == 9 else out
    svc.batcher.result = dropping


def test_an_answer_that_never_comes_is_lost(root):
    out, _ = one_run(root, "lastfm64.small", fault=answers_dropped)
    assert not out["correct"]
    assert out["failed"] == out["check"]["lost"]["value"] > 0
