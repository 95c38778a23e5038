"""The check that decides ``correct``, driven through a whole run at a
small size on the CPU with the chip look skipped: a sound run passes, the
control fails, and a run with the served path broken underneath fails.
A cell of four chips runs on four virtual CPU devices in a child process:
it is served from a mesh, reads not correct where one device's answers are
left out of the merge, and is refused where its kernel state is not spread
over the four.  The knee sweep opens a cell as a run does."""
import contextlib
import io
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import run
import sweep
from chipbench import check
from chipbench.cell import HERE, load_cell

#: shrunken copies of the real configurations: same widths, few rows
SMALL = {"lastfm64": 3000, "glove100-int8": 4000}
#: the cell run on four virtual devices
MESH_CELL = "glove100-int8.small4"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding BENCHMARK.json and the two configurations cut to
    a few thousand rows (narrow buckets suffice there), each in a cell of
    one chip and in one of four."""
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
        {"name": f"{c['name']}.small{'' if chips == 1 else chips}",
         "config": c["name"], "traffic": "small-open", "chips": chips}
        for c in bench["configs"] for chips in (1, 4)]
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


def slab_on_one_device(svc):
    """The factor slab of the main segment moved back to the first device,
    as a layout that does not split over the mesh would leave it."""
    import jax

    svc.base.factors_g[0] = jax.device_put(svc.base.factors_g[0],
                                           jax.devices()[0])


def one_device_left_out_of_the_merge(svc):
    """The host merge across the mesh drops the second device's top-kappa
    (its launch ran, its answers never reach the result).  Patches the
    program's module: for a child process that ends after it."""
    from repro.service import sharded_index

    lost = (svc.base.partition.group_rows(0)[0]
            + svc.base._launch_units(0)[1][0])
    export = sharded_index.export_topk

    def dropping(vals, rows, *, offset=0):
        scores, ids = export(vals, rows, offset=offset)
        if offset == lost:
            scores = np.full_like(scores, -3.0e38)
        return scores, ids
    sharded_index.export_topk = dropping


def sweep_lines(root: Path, workload: str, rates) -> tuple[int, list]:
    """``sweep.main`` at the small size: -> (its exit code, its lines)."""
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = sweep.main(["--workload", workload, "--seed", str(2 ** 40 + 5),
                         "--seconds", "1",
                         "--rates", *map(str, rates)],
                        root=root, require_chip=False)
    return rc, [json.loads(x) for x in buf.getvalue().splitlines()]


def mesh_runs(root: Path) -> dict:
    """The four-chip cell: a sweep, and runs sound, with its slab on one
    device, and with one device left out of the merge (last, as its fault
    stays in the program).  For a process whose JAX has four devices."""
    out = {"sweep": sweep_lines(root, MESH_CELL, [200])}
    for name, fault in (("sound", None), ("one_device", slab_on_one_device),
                        ("merge_drops_one", one_device_left_out_of_the_merge)):
        res, _ = one_run(root, MESH_CELL, fault=fault)
        out[name] = res
    return out


@pytest.fixture(scope="module")
def on_four_devices(root):
    """``mesh_runs`` in a child process given four CPU devices: the
    parent's JAX has started with its own."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, sys; from pathlib import Path; "
            "sys.path[:0] = sys.argv[1:3]; import test_check; "
            "print(json.dumps(test_check.mesh_runs(Path(sys.argv[3]))))")
    p = subprocess.run(
        [sys.executable, "-c", code, str(HERE), str(Path(__file__).parent),
         str(root)], env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.splitlines()[-1]), p.stderr


def test_a_four_chip_cell_is_served_from_a_mesh(on_four_devices):
    runs, _ = on_four_devices
    out = runs["sound"]
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 300
    assert out["device"]["count"] == 4
    assert out["device"]["chips_used"] == 4
    assert len(out["device"]["memory_peak_bytes_per_device"]) == 4
    assert len(out["device"]["memory_in_use_bytes_per_device"]) == 4
    s = out["setup"]
    assert s["data_s"] + s["build_s"] + s["warm_s"] == pytest.approx(
        s["setup_s"])
    assert s["check_s"] > 0


def test_a_four_chip_cell_on_one_device_is_refused(on_four_devices):
    runs, stderr = on_four_devices
    assert runs["one_device"] is None
    assert "not spread over the 4 chip(s)" in stderr
    assert "factor slab on 1" in stderr and "1 launch(es)" in stderr


def test_one_device_left_out_of_the_merge_is_not_correct(on_four_devices):
    runs, _ = on_four_devices
    out = runs["merge_drops_one"]
    assert out["device"]["chips_used"] == 4
    assert not out["correct"]
    gap = out["check"]["answer_gap"]
    assert gap["value"] > gap["limit"]


def test_the_sweep_opens_a_four_chip_cell_on_its_mesh(on_four_devices):
    runs, _ = on_four_devices
    rc, lines = runs["sweep"]
    assert rc == 0
    [line] = lines
    assert line["workload"] == MESH_CELL and line["rate_qps"] == 200
    assert line["requests"] > 100 and line["answered_per_s"] > 100


def test_the_sweep_reads_each_rate_of_a_one_chip_cell(root):
    rc, lines = sweep_lines(root, "lastfm64.small", [150, 300])
    assert rc == 0
    assert [x["rate_qps"] for x in lines] == [150, 300]
    for x in lines:
        assert x["requests"] > 0.5 * x["rate_qps"]
        assert 0 < x["p50_ms"] <= x["p99_ms"]
