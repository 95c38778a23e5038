"""The four-chip cell ``deep96-int8.shard4`` at a small size on four
virtual CPU devices (in a child process, whose JAX starts with them): its
configuration cut to a few thousand rows is served from the mesh, a sound
run is correct and the bf16 control is not, and a traced run reports the
cell's per-layer metrics, the per-chip launches and their merge among
them.  The harness's four-chip faults are driven through this cell too:
its slab left on one device is refused, one device's answers left out of
the merge read not correct, and the knee sweep opens it on its mesh."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import run
from chipbench import check
from chipbench.cell import HERE, load_cell
from test_check import (one_device_left_out_of_the_merge, one_run,
                        slab_on_one_device, sweep_lines)

CELL = "deep96-int8.shard4"
#: the configuration's rows at the small size (its widths are kept)
ROWS = 6000


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """A checkout holding BENCHMARK.json, the cell's traffic and its
    configuration cut to ``ROWS`` rows (a narrow bucket suffices there)."""
    root = tmp_path_factory.mktemp("bench")
    (root / "benchmarks/chip/configs").mkdir(parents=True)
    (root / "benchmarks/chip/traffic").mkdir(parents=True)
    bench = json.loads((HERE.parents[1] / "BENCHMARK.json").read_text())
    [w] = [w for w in bench["workloads"] if w["name"] == CELL]
    [c] = [c for c in bench["configs"] if c["name"] == w["config"]]
    cfg = json.loads((HERE.parents[1] / c["file"]).read_text())
    cfg["n_items"] = ROWS
    cfg["service"]["bucket"] = 1024
    (root / c["file"]).write_text(json.dumps(cfg))
    name = f"benchmarks/chip/traffic/{w['traffic']}.json"
    (root / name).write_text((HERE.parents[1] / name).read_text())
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def cell_runs(root: Path) -> dict:
    """An untraced and a traced run, and the control's gap on the first's
    sample.  For a process whose JAX has four devices."""
    cell = load_cell(CELL, root)
    env = run.open_device(cell, require_chip=False)
    out, seen = run.run_cell(cell, 2 ** 40 + 11, 1.5, False, env)
    refc, qs = seen["reference"], seen["queries"]
    ids, scores = check.control(refc, qs, cell.config["check"]["control"])
    control_gap, _ = check.judge(refc, qs, ids, scores)
    traced, _ = run.run_cell(cell, 2 ** 40 + 12, 1.5, True, env)
    runs = {"sound": out, "control_gap": float(control_gap),
            "traced": traced, "sweep": sweep_lines(root, CELL, [200])}
    # the merge fault stays in the program's module, so it comes last
    for name, fault in (("one_device", slab_on_one_device),
                        ("merge_drops_one",
                         one_device_left_out_of_the_merge)):
        runs[name], _ = one_run(root, CELL, fault=fault)
    return runs


@pytest.fixture(scope="module")
def on_four_devices(root):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = ("import json, sys; from pathlib import Path; "
            "sys.path[:0] = sys.argv[1:3]; import test_deep96_cell as t; "
            "print(json.dumps(t.cell_runs(Path(sys.argv[3]))))")
    p = subprocess.run(
        [sys.executable, "-c", code, str(HERE), str(Path(__file__).parent),
         str(root)], env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return dict(json.loads(p.stdout.splitlines()[-1]), stderr=p.stderr)


def test_the_cell_is_served_from_four_devices_and_is_correct(
        on_four_devices):
    out = on_four_devices["sound"]
    assert out["correct"], out["check"]
    assert out["failed"] == 0 and out["attempted"] > 150
    assert out["device"]["chips_used"] == 4
    assert out["setup"]["spill_rows"] == 0
    assert set(out["metrics"]) == {"latency_p50_ms", "setup_s"}


def test_the_control_is_not_correct(on_four_devices):
    gap = on_four_devices["sound"]["check"]["answer_gap"]
    assert on_four_devices["control_gap"] > gap["limit"] > gap["value"]


def test_a_traced_run_reports_the_launches_and_their_merge(on_four_devices):
    out = on_four_devices["traced"]
    assert out["correct"], out["check"]
    m = out["metrics"]
    for name in ("group_merge_ms.steady", "launch_fanout_ms.steady",
                 "rerank_ms.steady", "device_wait_ms.steady",
                 "query_host_ms.steady", "batch_host_ms.steady"):
        assert m[name]["value"] > 0, name
    assert m["launch_fanout_ms.steady"]["value"] \
        < m["query_host_ms.steady"]["value"] \
        + m["device_wait_ms.steady"]["value"]


def test_a_traced_run_reports_the_prepass_skips(on_four_devices):
    m = on_four_devices["traced"]["metrics"]
    assert 0.0 <= m["tiles_skipped_pct.steady"]["value"] <= 100.0


def test_the_cell_on_one_device_is_refused(on_four_devices):
    assert on_four_devices["one_device"] is None
    stderr = on_four_devices["stderr"]
    assert "not spread over the 4 chip(s)" in stderr
    assert "factor slab on 1" in stderr and "1 launch(es)" in stderr


def test_one_device_left_out_of_the_merge_is_not_correct(on_four_devices):
    out = on_four_devices["merge_drops_one"]
    assert out["device"]["chips_used"] == 4
    assert not out["correct"]
    gap = out["check"]["answer_gap"]
    assert gap["value"] > gap["limit"]


def test_the_sweep_opens_the_cell_on_its_mesh(on_four_devices):
    rc, lines = on_four_devices["sweep"]
    assert rc == 0
    [line] = lines
    assert line["workload"] == CELL and line["rate_qps"] == 200
    assert line["requests"] > 100 and line["answered_per_s"] > 100
