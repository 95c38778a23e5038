"""Readings that the limits of the check are set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <name> --seconds 3 \\
        --seeds 101 102 ...

For each seed, one run of the cell as ``run.py`` makes it (set-up, a window
at the cell's own load, the check), all in one process; then the control
on the same sampled requests: the reference put in the program's place,
its scores computed in the precision the configuration names under
``check.control``, one step below what the configuration states.  One JSON
line per seed: the program's ``answer_gap`` (the lower reading is the
largest of these) and the control's (the upper reading is the smallest).
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from chipbench.cell import load_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    env = run.open_device(cell)
    if isinstance(env, str):
        return run.fail(env)
    from chipbench import check

    precision = cell.config["check"]["control"]
    for seed in args.seeds:
        out, seen = run.run_cell(cell, seed, args.seconds, False, env)
        if out is None:
            return 1
        refc, qs = seen["reference"], seen["queries"]
        ids, scores = check.control(refc, qs, precision)
        control_gap, _ = check.judge(refc, qs, ids, scores)
        print(json.dumps({
            "workload": cell.name, "seed": seed,
            "answer_gap": out["check"]["answer_gap"]["value"],
            "control": precision, "control_gap": float(control_gap),
            "lost": out["failed"], "answers": out["setup"]["answers_compared"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
