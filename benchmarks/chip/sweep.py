"""The knee of an open-loop cell, found once on the chip.

    python3 benchmarks/chip/sweep.py --workload <name> --seed <n> \\
        --seconds 5 --rates 500 1000 2000 ...

One set-up of the cell's configuration, on the cell's chips as ``run.py``
places it (and refused as it refuses), then one window per offered rate
(Poisson, fresh queries), in the order given.  Per rate: requests, the
answered rate, p50 and p99 latency from the due time, and the p99 of the
last fifth of the window against the first fifth, which grows when the
backlog does.  The knee is the highest rate at which the answered rate
keeps up and the backlog does not grow; an open-loop cell offers about
four fifths of it.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402
from chipbench.cell import ROOT, load_cell  # noqa: E402


def main(argv=None, *, root: Path = ROOT, require_chip: bool = True) -> int:
    """``root`` and ``require_chip`` are for the benchmark's own tests, as
    ``run.open_device``'s are."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload, root)
    env = run.open_device(cell, require_chip)
    if isinstance(env, str):
        return run.fail(env)
    import numpy as np

    from chipbench import data, drive, system, traffic

    cfg = cell.config
    scheds = [traffic.plan({"loop": "open", "rate_qps": r,
                            "queries": {"kind": "fresh"}}, args.seconds,
                           data.host_rng(args.seed, 10 + i))
              for i, r in enumerate(args.rates)]
    bs = int(cfg["service"]["batch_size"])
    n_warm = (run.WARM_BATCHES + 1) * bs
    items, rows = data.make_rows(args.seed, cfg,
                                 n_warm + sum(s.n_rows for s in scheds))
    svc = system.open_service(cfg, items, traced=False, chips=cell.chips)
    _, refusal = run.placement(svc, cell.chips)
    if refusal:
        return run.fail(refusal)
    run.warm_up(svc, rows[:n_warm], system.QueryResult)
    off = n_warm
    for rate, sched in zip(args.rates, scheds):
        q = rows[off:off + sched.n_rows]
        off += sched.n_rows
        rec = drive.run_window(svc, q, sched, args.seconds,
                               kappa=int(cfg["gam"]["kappa"]), traced=False,
                               result_type=system.QueryResult)
        n = rec.issued
        lat = (rec.done - rec.due)[:n] * 1e3
        fifth = max(n // 5, 1)
        print(json.dumps({
            "workload": cell.name, "rate_qps": rate, "requests": n,
            "answered_per_s": float((rec.done[:n] <= rec.window_s).sum()
                                    / rec.window_s),
            "p50_ms": float(np.nanpercentile(lat, 50)),
            "p99_ms": float(np.nanpercentile(lat, 99)),
            "p99_first_fifth_ms": float(np.nanpercentile(lat[:fifth], 99)),
            "p99_last_fifth_ms": float(np.nanpercentile(lat[-fifth:], 99)),
            "mean_batch": float(n / max(len(rec.batches), 1))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
