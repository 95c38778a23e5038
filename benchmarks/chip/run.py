"""One run of one benchmark cell: set-up, a measured window, the check.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

run from the checkout's root on a machine with the chips the cell asks
for.  The cell, its configuration and its traffic are found by name
(``BENCHMARK.json``, ``benchmarks/chip/configs/``,
``benchmarks/chip/traffic/``); each per-layer metric by its reader in
``benchmarks/chip/metrics/<metric>.py``.

Set-up (``setup_s``): draw the catalog and the window's queries on the
device from the seed, build the service, refuse a catalog with spill rows,
and send warm-up batches through every shape the window uses.  A cell of
one chip serves from the first device.  A cell whose ``chips`` is more
opens the service on ``make_index_mesh(chips)``, as one process serves a
host's chips: the main segment's kernel state is partitioned over the
devices and each batch launches the kernel once on each.  The run is
refused unless the factor slab (and an int8 slab), the packed patterns and
the launches a batch makes are each spread over exactly the cell's chips.
The window then offers the cell's traffic for ``--seconds``.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the program's tracer and the profiler are on, and it carries
the per-layer metrics, the device's busy and window seconds and a
breakdown; a batch's kernel time is its slowest chip's.  After the window
the peak memory of each of the cell's devices is read (the fullest is
``memory_peak_bytes``), with the bytes each holds at the window's close
(its share of the served state: a mesh's build stages on the first
device, whose peak is the set-up's), the service is freed, and a sample
of the window's answers is compared with the plain reference.  ``setup`` in the result
breaks set-up into drawing the data, the build, the warm-up, and the
check's own time after the window; those are reported, not bounded.

The last line of stdout is one JSON object; the numbers compared are the
last lines of stderr and the last key of that object.  Without a TPU, with
fewer chips than the cell asks for, or with a catalog set-up refuses, the
run prints no result and exits 1.
"""
from __future__ import annotations

import argparse
import gc
import importlib.util
import json
import shutil
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from chipbench.cell import ROOT, load_cell  # noqa: E402

#: per-run output inside the checkout (the profiler's trace)
OUT = ROOT / ".bench_out"
#: warm-up batches sent through the service before the window
WARM_BATCHES = 3


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def fail(msg: str) -> int:
    print(f"run.py: {msg}", file=sys.stderr, flush=True)
    return 1


class CompileCounter:
    """Programs lowered (compiled or read from the cache) since start."""

    def __init__(self):
        import jax

        self.n = 0
        self._on_event = self._on
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on(self, event, duration, **kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.n += 1

    def close(self) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


def load_reader(name: str):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def warm_up(svc, rows, result_type) -> None:
    """Full batches through the size trigger, then a short one through the
    deadline: the shapes, programs and host paths the window uses."""
    b = svc.batcher
    bs = b.batch_size
    rids = [b.submit(r) for r in rows[:WARM_BATCHES * bs]]
    rids += [b.submit(r) for r in rows[WARM_BATCHES * bs:
                                       WARM_BATCHES * bs + bs // 2]]
    time.sleep(b.max_delay_s)
    b.poll()
    while b.pending:
        b.flush()
    for r in rids:
        if not isinstance(b.result(r), result_type):
            raise RuntimeError("a warm-up request was not answered")


def placement(svc, chips: int) -> tuple[int, str | None]:
    """-> (the devices that hold the main segment's kernel state, why the
    run is refused or None).  It is refused unless the factor slab, an
    int8 slab and the packed patterns each lie on the same ``chips``
    devices and a batch launches the kernel once on each."""
    from chipbench import system

    held, launches = system.kernel_placement(svc)
    print(f"setup: kernel state on devices {held}, {launches} launch(es) "
          f"a batch", file=sys.stderr, flush=True)
    used = len(set().union(*held.values()))
    if launches == chips and all(
            len(ids) == chips and ids == held["factor slab"]
            for ids in held.values()):
        return used, None
    return used, (
        f"kernel state is not spread over the {chips} chip(s) the cell "
        f"asks for: {', '.join(f'{k} on {len(v)}' for k, v in held.items())}"
        f" device(s), {launches} launch(es) a batch; refusing to run")


def main(argv=None) -> int:
    args = parse(argv)
    cell = load_cell(args.workload)
    env = open_device(cell)
    if isinstance(env, str):
        return fail(env)
    out, _ = run_cell(cell, args.seed, args.seconds, bool(args.trace), env)
    if out is None:
        return 1
    for name, c in out["check"].items():
        print(f"check {name}={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def open_device(cell, require_chip: bool = True):
    """-> (devices, peaks), or the reason the run cannot go on.  The
    benchmark's own tests pass ``require_chip=False`` to run on the CPU."""
    import jax

    devices = jax.devices()
    if require_chip:
        if devices[0].platform != "tpu":
            return (f"JAX found no TPU (platform {devices[0].platform!r}); "
                    f"nothing was run")
        if len(devices) < cell.chips:
            return (f"{cell.name} needs {cell.chips} chips, JAX found "
                    f"{len(devices)}")
    kind = devices[0].device_kind
    table = json.loads((HERE / "peaks.json").read_text())
    if require_chip:
        if kind not in table:
            return f"no peaks for device kind {kind!r} in peaks.json"
        from chipbench import system

        system.enable_compile_cache()
    return devices, table.get(kind, next(iter(table.values())))


def run_cell(cell, seed: int, seconds: float, traced: bool, env, *,
             fault=None):
    """One run: -> (the result object, what the check compared), or
    (None, None) when set-up refuses the catalog.  ``fault`` (tests only)
    is applied to the built service, to break the served path."""
    import jax
    import numpy as np

    from chipbench import check, data, drive, system, traffic
    from chipbench import trace as trace_mod
    from chipbench.context import Context

    devices, peaks = env
    cfg = cell.config
    compiles = CompileCounter()
    kappa = int(cfg["gam"]["kappa"])
    bs = int(cfg["service"]["batch_size"])
    sched = traffic.plan(cell.traffic, seconds, data.host_rng(seed, 1))

    t_setup = time.perf_counter()
    n_warm = (WARM_BATCHES + 1) * bs
    items, rows = data.make_rows(seed, cfg, sched.n_rows + n_warm)
    warm_rows, queries = rows[:n_warm], rows[n_warm:]
    t_data = time.perf_counter()
    svc = system.open_service(cfg, items, traced=traced, chips=cell.chips)
    t_build = time.perf_counter()
    spill = system.spill_rows(svc)
    print(f"setup: {cfg['n_items']} items x {cfg['dim']}, bucket "
          f"{cfg['service']['bucket']}, spill rows {spill}; data "
          f"{t_data - t_setup:.2f}s, build {t_build - t_data:.2f}s",
          file=sys.stderr, flush=True)
    if spill:
        fail(f"{spill} spill rows: the bucket is too narrow for this "
             f"catalog, whose answers would then not follow the overlap "
             f"semantics; refusing to run")
        return None, None
    if fault is not None:
        fault(svc)
    chips_used, refusal = placement(svc, cell.chips)
    if refusal:
        fail(refusal)
        return None, None
    warm_up(svc, warm_rows, system.QueryResult)
    t_warm = time.perf_counter()
    setup_s = t_warm - t_setup
    print(f"setup: warm-up {t_warm - t_build:.2f}s, "
          f"setup_s {setup_s:.2f}", file=sys.stderr, flush=True)
    n_compiled_setup = compiles.n

    trace_dir = OUT / "trace"
    if traced:
        shutil.rmtree(trace_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    rec = drive.run_window(svc, queries, sched, seconds, kappa=kappa,
                           traced=traced, result_type=system.QueryResult)
    n_compiled_window = compiles.n - n_compiled_setup
    compiles.close()
    tr = None
    if traced:
        jax.profiler.stop_trace()
        tr = trace_mod.load(trace_mod.find_xplane(trace_dir))
    # the peak counts the build's staging on the first device; what each
    # device holds at the window's close is its share of the served state
    mem = [d.memory_stats() or {} for d in system.cell_devices(svc, devices)]
    peaks_per_device = [int(m.get("peak_bytes_in_use", 0)) for m in mem]
    in_use_per_device = [int(m.get("bytes_in_use", 0)) for m in mem]
    spans = list(svc.tracer.finished) if traced else []
    print(f"window: {rec.issued} requests in {rec.window_s:.3f}s, "
          f"{len(rec.batches)} batches, programs lowered in set-up "
          f"{n_compiled_setup}, in the window {n_compiled_window}",
          file=sys.stderr, flush=True)
    del svc
    gc.collect()

    # -------------------------------------------- the check (reference)
    t_check = time.perf_counter()
    issued = rec.issued
    lost = int(issued - rec.answered[:issued].sum())
    refc = check.Reference(items, cfg)
    pick = check.sample(issued, int(cfg["check"]["sample"]),
                        data.host_rng(seed, 2))
    qs = queries[sched.qidx[pick]]
    gap, n_amb = check.judge(refc, qs, rec.ids[pick], rec.scores[pick])
    gap = float(gap)
    limit = float(cfg["check"]["answer_gap_limit"])
    correct = lost == 0 and gap <= limit
    check_s = time.perf_counter() - t_check

    # -------------------------------------------- metrics
    metrics = {}
    if not traced:
        lat = (rec.done - rec.due)[:issued]
        ok = rec.answered[:issued]
        pct = {p: (float(np.percentile(lat[ok], p) * 1e3) if ok.any()
                   else None) for p in (50, 90, 95, 99, 99.9, 100)}
        values = {
            "setup_s": setup_s,
            "latency_p50_ms": pct[50],
        }
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    else:
        s = cfg["service"]
        int8 = s["quantize"] == "int8"
        mo = int(cfg["gam"]["min_overlap"])

        def batch_work(reqs):
            slots, _ = refc.query_patterns(queries[sched.qidx[reqs]])
            return check.ref.batch_work(
                refc.index, slots, mo, dim=int(cfg["dim"]),
                row_bytes=int(cfg["dim"]) * (1 if int8 else 4),
                pool_rows=kappa * int(s["rerank_factor"]) if int8 else 0)

        ctx = Context(cfg, sched, rec, spans, tr, peaks,
                      peaks["int8_ops_per_s"] if int8
                      else peaks["bf16_flops_per_s"],
                      batch_work, data.host_rng(seed, 3), cell.chips)
        for m in cell.per_layer:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(peaks_per_device),
              "memory_peak_bytes_per_device": peaks_per_device,
              "memory_in_use_bytes_per_device": in_use_per_device,
              "chips_used": chips_used}
    out = {"correct": bool(correct), "attempted": int(issued),
           "failed": lost, "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = trace_mod.busy_s(tr)
        device["window_s"] = trace_mod.window_s(tr)
        out["breakdown"] = {
            "device_ops": [list(x) for x in trace_mod.op_seconds(tr)[:10]],
            "idle_gaps": [list(x) for x in trace_mod.idle_gaps(tr)[:10]]}
    if not traced:
        # the whole tail, for the record: the bounded metrics are above
        out["latency_ms"] = {f"p{p:g}": v for p, v in pct.items()}
    out["setup"] = {"setup_s": setup_s, "data_s": t_data - t_setup,
                    "build_s": t_build - t_data, "warm_s": t_warm - t_build,
                    "check_s": check_s, "spill_rows": spill,
                    "programs_lowered_in_window": n_compiled_window,
                    "queries_left_out_ambiguous": n_amb,
                    "answers_compared": int(pick.size)}
    out["check"] = {"answer_gap": {"value": gap, "limit": limit},
                    "lost": {"value": lost, "limit": 0}}
    return out, {"reference": refc, "queries": qs}


if __name__ == "__main__":
    sys.exit(main())
