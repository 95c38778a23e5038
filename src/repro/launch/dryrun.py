"""Multi-pod dry-run: lower + compile every (arch x input-shape) combination
on the production mesh, print memory/cost analyses, and dump the roofline
inputs to a JSON ledger.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out FILE]

Each record proves: the sharding lowers, the collectives schedule, and the
per-device memory fits; failures here are bugs in the system.
"""
import argparse
import json
import os
import re
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES
from repro.launch.mesh import force_host_devices, make_production_mesh
from repro.launch.steps import (
    abstract_cache,
    abstract_opt_state,
    abstract_params,
    input_specs,
    make_prefill_step,
    make_serve_step,
    make_train_step,
    shape_adapted_config,
)
from repro.models.model import Model
from repro.sharding.specs import batch_specs, cache_specs, param_shardings

SKIPS = {
    # (arch, shape) combinations that are out of family scope (DESIGN.md §4)
    ("whisper-tiny", "long_500k"):
        "enc-dec: a 524288-token text decode is outside the family's scope",
}

_COLLECTIVE_RE = re.compile(
    r"=\s*\(?([a-z0-9]+)\[([0-9,]*)\][^=]*?"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
)
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def cost_analysis_dict(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a plain dict (empty when the backend
    reports nothing) — the one reader the dry-run ledger, perf probe,
    roofline and tests share."""
    return dict(compiled.cost_analysis() or {})


def collective_bytes(hlo_text: str) -> dict:
    """Per-device bytes moved by each collective kind, parsed from the SPMD
    per-partition HLO module."""
    out: dict = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        dt, dims, kind = m.groups()
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d.strip():
                n *= int(d)
        out[kind] = out.get(kind, 0) + n * _DTYPE_BYTES[dt]
    return out


def build_lowered(cfg: ModelConfig, shape: ShapeConfig, mesh):
    """Lower the appropriate step for (cfg, shape) on ``mesh``."""
    cfg = shape_adapted_config(cfg, shape)
    model = Model(cfg)
    params_sds = abstract_params(model)
    p_shard = param_shardings(mesh, params_sds, fsdp=cfg.fsdp,
                              overrides=cfg.spec_overrides)
    batch_sds = input_specs(cfg, shape)
    b_shard = batch_specs(cfg, mesh, batch_sds)

    with mesh:
        if shape.kind == "train":
            opt_sds = abstract_opt_state(params_sds)
            opt_shard = type(opt_sds)(
                step=NamedSharding(mesh, P()),
                mu=param_shardings(mesh, opt_sds.mu, fsdp=True),
                nu=param_shardings(mesh, opt_sds.nu, fsdp=True))
            step = make_train_step(model)
            jitted = jax.jit(step, in_shardings=(p_shard, opt_shard, b_shard),
                             donate_argnums=(0, 1))
            return jitted.lower(params_sds, opt_sds, batch_sds)
        if shape.kind == "prefill":
            step = make_prefill_step(model, capacity=shape.seq_len)
            jitted = jax.jit(step, in_shardings=(p_shard, b_shard))
            return jitted.lower(params_sds, batch_sds)
        # decode: ONE new token against a cache of seq_len
        cache_sds = abstract_cache(model, shape.global_batch, shape.seq_len)
        c_shard = cache_specs(cfg, mesh, cache_sds,
                              seq_shard=shape.global_batch == 1)
        tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), "int32")
        t_shard = batch_specs(cfg, mesh, tok_sds)
        step = make_serve_step(model)
        jitted = jax.jit(step, in_shardings=(p_shard, c_shard, t_shard),
                         donate_argnums=(1,))
        return jitted.lower(params_sds, cache_sds, tok_sds)


def run_one(arch: str, shape_name: str, *, multi_pod: bool = False,
            cfg_override=None, verbose: bool = True) -> dict:
    shape = SHAPES[shape_name]
    cfg = cfg_override or get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "chips": 512 if multi_pod else 256}
    if (arch, shape_name) in SKIPS:
        rec["status"] = "skip"
        rec["reason"] = SKIPS[(arch, shape_name)]
        return rec
    t0 = time.monotonic()
    lowered = build_lowered(cfg, shape, mesh)
    rec["lower_s"] = round(time.monotonic() - t0, 1)
    t0 = time.monotonic()
    compiled = lowered.compile()
    rec["compile_s"] = round(time.monotonic() - t0, 1)
    mem = compiled.memory_analysis()
    cost = cost_analysis_dict(compiled)
    rec["bytes_per_device"] = {
        "argument": mem.argument_size_in_bytes,
        "output": mem.output_size_in_bytes,
        "temp": mem.temp_size_in_bytes,
        "peak": mem.peak_memory_in_bytes,
    }
    rec["flops_per_device"] = cost.get("flops", 0.0)
    rec["hbm_bytes_per_device"] = (cost.get("bytes accessed", 0.0))
    rec["collectives_per_device"] = collective_bytes(compiled.as_text())
    rec["status"] = "ok"
    if verbose:
        print(f"== {arch} x {shape_name} on {rec['mesh']} "
              f"(lower {rec['lower_s']}s, compile {rec['compile_s']}s)")
        print("memory_analysis:", rec["bytes_per_device"])
        print("cost_analysis: flops/device={:.3e} bytes/device={:.3e}".format(
            rec["flops_per_device"], rec["hbm_bytes_per_device"]))
        print("collectives/device:", rec["collectives_per_device"])
    return rec


def main() -> None:
    force_host_devices(512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/dryrun.json")
    args = ap.parse_args()

    pairs = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in ARCH_IDS for s in SHAPES])
    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"], r["mesh"]) for r in results
            if r.get("status") in ("ok", "skip")}
    for arch, shape in pairs:
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        if (arch, shape, mesh_name) in done:
            print(f"-- cached: {arch} x {shape} on {mesh_name}")
            continue
        try:
            rec = run_one(arch, shape, multi_pod=args.multi_pod)
        except Exception as e:  # a failure here is a bug in the system
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                   "status": "FAIL", "error": f"{type(e).__name__}: {e}"}
        results = [r for r in results
                   if not (r["arch"] == arch and r["shape"] == shape
                           and r["mesh"] == mesh_name)]
        results.append(rec)
        if args.out:
            os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    bad = [r for r in results if r.get("status") == "FAIL"]
    print(f"\n{len([r for r in results if r.get('status') == 'ok'])} ok, "
          f"{len([r for r in results if r.get('status') == 'skip'])} skip, "
          f"{len(bad)} FAIL")
    for r in bad:
        print("FAIL:", r["arch"], r["shape"], r["mesh"], r.get("error"))


if __name__ == "__main__":
    main()
