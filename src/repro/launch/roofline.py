"""Roofline analysis from the compiled dry-run artifacts (TPU v5e target).

Terms (per arch x shape x mesh), all derived WITHOUT hardware:
  compute    = HLO_FLOPs_global  / (chips * 197e12  bf16 FLOP/s)
  memory     = HLO_bytes_global  / (chips * 819e9   B/s HBM)
  collective = coll_bytes_global / (chips * 50e9    B/s ICI link)

Caveat handled here: XLA's cost analysis counts a while-loop (scan) body
ONCE, not x trip-count.  We therefore compile each pair three times — the
true layer count L (memory + collective schedule), and probe layer counts
L1 < L2 — and extrapolate:  cost(L) = cost(L1) + (L - L1)/(L2 - L1) *
(cost(L2) - cost(L1)).  Scan bodies are homogeneous so this is exact up to
the non-loop prologue (embed/unembed), which the affine fit captures.

MODEL_FLOPS = 6 * N(active) * D tokens (train; 2ND for single-token decode
per sequence) — the usefulness ratio MODEL_FLOPS / HLO_FLOPs catches
remat/redundancy waste.
"""
import argparse
import json
import os

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES
from repro.launch.dryrun import (SKIPS, build_lowered, collective_bytes,
                                 cost_analysis_dict)
from repro.launch.mesh import force_host_devices, make_production_mesh

PEAK_FLOPS = 197e12      # bf16 / chip
HBM_BW = 819e9           # B/s / chip
ICI_BW = 50e9            # B/s / link

__all__ = ["roofline_for", "model_flops", "main"]


def _probe_layers(cfg: ModelConfig) -> tuple[int, int]:
    if cfg.family == "hybrid":
        return 3, 6          # one and two period-3 groups
    if cfg.family == "encdec":
        return 1, 2
    return 1, 2


def _with_layers(cfg: ModelConfig, n: int) -> ModelConfig:
    """Probe config: n layers, UNROLLED (scan bodies are cost-counted once by
    XLA, so per-layer marginal costs require unrolling), and the blockwise
    q-chunk scan disabled for the same reason (single-chunk attention)."""
    kw = {"n_layers": n, "scan_layers": False}
    if cfg.family == "encdec":
        kw["n_encoder_layers"] = n
    return cfg.with_(**kw)


def _costs(cfg, shape_name, mesh):
    shape = SHAPES[shape_name]
    lowered = build_lowered(cfg, shape, mesh)
    compiled = lowered.compile()
    cost = cost_analysis_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    return {
        "flops": cost.get("flops", 0.0),
        "bytes": cost.get("bytes accessed", 0.0),
        "coll": sum(coll.values()),
        "coll_by_kind": coll,
        "mem": {
            "argument": mem.argument_size_in_bytes,
            "temp": mem.temp_size_in_bytes,
            "peak": mem.peak_memory_in_bytes,
        },
    }


def model_flops(cfg: ModelConfig, shape) -> float:
    """Analytic useful FLOPs (global): 6*N_active*D train, 2*N_active*B decode."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token/sequence


def roofline_for(arch: str, shape_name: str, *, multi_pod: bool = False,
                 cfg_override=None) -> dict:
    if (arch, shape_name) in SKIPS:
        return {"arch": arch, "shape": shape_name, "status": "skip",
                "reason": SKIPS[(arch, shape_name)]}
    cfg = cfg_override or get_config(arch)
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = 512 if multi_pod else 256
    shape = SHAPES[shape_name]

    l1, l2 = _probe_layers(cfg)
    full = _costs(cfg, shape_name, mesh)
    c1 = _costs(_with_layers(cfg, l1), shape_name, mesh)
    c2 = _costs(_with_layers(cfg, l2), shape_name, mesh)

    layers_eff = cfg.n_layers
    scale = (layers_eff - l1) / (l2 - l1)

    def extrap(key):
        return max(c1[key] + scale * (c2[key] - c1[key]), 0.0)

    flops_dev = extrap("flops")
    bytes_dev = extrap("bytes")
    coll_dev = extrap("coll")
    flops_global = flops_dev * chips
    bytes_global = bytes_dev * chips
    coll_global = coll_dev * chips

    t_compute = flops_global / (chips * PEAK_FLOPS)
    t_memory = bytes_global / (chips * HBM_BW)
    t_coll = coll_global / (chips * ICI_BW)
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": chips,
        "status": "ok",
        "flops_global": flops_global,
        "bytes_global": bytes_global,
        "coll_global": coll_global,
        "coll_by_kind_body": full["coll_by_kind"],
        "t_compute_s": t_compute,
        "t_memory_s": t_memory,
        "t_collective_s": t_coll,
        "dominant": dominant,
        "model_flops": mf,
        "useful_ratio": mf / max(flops_global, 1.0),
        "mem_per_device": full["mem"],
    }


def main():
    force_host_devices(512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out", default="results/roofline.json")
    args = ap.parse_args()

    pairs = ([(args.arch, args.shape)] if not args.all else
             [(a, s) for a in ARCH_IDS for s in SHAPES])
    results = []
    if args.out and os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    done = {(r["arch"], r["shape"]) for r in results
            if r.get("status") in ("ok", "skip")}
    for arch, shape in pairs:
        if (arch, shape) in done:
            print(f"-- cached {arch} x {shape}")
            continue
        try:
            rec = roofline_for(arch, shape, multi_pod=args.multi_pod)
        except Exception as e:
            import traceback
            traceback.print_exc()
            rec = {"arch": arch, "shape": shape, "status": "FAIL",
                   "error": str(e)}
        if rec.get("status") == "ok":
            print(f"{arch:18s} {shape:12s} compute={rec['t_compute_s']:.3e}s "
                  f"memory={rec['t_memory_s']:.3e}s "
                  f"coll={rec['t_collective_s']:.3e}s "
                  f"dom={rec['dominant']:10s} "
                  f"useful={rec['useful_ratio']:.2f}")
        else:
            print(f"{arch} {shape}: {rec['status']}")
        results = [r for r in results
                   if not (r["arch"] == arch and r["shape"] == shape)]
        results.append(rec)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
