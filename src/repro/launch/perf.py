"""Perf hillclimbing driver (§Perf): lower+compile named optimization
variants for a (arch, shape) pair and report the three roofline terms for
each, so the hypothesis -> change -> measure loop is fully scripted.

Variants (composable by '+'):
  baseline       the paper-faithful configuration as shipped
  attn_bf16      bf16 score/softmax tensors (attn_f32=False)
  truncate       causal KV truncation per q-chunk (attn_truncate=True)
  tp_only        no FSDP weight sharding (params TP-only; opt stays ZeRO)
  remat_dots     checkpoint_dots remat policy
  remat_none     no remat
  qchunk512/2048 blockwise attention chunk size
  cap10          MoE capacity factor 1.0 (from 1.25)
  gam_head       decode only: GAM-accelerated LM head (coarse int8 pattern
                 prefilter + candidate-budget exact scoring)

Usage:
  PYTHONPATH=src python -m repro.launch.perf --arch qwen2-1.5b \
      --shape prefill_32k --variants baseline,attn_bf16,attn_bf16+truncate
"""
import argparse
import json
import os

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.configs.registry import ARCH_IDS, get_config
from repro.configs.shapes import SHAPES
from repro.launch.dryrun import (build_lowered, collective_bytes,
                                 cost_analysis_dict)
from repro.launch.mesh import force_host_devices, make_production_mesh
from repro.launch.roofline import (
    HBM_BW, ICI_BW, PEAK_FLOPS, _with_layers, model_flops)
from repro.launch.steps import (
    abstract_cache, abstract_params, gam_head_inputs, make_gam_serve_step,
    shape_adapted_config,
)
from repro.models.model import Model
from repro.sharding.specs import batch_specs, cache_specs, param_shardings

__all__ = ["apply_variant", "measure", "main"]


def apply_variant(cfg: ModelConfig, variant: str) -> tuple[ModelConfig, dict]:
    extra = {"gam_head": False, "mesh1": False}
    for tok in variant.split("+"):
        if tok == "baseline":
            continue
        elif tok == "attn_bf16":
            cfg = cfg.with_(attn_f32=False)
        elif tok == "truncate":
            cfg = cfg.with_(attn_truncate=True)
        elif tok == "tp_only":
            cfg = cfg.with_(fsdp=False)
        elif tok == "remat_dots":
            cfg = cfg.with_(remat="dots")
        elif tok == "remat_none":
            cfg = cfg.with_(remat="none")
        elif tok.startswith("qchunk"):
            cfg = cfg.with_(q_chunk=int(tok[len("qchunk"):]))
        elif tok == "cap10":
            cfg = cfg.with_(capacity_factor=1.0)
        elif tok == "ssm_rep":
            cfg = cfg.with_(spec_overrides=(
                (r"\['(in_proj|out_proj|conv_[wb])'\]", "replicate"),))
        elif tok == "gam_head":
            extra["gam_head"] = True
        elif tok == "mesh1":
            extra["mesh1"] = True
        else:
            raise ValueError(f"unknown variant token {tok!r}")
    return cfg, extra


def build_gam_lowered(cfg: ModelConfig, shape, mesh, *, coarse_k=128,
                      budget=16_384):
    """serve_step with the GAM LM head (decode shapes only)."""
    cfg = shape_adapted_config(cfg, shape)
    model = Model(cfg)
    params_sds = abstract_params(model)
    p_shard = param_shardings(mesh, params_sds, fsdp=cfg.fsdp,
                              overrides=cfg.spec_overrides)
    cache_sds = abstract_cache(model, shape.global_batch, shape.seq_len)
    c_shard = cache_specs(cfg, mesh, cache_sds,
                          seq_shard=shape.global_batch == 1)
    gam_sds = gam_head_inputs(cfg)
    g_shard = {
        "patterns": NamedSharding(mesh, P(None, "model")),
        "inv_sqrt_nnz": NamedSharding(mesh, P("model")),
    }
    tok_sds = jax.ShapeDtypeStruct((shape.global_batch, 1), "int32")
    t_shard = batch_specs(cfg, mesh, tok_sds)
    step = make_gam_serve_step(model, coarse_k=coarse_k, budget=budget)
    with mesh:
        jitted = jax.jit(step, in_shardings=(p_shard, g_shard, c_shard,
                                             t_shard), donate_argnums=(2,))
        return jitted.lower(params_sds, gam_sds, cache_sds, tok_sds)


def _probe(cfg, shape, mesh, *, gam_head=False):
    def build(c):
        return (build_gam_lowered(c, shape, mesh) if gam_head
                else build_lowered(c, shape, mesh))
    compiled = build(cfg).compile()
    cost = cost_analysis_dict(compiled)
    coll = collective_bytes(compiled.as_text())
    mem = compiled.memory_analysis()
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": sum(coll.values()),
            "peak": mem.peak_memory_in_bytes,
            "arg": mem.argument_size_in_bytes}


def measure(arch: str, shape_name: str, variant: str, *,
            multi_pod: bool = False) -> dict:
    shape = SHAPES[shape_name]
    cfg, extra = apply_variant(get_config(arch), variant)
    if extra.pop("mesh1", False):
        # the paper's serving regime: single-chip (or few-chip) deployment
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(jax.sharding.AxisType.Auto,) * 2)
        chips = 1
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
        chips = 512 if multi_pod else 256

    from repro.launch.roofline import _probe_layers
    l1, l2 = _probe_layers(cfg)
    c1 = _probe(_with_layers(cfg, l1), shape, mesh, **extra)
    c2 = _probe(_with_layers(cfg, l2), shape, mesh, **extra)
    scale = (cfg.n_layers - l1) / (l2 - l1)

    def extrap(key):
        return max(c1[key] + scale * (c2[key] - c1[key]), 0.0)

    flops_g = extrap("flops") * chips
    bytes_g = extrap("bytes") * chips
    coll_g = extrap("coll") * chips
    terms = {
        "compute": flops_g / (chips * PEAK_FLOPS),
        "memory": bytes_g / (chips * HBM_BW),
        "collective": coll_g / (chips * ICI_BW),
    }
    mf = model_flops(cfg, shape)
    return {
        "arch": arch, "shape": shape_name, "variant": variant,
        "t_compute_s": terms["compute"], "t_memory_s": terms["memory"],
        "t_collective_s": terms["collective"],
        "dominant": max(terms, key=terms.get),
        "useful_ratio": mf / max(flops_g, 1.0),
    }


def main():
    force_host_devices(512)
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--shape", choices=tuple(SHAPES), required=True)
    ap.add_argument("--variants", required=True)
    ap.add_argument("--out", default="results/perf.json")
    args = ap.parse_args()
    results = []
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)
    for variant in args.variants.split(","):
        key = (args.arch, args.shape, variant)
        if any((r["arch"], r["shape"], r["variant"]) == key for r in results):
            print(f"-- cached {key}")
            continue
        rec = measure(args.arch, args.shape, variant)
        print(f"{args.arch} x {args.shape} [{variant}]: "
              f"compute={rec['t_compute_s']:.3e} "
              f"memory={rec['t_memory_s']:.3e} "
              f"coll={rec['t_collective_s']:.3e} dom={rec['dominant']} "
              f"useful={rec['useful_ratio']:.3f}")
        results.append(rec)
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)


if __name__ == "__main__":
    main()
