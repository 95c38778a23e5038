"""The system under test, opened as ``launch/serve.py --service`` opens it,
and on several chips as one process serves them (``chip_smoke.py --chips
4``): one index over ``make_index_mesh``.

This is the only module of the benchmark that imports the program: the
``sharded`` retriever through ``open_retriever``, its microbatcher, the
program's own tracer for the per-layer spans, the mesh, and where the
program keeps its compiled programs.
"""
from __future__ import annotations

import sys

import jax

from chipbench.cell import ROOT

sys.path.insert(0, str(ROOT / "src"))

from repro.core.mapping import GamConfig  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.launch.mesh import make_index_mesh  # noqa: E402
from repro.obs.tracing import Tracer  # noqa: E402
from repro.retriever import RetrieverSpec, open_retriever  # noqa: E402
from repro.service.microbatch import QueryResult  # noqa: E402

__all__ = ["QueryResult", "cell_devices", "enable_compile_cache",
           "kernel_placement", "open_service", "spill_rows"]


def enable_compile_cache() -> str:
    """The program's persistent compilation cache
    (``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``),
    made to keep every program.
    JAX's default leaves out programs that compile in under a second, and
    each run would then compile those again in its set-up."""
    path = compile_cache.enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def spec_of(config: dict) -> RetrieverSpec:
    g, s = config["gam"], config["service"]
    return RetrieverSpec(
        cfg=GamConfig(k=int(config["dim"]), scheme=g["scheme"],
                      threshold=float(g["threshold"])),
        backend="sharded", min_overlap=int(g["min_overlap"]),
        kappa=int(g["kappa"]), bucket=int(s["bucket"]),
        delta_bucket=int(s["delta_bucket"]), n_shards=int(s["n_shards"]),
        batch_size=int(s["batch_size"]), max_delay_s=float(s["max_delay_s"]),
        bn=s["bn"], bq=int(s["bq"]), quantize=s["quantize"],
        rerank_factor=int(s["rerank_factor"]),
        cache_capacity=int(s["cache_capacity"]))


def open_service(config: dict, items, *, traced: bool, chips: int):
    """Build the service over ``items``; on ``chips`` > 1 its main segment
    is partitioned over ``make_index_mesh(chips)`` (the first ``chips``
    devices), and each batch launches the kernel once per device.  With
    ``traced`` the program's tracer records every request batch (its
    ``query``, ``map``, ``base``, ``delta`` and ``merge`` spans)."""
    kw = ({"tracer": Tracer(sample_rate=1.0, max_traces=1 << 20)}
          if traced else {})
    if chips > 1:
        kw["mesh"] = make_index_mesh(chips)
    return open_retriever(spec_of(config), items=items, **kw)


def kernel_placement(svc) -> tuple[dict, int]:
    """-> (the ids of the devices that hold each piece of the main
    segment's kernel state: the factor slab, for int8 the int8 slab too,
    and the packed patterns; the kernel launches a batch makes over it)."""
    base = svc.base
    meta = base.metas[0]
    held = {"factor slab": base.factors_g[0],
            "item_bits_t": meta.item_bits_t}
    if meta.quantize == "int8":
        held["int8 slab"] = meta.factors_q
    return ({k: sorted(d.id for d in x.sharding.device_set)
             for k, x in held.items()}, len(base._launch_units(0)))


def cell_devices(svc, devices) -> list:
    """The devices a cell runs on, in mesh order: the mesh's, or the first
    device where the service has none."""
    return list(svc.mesh.devices.flat) if svc.mesh is not None \
        else list(devices[:1])


def spill_rows(svc) -> int:
    """Rows of the main segment that overflowed a posting bucket: they
    would be candidates for every query, whatever their pattern."""
    base = svc.base
    return sum(int((base.spills[s] < base.partition.caps[s]).sum())
               for s in range(base.n_shards))
