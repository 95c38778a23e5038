"""The system under test, opened as ``launch/serve.py --service`` opens it.

This is the only module of the benchmark that imports the program: the
``sharded`` retriever through ``open_retriever``, its microbatcher, the
program's own tracer for the per-layer spans, and where the program keeps
its compiled programs.
"""
from __future__ import annotations

import sys

import jax

from chipbench.cell import ROOT

sys.path.insert(0, str(ROOT / "src"))

from repro.core.mapping import GamConfig  # noqa: E402
from repro.launch import compile_cache  # noqa: E402
from repro.obs.tracing import Tracer  # noqa: E402
from repro.retriever import RetrieverSpec, open_retriever  # noqa: E402
from repro.service.microbatch import QueryResult  # noqa: E402

__all__ = ["QueryResult", "enable_compile_cache", "open_service",
           "spill_rows"]


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


def open_service(config: dict, items, *, traced: bool):
    """Build the service over ``items``; with ``traced`` the program's
    tracer records every request batch (its ``query``, ``map``, ``base``,
    ``delta`` and ``merge`` spans)."""
    kw = ({"tracer": Tracer(sample_rate=1.0, max_traces=1 << 20)}
          if traced else {})
    return open_retriever(spec_of(config), items=items, **kw)


def spill_rows(svc) -> int:
    """Rows of the main segment that overflowed a posting bucket: they
    would be candidates for every query, whatever their pattern."""
    base = svc.base
    return sum(int((base.spills[s] < base.partition.caps[s]).sum())
               for s in range(base.n_shards))
