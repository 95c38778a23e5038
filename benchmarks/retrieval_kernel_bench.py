"""Fused streaming retrieval kernel vs. the dense-mask baseline.

Times the serving hot loop both ways on a cluster-sorted catalog (the layout
block-skipping is designed for — contiguous id ranges with coherent sparsity
patterns, i.e. a compacted production catalog):

  * baseline — the superseded path: ``DeviceIndex.batch_candidate_mask``
    materialises the (Q, N) bool mask, ``gam_score`` writes the (Q, N) masked
    score tensor, ``lax.top_k`` reduces it;
  * fused    — one ``gam_retrieve`` call: per-tile candidate overlap from
    packed pattern bitsets, zero-candidate blocks skipped via the
    union-popcount prepass, on-chip running top-kappa, O(Q*kappa) HBM out.

The discard fraction is swept via ``min_overlap``; posting buckets are sized
to the longest posting list so spill never inflates the candidate set and the
measured discard reflects true pruning.  Each point records wall time for
both paths, the scored-tile fraction from the block prepass, and recall
parity (fused ids must equal the dense ids bit-for-bit).

A second sweep records the **memory-vs-recall frontier** of the compressed
catalog representations (``docs/compression.md``): for f32 / int8 (at two
re-rank pool sizes) / int8 + varint-compressed postings, the bytes per item
with a component breakdown (factors, posting structure, pattern bitsets),
recall@kappa against the brute oracle on both the pruned and the
exact-rerank path, and the served query latency.  The regression gate pins
``>= 4x`` items-per-byte at exact-path recall parity on the compressed
setting.

Run:  PYTHONPATH=src python benchmarks/retrieval_kernel_bench.py [--tiny]
Writes BENCH_retrieval.json.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress import (encode_postings, pattern_dict_encode,
                            pattern_dict_nbytes)
from repro.core.inverted_index import table_to_csr
from repro.core.mapping import GamConfig, sparse_map
from repro.core.retrieval import masked_topk
from repro.kernels.gam_score import NEG
from repro.kernels.ops import gam_retrieve
from repro.launch.compile_cache import enable_compile_cache
from repro.retriever import RetrieverSpec, open_retriever


def clustered_catalog(n: int, k: int, n_clusters: int, sigma: float,
                      seed: int) -> tuple[np.ndarray, np.ndarray]:
    """Cluster-sorted items + queries drawn around the same centers."""
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(n_clusters, k)).astype(np.float32)
    centers /= np.linalg.norm(centers, axis=1, keepdims=True)
    per = -(-n // n_clusters)
    items = (np.repeat(centers, per, axis=0)[:n]
             + sigma * rng.normal(size=(n, k)).astype(np.float32))
    items /= np.linalg.norm(items, axis=1, keepdims=True)
    return items, centers


def _time(fn, reps: int) -> float:
    fn()                                   # compile + warm
    best = np.inf
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def run_point(items: np.ndarray, users: np.ndarray, cfg: GamConfig, *,
              kappa: int, min_overlap: int, bn: int | None, bq: int | None,
              reps: int) -> dict:
    n = items.shape[0]
    nq = users.shape[0]
    # auto tile sizing: keep the (Q/bq)*(N/bn) grid small enough that
    # per-cell overhead (the dominant cost in interpret mode) stays bounded,
    # growing bq before bn so block skipping keeps its granularity
    if bn is None:
        bn = 512 if n <= 32768 else 1024
    if bq is None:
        q_blocks = max(1, 256 // max(1, n // bn))
        per_block = -(-nq // q_blocks)
        bq = -(-per_block // 8) * 8
    tau, vals = sparse_map(jnp.asarray(items), cfg)
    tau, mask = np.asarray(tau), np.asarray(vals) != 0.0
    q_tau, q_vals = sparse_map(jnp.asarray(users), cfg)
    q_tau, q_mask = np.asarray(q_tau), np.asarray(q_vals) != 0.0
    # bucket = longest posting list: zero spill, discard == true pruning
    bucket = int(np.bincount(tau[mask].ravel(), minlength=cfg.p).max())
    # the unified API owns index + kernel metadata construction; the timed
    # closures below call the kernel directly against the backend's state so
    # the measurement stays query-mapping-free on both paths
    retriever = open_retriever(
        RetrieverSpec(cfg=cfg, backend="gam-device", min_overlap=min_overlap,
                      bucket=bucket, bn=bn, bq=bq),
        items=items)
    dev = retriever.device_index
    meta = retriever._retrieve_meta
    users_j, items_j = jnp.asarray(users), jnp.asarray(items)
    q_tau_j, q_mask_j = jnp.asarray(q_tau), jnp.asarray(q_mask)

    def baseline():
        masks = dev.batch_candidate_mask(q_tau_j, min_overlap, q_mask_j)
        vals, ids = masked_topk(users_j, items_j, masks, kappa)
        jax.block_until_ready((vals, ids))
        return vals, ids

    def fused():
        res = gam_retrieve(users_j, items_j, q_tau_j, q_mask_j, meta, kappa,
                           min_overlap=min_overlap, bq=bq)
        jax.block_until_ready(res)
        return res

    b_vals, b_ids = baseline()
    res = fused()
    b_vals, b_ids = np.asarray(b_vals), np.asarray(b_ids)
    b_ids = np.where(b_vals <= NEG / 2, -1, b_ids)
    parity = bool(np.array_equal(np.asarray(res.rows), b_ids))
    n_cand = np.asarray(res.blk_counts).sum(1)

    base_s = _time(lambda: baseline(), reps)
    fused_s = _time(lambda: fused(), reps)
    return {
        "n_items": n,
        "n_queries": int(users.shape[0]),
        "kappa": kappa,
        "min_overlap": min_overlap,
        "bucket": bucket,
        "discard_frac": float(1.0 - n_cand.mean() / n),
        "scored_tile_frac": float(1.0 - np.asarray(res.skipped).mean()),
        "baseline_ms": base_s * 1e3,
        "fused_ms": fused_s * 1e3,
        "speedup": base_s / fused_s,
        "recall_parity": parity,
    }


# ------------------------------------------------- memory-recall frontier

# the four catalog representations the serving tier can hold; "f32" is the
# uncompressed reference every ratio is against
FRONTIER_SETTINGS = (
    {"name": "f32", "quantize": "none", "rerank_factor": 4,
     "compress_postings": False},
    {"name": "int8_r2", "quantize": "int8", "rerank_factor": 2,
     "compress_postings": False},
    {"name": "int8_r4", "quantize": "int8", "rerank_factor": 4,
     "compress_postings": False},
    {"name": "int8_r4_compressed", "quantize": "int8", "rerank_factor": 4,
     "compress_postings": True},
)


def catalog_bytes(retriever, compressed: bool) -> dict:
    """Serving-state footprint by component, measured off the actual arrays
    (what a snapshot of this representation carries)."""
    meta = retriever._retrieve_meta
    n = retriever.n_items
    if meta.quantize == "int8":
        factor_bytes = int(np.asarray(meta.factors_q).nbytes
                           + np.asarray(meta.scales).nbytes)
    else:
        factor_bytes = int(retriever.items.nbytes)
    table = np.asarray(retriever.device_index.table)
    counts = np.asarray(retriever.device_index.counts)
    if compressed:
        index_bytes = int(encode_postings(*table_to_csr(table,
                                                        counts)).nbytes)
        bits = np.ascontiguousarray(np.asarray(meta.item_bits_t).T[:n])
        pattern_bytes = pattern_dict_nbytes(*pattern_dict_encode(bits))
    else:
        index_bytes = int(table.nbytes + counts.nbytes)
        pattern_bytes = int(np.asarray(meta.item_bits_t).nbytes)
    total = factor_bytes + index_bytes + pattern_bytes
    return {"factor_bytes": factor_bytes, "index_bytes": index_bytes,
            "pattern_bytes": pattern_bytes, "total_bytes": total,
            "bytes_per_item": total / n}


def run_frontier(items: np.ndarray, users: np.ndarray, cfg: GamConfig, *,
                 kappa: int, min_overlap: int, reps: int) -> list[dict]:
    """One point per FRONTIER_SETTINGS entry over the same catalog."""
    oracle = open_retriever(RetrieverSpec(cfg=cfg, backend="brute"),
                            items=items)
    o_ids = np.asarray(oracle.query(users, kappa).ids)
    tau, vals = sparse_map(jnp.asarray(items), cfg)
    tau, mask = np.asarray(tau), np.asarray(vals) != 0.0
    bucket = int(np.bincount(tau[mask].ravel(), minlength=cfg.p).max())

    def recall(ids: np.ndarray) -> float:
        return float(np.mean([np.isin(ids[qi], o_ids[qi]).mean()
                              for qi in range(o_ids.shape[0])]))

    points = []
    for s in FRONTIER_SETTINGS:
        spec = RetrieverSpec(cfg=cfg, backend="gam-device",
                             min_overlap=min_overlap, bucket=bucket,
                             quantize=s["quantize"],
                             rerank_factor=s["rerank_factor"],
                             compress_postings=s["compress_postings"])
        retriever = open_retriever(spec, items=items)
        pruned = np.asarray(retriever.query(users, kappa).ids)
        exact = np.asarray(retriever.query(users, kappa, exact=True).ids)
        lat_s = _time(lambda: retriever.query(users, kappa), reps)
        points.append({
            "name": s["name"],
            "quantize": s["quantize"],
            "rerank_factor": s["rerank_factor"],
            "compress_postings": s["compress_postings"],
            "recall_at_kappa": recall(pruned),
            "recall_exact_path": recall(exact),
            "query_ms": lat_s * 1e3,
            **catalog_bytes(retriever, s["compress_postings"]),
        })
    return points


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--items", type=int, nargs="+",
                    default=[8192, 32768, 131072])
    ap.add_argument("--queries", type=int, default=32)
    ap.add_argument("--dim", type=int, default=16)
    ap.add_argument("--kappa", type=int, default=10)
    ap.add_argument("--min-overlap", type=int, nargs="+", default=[2, 3, 4])
    ap.add_argument("--clusters", type=int, default=64)
    ap.add_argument("--sigma", type=float, default=0.05)
    ap.add_argument("--threshold", type=float, default=0.2)
    ap.add_argument("--bn", type=int, default=None,
                    help="item-block width (default: auto per catalog size)")
    ap.add_argument("--bq", type=int, default=None,
                    help="query-block height (default: auto)")
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke: one small catalog, one sweep point")
    ap.add_argument("--out", default="BENCH_retrieval.json")
    args = ap.parse_args(argv)
    if args.tiny:
        args.items, args.min_overlap = [2048], [3]
        args.queries, args.reps, args.bn, args.bq = 8, 1, 128, 8

    cfg = GamConfig(k=args.dim, scheme="parse_tree", threshold=args.threshold)
    rng = np.random.default_rng(0)
    points = []
    print("n_items,min_overlap,discard,scored_tiles,baseline_ms,fused_ms,"
          "speedup,parity")
    for n in args.items:
        items, centers = clustered_catalog(n, args.dim, args.clusters,
                                           args.sigma, seed=n)
        # queries sorted by home cluster: coherent query blocks, the regime
        # the per-tile skip bound is designed for (locality-batched traffic)
        sel = np.sort(rng.integers(0, len(centers), args.queries))
        users = centers[sel] + args.sigma * rng.normal(
            size=(args.queries, args.dim)).astype(np.float32)
        users /= np.linalg.norm(users, axis=1, keepdims=True)
        for mo in args.min_overlap:
            pt = run_point(items, users, cfg, kappa=args.kappa,
                           min_overlap=mo, bn=args.bn, bq=args.bq,
                           reps=args.reps)
            points.append(pt)
            print(f"{pt['n_items']},{mo},{pt['discard_frac']:.3f},"
                  f"{pt['scored_tile_frac']:.3f},{pt['baseline_ms']:.1f},"
                  f"{pt['fused_ms']:.1f},{pt['speedup']:.2f},"
                  f"{pt['recall_parity']}")

    # memory-vs-recall frontier on the smallest catalog of the sweep (the
    # representation ratios are size-stable; the big sizes only add wall
    # time), at the loosest min_overlap so pruning recall is representative
    n_f = min(args.items)
    items, centers = clustered_catalog(n_f, args.dim, args.clusters,
                                       args.sigma, seed=n_f)
    sel = np.sort(rng.integers(0, len(centers), args.queries))
    users = centers[sel] + args.sigma * rng.normal(
        size=(args.queries, args.dim)).astype(np.float32)
    users /= np.linalg.norm(users, axis=1, keepdims=True)
    frontier = run_frontier(items, users, cfg, kappa=args.kappa,
                            min_overlap=min(args.min_overlap),
                            reps=args.reps)
    f32 = frontier[0]
    print("frontier: name,bytes/item,x_items_per_byte,recall,recall_exact,"
          "query_ms")
    for pt in frontier:
        print(f"{pt['name']},{pt['bytes_per_item']:.1f},"
              f"{f32['bytes_per_item'] / pt['bytes_per_item']:.2f},"
              f"{pt['recall_at_kappa']:.3f},{pt['recall_exact_path']:.3f},"
              f"{pt['query_ms']:.1f}")

    out = {
        "backend": jax.default_backend(),
        "config": {
            "dim": args.dim, "kappa": args.kappa, "queries": args.queries,
            "clusters": args.clusters, "sigma": args.sigma,
            "threshold": args.threshold, "bn": args.bn, "bq": args.bq,
        },
        "points": points,
        "frontier": {"n_items": n_f, "kappa": args.kappa,
                     "min_overlap": min(args.min_overlap),
                     "points": frontier},
    }
    with open(args.out, "w") as f:
        json.dump(out, f, indent=2)
    print(f"wrote {args.out}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
