"""Smoke run of the retrieval service's main path on a TPU.

Phases A (f32 factors) and B (int8 factors) open the ``sharded`` service
through ``open_retriever``, as ``launch/serve.py --service`` does, over a
seeded catalog of 2^20 unit-norm items at d=64.  Each phase serves a few
hundred microbatched requests with upserts interleaved (so the delta
segment's kernel runs), runs one background compaction to its swap, and
then checks 64 queries two ways: the exact path against the host
brute-force oracle, and the pruned path against the dense (Q, N)-mask
reference.  Ids must be equal except at near ties; a mismatch outside a
near tie fails the run.

``--chips 4`` runs only the four-chip phase instead: the same service on a
``make_index_mesh(4)`` mesh over 2^22 items, so that each chip holds about
what phase A's one chip holds, compared with the same index state on one
device, for the f32 rows and for an int8 slab quantized from them.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU, or when any phase fails, the script exits non-zero and
prints no result.  One process drives every chip it uses.

Usage:
  python chip_smoke.py             # phases A and B on one chip
  python chip_smoke.py --chips 4   # the four-chip phase
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

K = 64                      # factor width of every phase
KAPPA = 10
N_SHARDS = 4
BATCH = 32                  # microbatch rows (one fixed kernel shape)
N_REQUESTS = 320            # served per one-chip phase (half on four chips)
N_CHECK = 64                # queries checked against the references
SEED = 0
#: relative score gap under which two different ids count as a near tie
NEAR_TIE_RTOL = 1e-5


class PhaseFailed(RuntimeError):
    pass


def unit_rows(rng: np.random.Generator, n: int) -> np.ndarray:
    x = rng.standard_normal((n, K), dtype=np.float32)
    return x / np.linalg.norm(x, axis=1, keepdims=True)


def service_spec(quantize: str):
    from repro.core.mapping import GamConfig
    from repro.retriever import RetrieverSpec

    return RetrieverSpec(
        cfg=GamConfig(k=K, scheme="parse_tree", threshold=0.2),
        backend="sharded", n_shards=N_SHARDS, min_overlap=2, kappa=KAPPA,
        batch_size=BATCH, max_delay_s=2e-3, quantize=quantize,
        # a background compaction of 2^20 rows maps them in 16 slices
        options=(("compact_slice_rows", 1 << 16),))


def compare(name: str, users, got_ids, got_scores, ref_ids, ref_scores,
            catalog: dict) -> None:
    """Ids equal except at near ties, judged by the exact f32 score of the
    id the system returned against the reference's score at that slot."""
    got_ids, ref_ids = np.asarray(got_ids), np.asarray(ref_ids)
    got_s = np.asarray(got_scores, np.float32)
    ref_s = np.asarray(ref_scores, np.float32)
    same = got_ids == ref_ids
    real = same & (ref_ids >= 0)
    max_diff = float(np.abs(got_s[real] - ref_s[real]).max(initial=0.0))
    bad = 0
    for q, j in zip(*np.nonzero(~same)):
        gid = int(got_ids[q, j])
        if gid < 0:
            bad += 1
            continue
        true = float(np.dot(catalog[gid], users[q]))
        ref = float(ref_s[q, j])
        if abs(true - ref) > NEAR_TIE_RTOL * max(abs(true), abs(ref)):
            bad += 1
    print(f"  {name}: ids match {int(same.sum())}/{same.size} "
          f"({same.mean():.4%}), near ties {int((~same).sum()) - bad}, "
          f"mismatches {bad}, largest score difference {max_diff:.3e}",
          flush=True)
    if bad:
        raise PhaseFailed(f"{name}: {bad} ids differ outside a near tie "
                          f"(rtol {NEAR_TIE_RTOL})")


def print_memory(devices) -> None:
    for d in devices:
        st = d.memory_stats() or {}
        print(f"  {d}: bytes_in_use={st.get('bytes_in_use')} "
              f"peak_bytes_in_use={st.get('peak_bytes_in_use')}",
              flush=True)


def serve(svc, rng, n_requests: int, *, brute=None, n_items: int,
          compact_at: int | None = None) -> int:
    """Microbatched requests with an upsert pair every 16 requests (one new
    id, one overwrite of a compacted row); returns the requests served."""
    from repro.service.microbatch import QueryResult

    pending = []
    for r in range(n_requests):
        pending.append(svc.batcher.submit(unit_rows(rng, 1)[0]))
        if r % 16 == 15:
            ids = [n_items + r, (r * 7919) % n_items]
            rows = unit_rows(rng, 2)
            svc.upsert(ids, rows)
            if brute is not None:
                brute.upsert(ids, rows)
        if r == compact_at:
            svc.compact(async_=True)
        svc.batcher.poll()
    while svc.batcher.pending:
        svc.batcher.flush()
    outcomes = [svc.batcher.result(p) for p in pending]
    return sum(isinstance(o, QueryResult) for o in outcomes)


def one_chip_phase(label: str, quantize: str, *, n_items: int,
                   n_requests: int, seed: int, devices) -> None:
    import jax
    import jax.numpy as jnp

    from repro.core.mapping import sparse_map
    from repro.retriever import RetrieverSpec, open_retriever

    print(f"phase {label}: sharded service, {n_items} items x d={K}, "
          f"quantize={quantize}", flush=True)
    rng = np.random.default_rng(seed)
    spec = service_spec(quantize)
    t0 = time.monotonic()
    items = unit_rows(rng, n_items)
    svc = open_retriever(spec, items=items)
    brute = open_retriever(RetrieverSpec(cfg=spec.cfg, backend="brute",
                                         kappa=KAPPA), items=items)
    del items
    setup_s = time.monotonic() - t0
    base = svc.base
    caps = base.partition.caps
    spilled = sum(int((np.asarray(base.spills[s]) < caps[s]).sum())
                  for s in range(base.n_shards))
    print(f"  setup_s={setup_s:.2f} (data + index build + upload); "
          f"spill rows {spilled}/{n_items}", flush=True)

    t0 = time.monotonic()
    svc.query(unit_rows(rng, BATCH))
    print(f"  first_query_s={time.monotonic() - t0:.2f} (includes compile)",
          flush=True)
    svc.metrics.reset()

    t0 = time.monotonic()
    served = serve(svc, rng, n_requests, brute=brute, n_items=n_items,
                   compact_at=n_requests // 4)
    steps = 0
    while svc.maintenance_stats()["compaction"]["active"]:
        svc.compaction_step()
        steps += 1
    snap = svc.metrics.snapshot()
    ms = svc.maintenance_stats()
    print(f"  served {served}/{n_requests} requests in "
          f"{time.monotonic() - t0:.2f}s (wall, host clock, includes "
          f"delta recompiles and the compaction); upserts "
          f"{snap['n_upserts']}; generation {ms['generation']} "
          f"({steps} compaction steps after the stream)", flush=True)
    if served != n_requests:
        raise PhaseFailed(f"phase {label}: {n_requests - served} requests "
                          f"not served")
    if ms["generation"] != 1:
        raise PhaseFailed(f"phase {label}: compaction did not swap "
                          f"(generation {ms['generation']})")

    users = unit_rows(rng, N_CHECK)
    got = svc.query(users, KAPPA, exact=True)
    want = brute.query(users, KAPPA)
    compare("exact path vs host brute oracle", users, got.ids, got.scores,
            want.ids, want.scores, svc.catalog)

    base = svc.base
    users_j = jnp.asarray(users)
    tau, vals = sparse_map(users_j, spec.cfg)
    q_mask = vals != 0.0
    g = base.query(users_j, tau, q_mask, KAPPA)
    w = base.query_dense_reference(users_j, tau, q_mask, KAPPA)
    compare("pruned path vs dense reference", users,
            base.rows_to_ids(g.rows, g.scores), g.scores,
            base.rows_to_ids(w.rows, w.scores), w.scores, svc.catalog)
    jax.block_until_ready(got.scores)
    print_memory(devices)
    del svc, brute, base, g, w
    gc.collect()


def on_one_device(base, device):
    """The same built index state as ``base`` (a mesh-partitioned single
    group), gathered onto one device."""
    import jax

    from repro.service.sharded_index import ShardedGamIndex

    meta = base.metas[0]
    moved = {f.name: jax.device_put(getattr(meta, f.name), device)
             for f in dataclasses.fields(meta)
             if isinstance(getattr(meta, f.name), jax.Array)}
    return ShardedGamIndex(
        base.cfg, base.item_ids, base.postings, base.counts, base.spills,
        jax.device_put(base.factors_g[0], device),
        np.asarray(base.alive_g[0]), base.partition, base.min_overlap,
        base.bucket, None, [dataclasses.replace(meta, **moved)],
        quantize=base.quantize, rerank_factor=base.rerank_factor)


def four_chip_phase(*, n_items: int, n_requests: int, seed: int,
                    devices) -> None:
    import jax.numpy as jnp

    from repro.core.mapping import sparse_map
    from repro.launch.mesh import make_index_mesh
    from repro.retriever import open_retriever
    from repro.service.sharded_index import ShardedGamIndex

    print(f"four-chip phase: sharded service on make_index_mesh(4), "
          f"{n_items} items x d={K}", flush=True)
    rng = np.random.default_rng(seed)
    spec = service_spec("none")
    mesh = make_index_mesh(4)
    t0 = time.monotonic()
    items = unit_rows(rng, n_items)
    svc = open_retriever(spec, items=items, mesh=mesh)
    del items
    print(f"  setup_s={time.monotonic() - t0:.2f} (data + index build + "
          f"placement)", flush=True)
    base = svc.base
    meta = base.metas[0]
    for name, arr in (("factors", base.factors_g[0]),
                      ("alive", base.alive_g[0]),
                      ("item_bits_t", meta.item_bits_t),
                      ("block_union", meta.block_union)):
        where = ", ".join(f"{sh.device.id}:{tuple(sh.data.shape)}"
                          for sh in sorted(arr.addressable_shards,
                                           key=lambda sh: sh.device.id))
        print(f"  {name} {tuple(arr.shape)} shards {where}", flush=True)
    del meta          # upserts replace it; a stale copy would stay resident
    print_memory(mesh.devices.flat)

    t0 = time.monotonic()
    svc.query(unit_rows(rng, BATCH))
    print(f"  first_query_s={time.monotonic() - t0:.2f} (includes compile)",
          flush=True)
    served = serve(svc, rng, n_requests, n_items=n_items)
    print(f"  served {served}/{n_requests} requests, each base query one "
          f"kernel launch per device of "
          f"{sorted(d.id for d in base.factors_g[0].sharding.device_set)}",
          flush=True)
    if served != n_requests:
        raise PhaseFailed(f"{n_requests - served} requests not served")

    users = unit_rows(rng, N_CHECK)
    users_j = jnp.asarray(users)
    tau, vals = sparse_map(users_j, spec.cfg)
    q_mask = vals != 0.0
    # the served f32 index, then the int8 slab quantized from its rows
    base = svc.base
    for label in ("f32", "int8"):
        if label == "int8":
            base = ShardedGamIndex(
                base.cfg, base.item_ids, base.postings, base.counts,
                base.spills, base.factors_g[0], np.asarray(base.alive_g[0]),
                base.partition, base.min_overlap, base.bucket, mesh,
                list(base.metas), quantize="int8",
                rerank_factor=base.rerank_factor)
            q8 = base.metas[0].factors_q
            print(f"  int8 factors_q {tuple(q8.shape)} on devices "
                  f"{sorted(d.id for d in q8.sharding.device_set)}",
                  flush=True)
        one = on_one_device(base, devices[0])
        for exact in (False, True):
            g = base.query(users_j, tau, q_mask, KAPPA, exact=exact)
            w = one.query(users_j, tau, q_mask, KAPPA, exact=exact)
            compare(f"{label} {'exact' if exact else 'pruned'} path, four "
                    f"chips vs one", users,
                    base.rows_to_ids(g.rows, g.scores), g.scores,
                    base.rows_to_ids(w.rows, w.scores), w.scores, svc.catalog)
            if not np.array_equal(g.shard_candidates, w.shard_candidates):
                raise PhaseFailed("candidate counts differ between the "
                                  "layouts")
        print_memory(mesh.devices.flat)
        del one, g, w
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip mesh phase")
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX found {len(devices)}", file=sys.stderr)
        return 1
    print(f"devices: {len(devices)} x {devices[0].device_kind}", flush=True)
    t0 = time.monotonic()
    try:
        if args.chips == 4:
            four_chip_phase(n_items=1 << 22, n_requests=N_REQUESTS // 2,
                            seed=SEED, devices=devices)
        else:
            one_chip_phase("A", "none", n_items=1 << 20,
                           n_requests=N_REQUESTS, seed=SEED,
                           devices=devices[:1])
            one_chip_phase("B", "int8", n_items=1 << 20,
                           n_requests=N_REQUESTS, seed=SEED + 1,
                           devices=devices[:1])
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(f"total_s={time.monotonic() - t0:.2f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
