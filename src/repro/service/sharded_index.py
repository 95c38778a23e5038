"""Item-axis sharded GAM index: the service's main (compacted) segment.

The catalog is sorted by item id and partitioned contiguously according to a
:class:`~repro.service.repartition.Partition` — per-shard row counts, padded
caps and fused-kernel block widths ``bn``.  The default
``Partition.uniform`` reproduces the legacy equal-cut layout (one shared cap
rounded up to whole kernel blocks, pads at the catalog tail); a skew-aware
partition from the :class:`~repro.service.repartition.Repartitioner` may
instead cut hot regions into short shards with narrow blocks.  Each shard
owns a dense-bucket posting segment over LOCAL row ids (built with
``core.inverted_index.build_segment``) — kept for posting-load stats and as
the source of the bucket-spill flags — while the query path streams the flat
factor matrix through the fused ``kernels.gam_retrieve`` kernel: per-tile
candidate overlap from packed pattern bitsets, zero-candidate blocks skipped
via the block-union prepass, and an on-chip running top-kappa, so no (Q, N)
mask or score tensor is ever materialised.

Consecutive shards sharing one ``bn`` form a *group*: one contiguous slab of
the flat factor matrix with one ``RetrievalMeta`` and one kernel launch (the
uniform default is a single group — exactly the legacy single launch).
Heterogeneous partitions launch once per group and merge on host.  Built
with a ``launch.mesh.make_index_mesh`` mesh, a single group's kernel state
is partitioned block-aligned over the devices (the build pads the last
shard with dead blocks to a whole number per device), and the group
launches once per device over that device's rows, through the same host
merge.

Merge semantics: the kernel's accumulator realises the total order
(score desc, global row asc); live rows appear in the flat layout in id
order (pad rows are dead and never candidates), so global-row order among
candidates == catalog-id order and any multi-shard/multi-group query is
bit-identical to the single-shard ``GamRetriever(device=True)`` path — and
to ``lax.top_k`` over the dense masked score matrix, which the retained
``query_dense_reference`` oracle still computes for parity tests.

Incremental builds: :func:`build_shard_segment`, :func:`build_group_meta`
and :meth:`ShardedGamIndex.assemble` are the staged units the background
:class:`~repro.service.compaction.CompactionPlanner` drives one bounded
slice at a time; ``ShardedGamIndex.build`` runs the same stages eagerly.
"""
from __future__ import annotations

import dataclasses
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.inverted_index import build_segment, candidate_mask_from_table
from repro.core.mapping import GamConfig, sparse_map
from repro.core.retrieval import masked_topk
from repro.kernels.gam_retrieve import (RetrievalMeta, expand_tile_skips,
                                        export_topk, pack_patterns,
                                        quantize_meta, rerank_pool)
from repro.kernels.gam_score import NEG
from repro.kernels.ops import gam_retrieve
from repro.obs.tracing import NOOP_TRACER
from repro.service.repartition import Partition

__all__ = ["ShardTopK", "ShardedGamIndex", "build_group_meta",
           "build_shard_segment", "collect_launch"]


@dataclasses.dataclass
class ShardTopK:
    """Result of a sharded query, still in global-row coordinates."""
    scores: np.ndarray      # (Q, kappa) f32, NEG in empty slots
    rows: np.ndarray        # (Q, kappa) int32 global rows, -1 in empty slots
    shard_candidates: np.ndarray  # (Q, S) per-shard candidate counts
    block_candidates: np.ndarray | None = None  # (Q, n_blocks) per-block
    tiles_skipped_frac: float = 0.0  # fraction of (Q_blk, N_blk) tiles pruned
    active_words_frac: float = 1.0   # pattern words the overlap loop ran
                                     # over, as a fraction of all words
    tile_skips: np.ndarray | None = None  # (Q, n_blocks) bool prepass skips
                                          # (explain-only; None by default)


def collect_launch(res, users, factors, kappa: int, int8: bool,
                   tracer=NOOP_TRACER):
    """The host's side of one queued ``gam_retrieve(..., rerank=False)``
    launch: -> its result, an int8 pool re-ranked to ``kappa``.

    Inside a sampled trace a ``device_wait`` span blocks on the launch
    where the host first reads it, so the wait is told apart from host
    work; untraced, that first read waits instead, and launches and waits
    keep the same order either way.  An int8 pool's exact re-rank
    (:func:`rerank_pool`: a device gather, the transfer, a per-query numpy
    loop) runs under a ``rerank`` span."""
    if tracer.active:
        with tracer.span("device_wait"):
            jax.block_until_ready(res)
    if int8:
        with tracer.span("rerank"):
            res = rerank_pool(res, users, factors, kappa)
    return res


# -------------------------------------------------------- staged build units


def build_shard_segment(tau: np.ndarray, mask: np.ndarray,
                        partition: Partition, s: int, p: int, bucket: int):
    """Posting segment of shard ``s`` over its local rows.

    ``tau``/``mask`` are the (n, k) mapped patterns of the whole id-sorted
    catalog; the shard's slice is taken here so the compaction planner can
    call one shard per step.  Returns ``(table, counts, spill)`` with the
    shard's cap as the pad sentinel.
    """
    lo = partition.starts[s]
    hi = lo + partition.lengths[s]
    return build_segment(tau[lo:hi], p, bucket, mask[lo:hi],
                         sentinel=partition.caps[s])


def build_group_meta(tau: np.ndarray, mask: np.ndarray, p: int,
                     partition: Partition, g: int,
                     shard_spills) -> RetrievalMeta:
    """Fused-kernel block metadata for group ``g``'s slab.

    Each member shard's real-row patterns are placed at their PADDED flat
    positions within the slab (pad rows keep empty patterns and can never
    become candidates); ``shard_spills[s]`` are the shard-local spill rows
    from :func:`build_shard_segment`.  For the uniform single-group
    partition this reproduces ``kernels.gam_retrieve.build_retrieval_meta``
    over the whole flat layout bit-for-bit.
    """
    s_lo, s_hi = partition.groups[g]
    bn = partition.bns[s_lo]
    row_lo, row_hi = partition.group_rows(g)
    rows = row_hi - row_lo
    words = -(-p // 32)
    bits = np.zeros((rows, words), np.uint32)
    spill = np.zeros(rows, bool)
    for s in range(s_lo, s_hi):
        off = partition.offsets[s] - row_lo
        lo, ln = partition.starts[s], partition.lengths[s]
        if ln:
            bits[off:off + ln] = pack_patterns(tau[lo:lo + ln],
                                               mask[lo:lo + ln], p)
        sp = np.asarray(shard_spills[s], np.int64)
        if sp.size:
            spill[off + sp] = True
    n_blocks = rows // bn
    union = np.bitwise_or.reduce(bits.reshape(n_blocks, bn, words), axis=1)
    return RetrievalMeta(
        item_bits_t=jnp.asarray(np.ascontiguousarray(bits.T)),
        block_union=jnp.asarray(union),
        block_spill=jnp.asarray(spill.reshape(n_blocks, bn).any(axis=1)),
        spill8=jnp.asarray(spill.astype(np.int8)[None, :]),
        p=int(p), words=words, bn=bn, n_rows=rows, n_pad=rows,
    )


class ShardedGamIndex:
    """Partitioned phi-index + factor store over the item axis."""

    def __init__(self, cfg: GamConfig, item_ids: np.ndarray,
                 tables: jax.Array, counts: jax.Array, spills: jax.Array,
                 factors: jax.Array, alive: np.ndarray,
                 partition: Partition, min_overlap: int,
                 bucket: int, mesh=None, metas=None, *,
                 quantize: str = "none", rerank_factor: int = 4):
        self.cfg = cfg
        self.quantize = quantize
        self.rerank_factor = int(rerank_factor)
        self.item_ids = item_ids          # (N,) int64 sorted catalog ids
        self.tables = tables              # (S, p, bucket) int32
        self.counts = counts              # (S, p) int32
        self.spills = spills              # (S, W) int32, padded with caps[s]
        self.partition = partition
        self._alive_host = np.asarray(alive, bool)  # (n_rows,) numpy mirror
        self.min_overlap = min_overlap
        self.bucket = bucket
        self.mesh = mesh
        self.metas: list[RetrievalMeta] = list(metas or [])
        # per-group device slabs (with a mesh, the single group's slab is
        # partitioned over the devices by _place_on_mesh below)
        factors = jnp.asarray(factors)
        groups = partition.groups
        if len(groups) == 1:
            self.factors_g = [factors]
            self.alive_g = [jnp.asarray(self._alive_host)]
        else:
            self.factors_g, self.alive_g = [], []
            for g in range(len(groups)):
                lo, hi = partition.group_rows(g)
                self.factors_g.append(factors[lo:hi])
                self.alive_g.append(jnp.asarray(self._alive_host[lo:hi]))
        # int8 slabs: quantize each group's factor slab against its meta's
        # block width (skipping metas restored with slabs already attached);
        # the f32 slabs stay resident as the exact re-rank store
        if quantize == "int8":
            self.metas = [m if m.quantize == "int8"
                          else quantize_meta(m, np.asarray(self.factors_g[g]))
                          for g, m in enumerate(self.metas)]
        if mesh is not None and len(groups) == 1 and self.metas:
            self._place_on_mesh(mesh)
        # flat row -> catalog id (-1 on pad rows), and id -> flat row
        self._padded_ids = np.full(partition.n_rows, -1, np.int64)
        self._row_of: dict[int, int] = {}
        for s in range(partition.n_shards):
            off, st, ln = (partition.offsets[s], partition.starts[s],
                           partition.lengths[s])
            self._padded_ids[off:off + ln] = item_ids[st:st + ln]
            self._row_of.update(zip(item_ids[st:st + ln].tolist(),
                                    range(off, off + ln)))
        # host mirrors of the per-row pattern bitsets and spill flags, so
        # kill() can recompute per-block metadata without a device gather.
        # Derived from the metas (not rebuilt from tau) so a restored
        # snapshot — whose dead rows were already zeroed by earlier kills —
        # stays consistent with what the device arrays actually contain.
        if self.metas:
            self._bits_host = np.concatenate([
                np.ascontiguousarray(np.asarray(m.item_bits_t).T)
                for m in self.metas])
            self._spill_host = np.concatenate([
                np.asarray(m.spill8[0]).astype(bool) for m in self.metas])
        else:
            self._bits_host = None
            self._spill_host = None

    # ------------------------------------------------------------- build

    @staticmethod
    def build(factors: np.ndarray, cfg: GamConfig, *,
              item_ids: np.ndarray | None = None, n_shards: int = 1,
              min_overlap: int = 1, bucket: int = 256, mesh=None,
              partition: Partition | None = None,
              premapped=None, quantize: str = "none",
              rerank_factor: int = 4) -> "ShardedGamIndex":
        """Eager build: the same staged units the background compaction
        planner drives incrementally, run back to back.  ``premapped``:
        optional (tau, mask) aligned with the CALLER's row order, when the
        phi-mapping was already paid (e.g. by the repartitioner's weights)."""
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        factors = np.asarray(factors, np.float32)
        n, _ = factors.shape
        if item_ids is None:
            item_ids = np.arange(n, dtype=np.int64)
        item_ids = np.asarray(item_ids, np.int64)
        if len(np.unique(item_ids)) != n:
            raise ValueError("item_ids must be unique")
        order = np.argsort(item_ids)
        item_ids, factors = item_ids[order], factors[order]

        if partition is None:
            partition = Partition.uniform(n, n_shards)
        elif partition.n != n:
            raise ValueError(f"partition covers {partition.n} rows, "
                             f"catalog has {n}")
        if mesh is not None:
            partition = partition.padded_to_blocks(mesh.devices.size)

        if premapped is None:
            tau, vals = sparse_map(jnp.asarray(factors), cfg)
            tau, mask = np.asarray(tau), np.asarray(vals) != 0.0
        else:
            tau, mask = premapped
            tau = np.asarray(tau)[order]
            mask = np.asarray(mask, bool)[order]

        segs = [build_shard_segment(tau, mask, partition, s, cfg.p, bucket)
                for s in range(partition.n_shards)]
        spill_list = [sp for _, _, sp in segs]
        metas = [build_group_meta(tau, mask, cfg.p, partition, g, spill_list)
                 for g in range(len(partition.groups))]
        return ShardedGamIndex.assemble(
            cfg, item_ids, factors, partition,
            [t for t, _, _ in segs], [c for _, c, _ in segs], spill_list,
            metas, min_overlap=min_overlap, bucket=bucket, mesh=mesh,
            quantize=quantize, rerank_factor=rerank_factor)

    @staticmethod
    def assemble(cfg: GamConfig, item_ids: np.ndarray, factors: np.ndarray,
                 partition: Partition, tables, counts, spill_list, metas, *,
                 min_overlap: int, bucket: int, mesh=None,
                 quantize: str = "none", rerank_factor: int = 4
                 ) -> "ShardedGamIndex":
        """Final stage: stack the per-shard segments, lay the factor slabs
        into the padded flat matrix, upload, and construct the index."""
        n, k = factors.shape
        width = max((np.asarray(sp).size for sp in spill_list), default=0)
        spills = (np.stack([
            np.concatenate([np.asarray(sp, np.int32),
                            np.full(width - np.asarray(sp).size,
                                    partition.caps[s], np.int32)])
            for s, sp in enumerate(spill_list)
        ]) if width else np.full((partition.n_shards, 0),
                                 partition.caps[0] if partition.caps else 0,
                                 np.int32))

        flat = np.zeros((partition.n_rows, k), np.float32)
        alive = np.zeros(partition.n_rows, bool)
        for s in range(partition.n_shards):
            off, st, ln = (partition.offsets[s], partition.starts[s],
                           partition.lengths[s])
            flat[off:off + ln] = factors[st:st + ln]
            alive[off:off + ln] = True

        tables_j = jnp.asarray(np.stack(tables))
        counts_j = jnp.asarray(np.stack(counts))
        spills_j = jnp.asarray(spills)
        factors_j = jnp.asarray(flat)
        if mesh is not None and len(partition.groups) > 1:
            # index_shardings partitions the single flat layout only — a
            # heterogeneous rebalance on a mesh deployment would otherwise
            # silently drop the item-axis placement, so say it out loud
            warnings.warn(
                "heterogeneous partition (multiple bn-groups) is not "
                "mesh-partitioned yet; serving from local devices — plan "
                "with a uniform bn to keep item-axis sharding",
                RuntimeWarning, stacklevel=2)
        if mesh is not None and len(partition.groups) == 1:
            # posting tables shard-major over the items axis; the kernel
            # state (factors, patterns, alive) is placed by the constructor
            from repro.sharding.specs import index_shardings
            arrs = {"tables": tables_j, "counts": counts_j,
                    "spills": spills_j}
            arrs = jax.device_put(arrs, index_shardings(mesh, arrs))
            tables_j, counts_j = arrs["tables"], arrs["counts"]
            spills_j = arrs["spills"]
        return ShardedGamIndex(cfg, item_ids, tables_j, counts_j, spills_j,
                               factors_j, alive, partition, min_overlap,
                               bucket, mesh, metas, quantize=quantize,
                               rerank_factor=rerank_factor)

    def _place_on_mesh(self, mesh) -> None:
        """Partition the single group's kernel state over the mesh's items
        axis: every device holds one contiguous, block-aligned run of rows
        (factor rows, alive flags, packed patterns, block unions, spill
        flags and, for int8, the slab and its scales), and :meth:`query`
        launches the kernel once per device over that device's rows."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        meta = self.metas[0]
        n_dev = mesh.devices.size
        if meta.n_blocks % n_dev:
            # build() and the compaction planner pad the partition to whole
            # blocks per device; only a hand-assembled layout gets here
            warnings.warn(
                f"{meta.n_blocks} kernel blocks do not split evenly over "
                f"{n_dev} devices; serving the kernel state from one device "
                f"(Partition.padded_to_blocks aligns a layout)",
                RuntimeWarning, stacklevel=3)
            return

        def put(x, axis):
            spec = [None] * x.ndim
            spec[axis] = "items"
            return jax.device_put(x, NamedSharding(mesh, P(*spec)))

        repl = dict(item_bits_t=put(meta.item_bits_t, 1),
                    block_union=put(meta.block_union, 0),
                    block_spill=put(meta.block_spill, 0),
                    spill8=put(meta.spill8, 1))
        if meta.quantize == "int8":
            repl.update(factors_q=put(meta.factors_q, 0),
                        scales=put(meta.scales, 1))
        self.metas[0] = dataclasses.replace(meta, **repl)
        self.factors_g[0] = put(self.factors_g[0], 0)
        self.alive_g[0] = put(self.alive_g[0], 0)

    def _launch_units(self, g: int) -> list:
        """Kernel launches of group ``g`` as ``(row offset within the
        group, meta, factors, alive)``: one launch for a group on one
        device, one per device (over that device's own rows) for a group
        partitioned over a mesh."""
        meta, fac, alive = self.metas[g], self.factors_g[g], self.alive_g[g]
        if len(fac.sharding.device_set) == 1:
            return [(0, meta, fac, alive)]

        def local(x):
            return {sh.device: sh.data for sh in x.addressable_shards}

        names = ["item_bits_t", "block_union", "block_spill", "spill8"]
        if meta.quantize == "int8":
            names += ["factors_q", "scales"]
        parts = {n: local(getattr(meta, n)) for n in names}
        alive_d = local(alive)
        units = []
        for sh in sorted(fac.addressable_shards,
                         key=lambda sh: sh.index[0].start or 0):
            n = sh.data.shape[0]
            units.append((sh.index[0].start or 0, dataclasses.replace(
                meta, n_rows=n, n_pad=n,
                **{k: v[sh.device] for k, v in parts.items()}),
                sh.data, alive_d[sh.device]))
        return units

    # ------------------------------------------------------------- state

    @property
    def n_shards(self) -> int:
        return self.partition.n_shards

    @property
    def n_live(self) -> int:
        return int(self._alive_host.sum())

    @property
    def meta(self) -> RetrievalMeta:
        """The single-group block metadata (uniform partitions)."""
        if len(self.metas) != 1:
            raise ValueError("heterogeneous partition has one meta per "
                             "bn-group; read .metas")
        return self.metas[0]

    def kill(self, ids) -> None:
        """Tombstone catalog ids (deleted or superseded by a delta upsert).

        O(batch + touched blocks) — never re-uploads the full alive array.
        Besides flipping ``alive``, the dead rows' pattern bits and spill
        flags are removed from the fused kernel's block metadata (pattern
        bitsets, block unions, block spill flags) group by group: the
        block-union popcount must upper-bound the overlap of LIVE members
        only, otherwise long tombstone streams erode the zero-candidate
        block-skip rate until ``compact()`` (the ROADMAP staleness bug).
        Candidate sets are unchanged — dead rows were already excluded
        in-kernel via ``alive`` — so query results are bit-identical before
        and after the refresh.
        """
        rows = [r for i in np.asarray(ids).ravel()
                if (r := self._row_of.get(int(i))) is not None]
        if not rows:
            return
        rows_a = np.asarray(rows, np.int64)
        self._alive_host[rows_a] = False
        if not self.metas:
            return
        self._bits_host[rows_a] = 0
        self._spill_host[rows_a] = False
        for g, meta in enumerate(self.metas):
            lo, hi = self.partition.group_rows(g)
            sel = rows_a[(rows_a >= lo) & (rows_a < hi)] - lo
            if sel.size == 0:
                continue
            sel_j = jnp.asarray(sel, jnp.int32)
            self.alive_g[g] = self.alive_g[g].at[sel_j].set(False)
            bn, words = meta.bn, meta.words
            blocks = np.unique(sel // bn)
            g_bits = self._bits_host[lo:hi]
            g_spill = self._spill_host[lo:hi]
            union = np.bitwise_or.reduce(
                g_bits.reshape(-1, bn, words)[blocks], axis=1)
            bspill = g_spill.reshape(-1, bn)[blocks].any(axis=1)
            blocks_j = jnp.asarray(blocks, jnp.int32)
            self.metas[g] = dataclasses.replace(
                meta,
                item_bits_t=meta.item_bits_t.at[:, sel_j].set(0),
                spill8=meta.spill8.at[0, sel_j].set(0),
                block_union=meta.block_union.at[blocks_j].set(
                    jnp.asarray(union)),
                block_spill=meta.block_spill.at[blocks_j].set(
                    jnp.asarray(bspill)),
            )

    def block_index(self, rows) -> np.ndarray:
        """Global flat rows -> global kernel block ids (blocks numbered
        group by group) — maps the metrics' per-block candidate loads back
        onto items for the repartitioner's weights."""
        rows = np.asarray(rows, np.int64)
        out = np.zeros(rows.shape, np.int64)
        blk_off = 0
        for g, meta in enumerate(self.metas):
            lo, hi = self.partition.group_rows(g)
            m = (rows >= lo) & (rows < hi)
            out[m] = blk_off + (rows[m] - lo) // meta.bn
            blk_off += meta.n_blocks
        return out

    def total_blocks(self) -> int:
        """Kernel blocks across every bn-group (the block-metrics width)."""
        return sum(m.n_blocks for m in self.metas)

    def posting_load(self) -> np.ndarray:
        """(S,) total posting entries per shard — the balance statistic."""
        return np.asarray(jnp.sum(self.counts, axis=-1))

    def flat_factors(self) -> np.ndarray:
        """(n_rows, k) host copy of the padded flat factor matrix."""
        return np.concatenate([np.asarray(f) for f in self.factors_g])

    # ------------------------------------------------------------- query

    def _shard_candidates(self, blk: np.ndarray) -> np.ndarray:
        """(Q, n_blocks) per-block candidate counts -> (Q, S) per-shard."""
        nb = [self.partition.caps[s] // self.partition.bns[s]
              for s in range(self.n_shards)]
        starts = np.concatenate([[0], np.cumsum(nb)[:-1]]).astype(int)
        return np.add.reduceat(blk, starts, axis=1)

    def query(self, users: jax.Array, q_tau: jax.Array, q_mask: jax.Array,
              kappa: int, *, exact: bool = False, tracer=None,
              collect_tile_skips: bool = False,
              min_overlap: int | None = None) -> ShardTopK:
        """users (Q, k) f32 + mapped query patterns -> merged top-kappa.

        One fused gam_retrieve pass per bn-group (uniform partitions: exactly
        one pass over the whole flat factor matrix; one per device when the
        group is placed over a mesh): candidate pruning, scoring and the
        in-launch top-kappa merge all happen on chip (zero-candidate item
        blocks are skipped outright); several launches merge on host under
        the same (score desc, global row asc) total order, which is what
        keeps a repartitioned catalog's ids identical to the single-launch
        layout's (scores up to float summation order).
        ``exact=True`` scores every live row through the same kernel
        (``min_overlap=0``) — the brute-force reference path.

        ``tracer`` wraps each launch's enqueue (``gam_retrieve``, which
        takes the launch's ``active_words_frac`` once it is read), the wait
        for it and its int8 re-rank (:func:`collect_launch`) and the host
        merge in spans; ``collect_tile_skips`` additionally expands the
        kernel's per-query-block skip map to a per-query (Q, n_blocks) bool in
        ``ShardTopK.tile_skips`` (host-side numpy over existing outputs —
        the device computation and the answer are identical either way)."""
        tracer = NOOP_TRACER if tracer is None else tracer
        # min_overlap override: the QoS degrade ladder raises the prune
        # threshold one notch under deadline pressure (exact still wins)
        mo = 0 if exact else (self.min_overlap if min_overlap is None
                              else int(min_overlap))
        q = int(np.asarray(users).shape[0])
        launched, offsets, spans = [], [], []
        for g in range(len(self.metas)):
            units = self._launch_units(g)
            for off, meta, fac, alive in units:
                args = (users, q_tau, q_mask)
                if len(units) > 1:       # the query goes to the rows' device
                    args = jax.device_put(args, fac.sharding)
                u, tq, qm = args
                with tracer.span("gam_retrieve", group=g, bn=meta.bn,
                                 n_rows=meta.n_rows) as sp:
                    res = gam_retrieve(
                        u, fac, tq, qm, meta, kappa, min_overlap=mo,
                        alive=alive, rerank_factor=self.rerank_factor,
                        rerank=False)
                launched.append((res, meta.quantize == "int8", u, fac))
                spans.append((sp, meta.words))
                offsets.append(self.partition.group_rows(g)[0] + off)
        # int8 pools are re-ranked on the host only once every launch is
        # queued: the re-rank waits on its launch, and would otherwise keep
        # a mesh's devices from running together
        results = [collect_launch(res, u, fac, kappa, int8, tracer)
                   for res, int8, u, fac in launched]
        # the compacted overlap loop's width, per launch and over the batch
        looped, total = 0, 0
        for r, (sp, words) in zip(results, spans):
            lw = np.asarray(r.loop_words)
            sp.set(active_words_frac=float(lw.mean()) / words)
            looped += int(lw.sum())
            total += lw.size * words
        words_frac = looped / max(total, 1)
        skips = (np.concatenate([expand_tile_skips(r.skipped, q)
                                 for r in results], axis=1)
                 if collect_tile_skips and results else None)
        if len(results) == 1:
            res = results[0]
            blk = np.asarray(res.blk_counts)
            return ShardTopK(scores=np.asarray(res.vals, np.float32),
                             rows=np.asarray(res.rows, np.int32),
                             shard_candidates=self._shard_candidates(blk),
                             block_candidates=blk,
                             tiles_skipped_frac=float(res.skipped.mean()),
                             active_words_frac=words_frac,
                             tile_skips=skips)
        with tracer.span("group_merge", n_launches=len(results)):
            exported = [export_topk(r.vals, r.rows, offset=off)
                        for off, r in zip(offsets, results)]
            cat_s = np.concatenate([s for s, _ in exported], axis=1)
            cat_r = np.concatenate([r for _, r in exported], axis=1)
            order = np.lexsort((cat_r, -cat_s), axis=-1)[:, :kappa]
            vals = np.take_along_axis(cat_s, order, axis=-1)
            rows = np.take_along_axis(cat_r, order, axis=-1)
            rows = np.where(vals <= NEG / 2, -1, rows).astype(np.int32)
        blk = np.concatenate([np.asarray(r.blk_counts) for r in results],
                             axis=1)
        tiles = sum(np.asarray(r.skipped).size for r in results)
        skipped = sum(int(np.asarray(r.skipped).sum()) for r in results)
        return ShardTopK(scores=vals, rows=rows,
                         shard_candidates=self._shard_candidates(blk),
                         block_candidates=blk,
                         tiles_skipped_frac=skipped / max(tiles, 1),
                         active_words_frac=words_frac,
                         tile_skips=skips)

    def query_dense_reference(self, users: jax.Array, q_tau: jax.Array,
                              q_mask: jax.Array, kappa: int, *,
                              exact: bool = False) -> ShardTopK:
        """The superseded (Q, N)-mask path, kept as the parity oracle.

        Per-shard candidate masks from the posting tables, dense masked
        scoring, one ``lax.top_k`` over the whole flat row space — ties break
        by position, i.e. ascending global row, the same total order the
        fused accumulator realises.  Works on any partition (heterogeneous
        shards loop on host; this is a test oracle, not a serving path)."""
        q = np.asarray(users).shape[0]
        alive = jnp.asarray(self._alive_host)
        if exact:
            masks = jnp.broadcast_to(alive[None, :],
                                     (q, self.partition.n_rows))
        else:
            cols = []
            for s in range(self.n_shards):
                cap = self.partition.caps[s]
                per_q = jax.vmap(
                    lambda tq, qm, t=self.tables[s], sp=self.spills[s],
                    c=cap: candidate_mask_from_table(
                        t, sp, tq, qm, sentinel=c,
                        min_overlap=self.min_overlap))
                cols.append(per_q(q_tau, q_mask))
            masks = jnp.concatenate(cols, axis=1) & alive[None, :]
        flat = jnp.asarray(self.flat_factors())
        vals, rows = masked_topk(jnp.asarray(users), flat, masks, kappa)
        vals = np.asarray(vals, np.float32)
        rows = np.where(vals <= NEG / 2, -1, np.asarray(rows, np.int32))
        masks_np = np.asarray(masks)
        shard_cand = np.stack(
            [masks_np[:, self.partition.offsets[s]:
                      self.partition.offsets[s] + self.partition.caps[s]]
             .sum(axis=1) for s in range(self.n_shards)], axis=1)
        return ShardTopK(scores=vals, rows=rows, shard_candidates=shard_cand)

    def rows_to_ids(self, rows: np.ndarray, scores: np.ndarray) -> np.ndarray:
        """Global rows -> catalog ids; empty (NEG-scored) slots -> -1."""
        rows = np.asarray(rows, np.int64)
        out = self._padded_ids[rows]
        out[np.asarray(scores) <= NEG / 2] = -1
        return out
