"""Item-axis sharded GAM index: the service's main (compacted) segment.

The catalog is sorted by item id and partitioned contiguously according to a
:class:`~repro.service.repartition.Partition` — per-shard row counts, padded
caps and fused-kernel block widths ``bn``.  The default
``Partition.uniform`` reproduces the legacy equal-cut layout (one shared cap
rounded up to whole kernel blocks, pads at the catalog tail); a skew-aware
partition from the :class:`~repro.service.repartition.Repartitioner` may
instead cut hot regions into short shards with narrow blocks.  Each shard
owns a bucket-clipped posting segment over LOCAL row ids, held in host
memory as CSR (built with ``core.inverted_index.build_segment_csr``) —
kept for posting-load stats and as the source of the bucket-spill flags;
no query reads it on the device — while the query path streams the flat
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
merge.  Each device's share is mapped on that device and sent to it from
host memory, so no device ever holds more than its share: a catalog only
the whole mesh holds builds as one that fits a single device does.

Merge semantics: the kernel's accumulator realises the total order
(score desc, global row asc); live rows appear in the flat layout in id
order (pad rows are dead and never candidates), so global-row order among
candidates == catalog-id order and any multi-shard/multi-group query is
bit-identical to the single-shard ``gam-device`` backend — and
to ``lax.top_k`` over the dense masked score matrix, which the retained
``query_dense_reference`` oracle still computes for parity tests.

Incremental builds: :func:`build_shard_segment`, :func:`build_group_meta`
and :meth:`ShardedGamIndex.assemble` are the staged units the background
:class:`~repro.service.compaction.CompactionPlanner` drives one bounded
slice at a time; ``ShardedGamIndex.build`` runs the same stages eagerly.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
import warnings
from concurrent.futures import ThreadPoolExecutor
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.compress.quantize import quantize_int8
from repro.core.inverted_index import (build_segment_csr,
                                       candidate_mask_from_table,
                                       csr_to_table)
from repro.core.mapping import GamConfig, sparse_map
from repro.core.retrieval import masked_topk
from repro.kernels.gam_retrieve import (RetrievalMeta, block_unions,
                                        expand_tile_skips, export_topk,
                                        pack_patterns, quantize_meta,
                                        rerank_pool)
from repro.kernels.gam_score import NEG
from repro.kernels.ops import gam_retrieve
from repro.obs.tracing import NOOP_TRACER
from repro.service.repartition import Partition

__all__ = ["MAP_CHUNK_ROWS", "ShardTopK", "ShardedGamIndex",
           "build_group_meta", "build_shard_segment", "collect_launch",
           "device_runs", "map_catalog", "mesh_runs"]

#: the device arrays of a :class:`RetrievalMeta`
_META_ARRAYS = ("item_bits_t", "block_union", "block_spill", "spill8",
                "factors_q", "scales")


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
    merge_steps_frac: float = 1.0    # selection steps the merge ran, as a
                                     # fraction of kappa a scored tile
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

#: catalog rows a device maps per call, so the map's device memory (one
#: chunk's rows, destinations and values) stays bounded whatever the size
#: of the catalog
MAP_CHUNK_ROWS = 1 << 18
#: map chunks a device holds at once: one comes back while the next maps
_MAP_IN_FLIGHT = 2


def _clock(tracer):
    return getattr(tracer, "clock", time.monotonic)


def _device_id(device) -> int:
    return (device if device is not None else jax.devices()[0]).id


def _in_parallel(fn, parts) -> list:
    """``fn`` over ``parts`` (a device's share, or a shard), one thread
    each: numpy's sorts, scatters and copies and JAX's transfers release
    the interpreter lock, so the shares build side by side."""
    parts = list(parts)
    if len(parts) <= 1:
        return [fn(x) for x in parts]
    with ThreadPoolExecutor(min(len(parts), os.cpu_count() or 1)) as ex:
        return list(ex.map(fn, parts))


def device_runs(mesh, n_rows: int) -> list:
    """``(device, lo, hi)``: the block-aligned run of flat rows each device
    of ``mesh`` holds of an ``n_rows``-row slab split over its items axis,
    in row order."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    idx = NamedSharding(mesh, P("items")).addressable_devices_indices_map(
        (n_rows,))
    return sorted(((d, sl.start or 0, n_rows if sl.stop is None else sl.stop)
                   for d, (sl,) in idx.items()), key=lambda r: r[1])


def mesh_runs(partition: Partition, mesh) -> list | None:
    """The per-device runs a layout's kernel state splits into over
    ``mesh`` (:func:`device_runs`), or None where it is served from one
    device: no mesh, several bn-groups, or blocks that do not split evenly
    (``build`` and the compaction planner pad a layout to whole blocks per
    device, so only a hand-assembled one gets there)."""
    if mesh is None or len(partition.groups) != 1:
        return None
    if (partition.n_rows // partition.bns[0]) % mesh.devices.size:
        return None
    return device_runs(mesh, partition.n_rows)


def _catalog_rows_before(partition: Partition, flat_row: int) -> int:
    """Live catalog rows laid out before flat row ``flat_row``."""
    return sum(min(max(flat_row - off, 0), ln)
               for off, ln in zip(partition.offsets, partition.lengths))


@partial(jax.jit, static_argnames=("cfg", "dtype"))
def _mapped_slots(z, cfg: GamConfig, dtype):
    """Each row's destinations where its mapped value is non-zero and -1
    elsewhere: the sparsity pattern as one array, narrowed to ``dtype``."""
    tau, vals = sparse_map(z, cfg)
    return jnp.where(vals != 0.0, tau, -1).astype(dtype)


def map_catalog(factors: np.ndarray, cfg: GamConfig, runs,
                tracer=NOOP_TRACER) -> tuple[np.ndarray, np.ndarray]:
    """(n, k) id-sorted factors -> host ``(tau, mask)``.  Each ``(device,
    lo, hi)`` run of catalog rows is mapped on its device (``None``: the
    default device), :data:`MAP_CHUNK_ROWS` rows a call; ``tau`` is -1
    where ``mask`` is False, which no consumer of the pair reads."""
    n, k = factors.shape
    dtype = np.int16 if cfg.p <= np.iinfo(np.int16).max else np.int32
    tau = np.empty((n, k), dtype)
    mask = np.empty((n, k), bool)
    clock = _clock(tracer)

    def one(run):
        dev, lo, hi = run
        t0 = clock()
        chunk = min(MAP_CHUNK_ROWS, -(-(hi - lo) // 8) * 8)
        pending = collections.deque()

        def land():
            a, out = pending.popleft()
            b = min(a + chunk, hi)
            tau[a:b] = np.asarray(out)[:b - a]
            mask[a:b] = tau[a:b] >= 0

        for a in range(lo, hi, chunk):
            z = factors[a:min(a + chunk, hi)]
            if z.shape[0] < chunk:       # one program serves every chunk
                z = np.concatenate(
                    [z, np.zeros((chunk - z.shape[0], k), np.float32)])
            pending.append((a, _mapped_slots(jax.device_put(z, dev), cfg,
                                             dtype)))
            if len(pending) > _MAP_IN_FLIGHT:
                land()
        while pending:
            land()
        return t0, clock()

    runs = [r for r in runs if r[2] > r[1]]
    for (dev, lo, hi), (t0, t1) in zip(runs, _in_parallel(one, runs)):
        tracer.record_span("build_map", t0, t1, device=_device_id(dev),
                           rows=hi - lo, bytes=(hi - lo) * k * 4)
    return tau, mask


def build_shard_segment(tau: np.ndarray, mask: np.ndarray,
                        partition: Partition, s: int, p: int, bucket: int):
    """Posting segment of shard ``s`` over its local rows.

    ``tau``/``mask`` are the (n, k) mapped patterns of the whole id-sorted
    catalog; the shard's slice is taken here so the compaction planner can
    call one shard per step.  Returns ``((postings, offsets), counts,
    spill)``: the dense-bucket table as CSR
    (``core.inverted_index.build_segment_csr``), the form
    :class:`ShardedGamIndex` keeps.
    """
    lo = partition.starts[s]
    hi = lo + partition.lengths[s]
    post, off, counts, spill = build_segment_csr(tau[lo:hi], p, bucket,
                                                 mask[lo:hi])
    return (post, off), counts, spill


def build_group_meta(tau: np.ndarray, mask: np.ndarray, p: int,
                     partition: Partition, g: int, shard_spills, *,
                     mesh=None, tracer=NOOP_TRACER) -> RetrievalMeta:
    """Fused-kernel block metadata for group ``g``'s slab, in host memory:
    :class:`ShardedGamIndex` places it (and keeps the packed patterns as
    its host mirror).

    Each member shard's real-row patterns are packed straight into the
    kernel's (words, rows) layout at their PADDED flat positions within the
    slab (pad rows keep empty patterns and can never become candidates);
    ``shard_spills[s]`` are the shard-local spill rows from
    :func:`build_shard_segment`.  A layout that splits over ``mesh`` packs
    each device's run of rows on a thread of its own, into a contiguous
    block of its own: ``item_bits_t`` is then (devices, words, rows /
    devices), the blocks side by side, each what its device receives.  For
    the uniform
    single-group partition this reproduces
    ``kernels.gam_retrieve.build_retrieval_meta`` over the whole flat
    layout bit-for-bit.
    """
    s_lo, s_hi = partition.groups[g]
    bn = partition.bns[s_lo]
    row_lo, row_hi = partition.group_rows(g)
    rows = row_hi - row_lo
    words = -(-p // 32)
    spill = np.zeros(rows, bool)
    for s in range(s_lo, s_hi):
        sp = np.asarray(shard_spills[s], np.int64)
        if sp.size:
            spill[partition.offsets[s] - row_lo + sp] = True
    runs = mesh_runs(partition, mesh) or [(None, 0, rows)]
    # one contiguous (words, rows) block per device: what is sent to it
    bits = np.zeros((len(runs), words, rows // len(runs)), np.uint32)
    clock = _clock(tracer)

    def pack(i):
        _, lo, hi = runs[i]
        t0 = clock()
        for s in range(s_lo, s_hi):
            off = partition.offsets[s] - row_lo
            a, b = max(lo, off), min(hi, off + partition.lengths[s])
            if a < b:
                c = partition.starts[s] + a - off
                pack_patterns(tau[c:c + b - a], mask[c:c + b - a], p,
                                out=bits[i], col=a - lo)
        return block_unions(bits[i], bn), t0, clock()

    done = _in_parallel(pack, range(len(runs)))
    for (dev, lo, hi), (_, t0, t1) in zip(runs, done):
        tracer.record_span("build_pack", t0, t1, device=_device_id(dev),
                           rows=hi - lo, bytes=(hi - lo) * words * 4)
    return RetrievalMeta(
        item_bits_t=bits[0] if len(runs) == 1 else bits,
        block_union=np.concatenate([u for u, _, _ in done]),
        block_spill=spill.reshape(-1, bn).any(axis=1),
        spill8=spill.astype(np.int8)[None, :],
        p=int(p), words=words, bn=bn, n_rows=rows, n_pad=rows,
    )


class ShardedGamIndex:
    """Partitioned phi-index + factor store over the item axis."""

    def __init__(self, cfg: GamConfig, item_ids: np.ndarray,
                 postings, counts: np.ndarray, spills: np.ndarray,
                 factors, alive: np.ndarray,
                 partition: Partition, min_overlap: int,
                 bucket: int, mesh=None, metas=None, *,
                 quantize: str = "none", rerank_factor: int = 4,
                 tracer=None):
        self.cfg = cfg
        self.quantize = quantize
        self.rerank_factor = int(rerank_factor)
        self.item_ids = item_ids          # (N,) int64 sorted catalog ids
        # the posting segments stay in host memory: no query reads them on
        # the device (posting_load, the spill flags and the dense parity
        # oracle read them here).  One (postings, offsets) CSR pair per
        # shard, slot i's bucket-clipped list being
        # postings[offsets[i]:offsets[i + 1]]
        self.postings = [(np.asarray(post), np.asarray(off, np.int64))
                         for post, off in postings]
        self.counts = np.asarray(counts)  # (S, p) int32
        self.spills = np.asarray(spills)  # (S, W) int32, padded with caps[s]
        self.partition = partition
        self._alive_host = np.asarray(alive, bool)  # (n_rows,) numpy mirror
        self.min_overlap = min_overlap
        self.bucket = bucket
        self.mesh = mesh
        self.metas: list[RetrievalMeta] = list(metas or [])
        # host mirrors of the packed patterns and of the spill flags, so
        # kill() can recompute per-block metadata without a device gather:
        # the build's own host arrays, or (a restored snapshot, whose dead
        # rows earlier kills zeroed; a carved or re-assembled index) read
        # back from the device arrays, so they match what the device holds
        if self.metas:
            self._bits_host = self._mirror_patterns(self.metas)
            self._spill_host = np.concatenate([
                np.asarray(m.spill8[0]).astype(bool) for m in self.metas])
        else:
            self._bits_host = None
            self._spill_host = None
        tracer = NOOP_TRACER if tracer is None else tracer
        runs = mesh_runs(partition, mesh) if self.metas else None
        if mesh is not None and len(partition.groups) == 1 and self.metas \
                and runs is None:
            warnings.warn(
                f"{self.metas[0].n_blocks} kernel blocks do not split evenly "
                f"over {mesh.devices.size} devices; serving the kernel state "
                f"from one device (Partition.padded_to_blocks aligns a "
                f"layout)", RuntimeWarning, stacklevel=2)
        if runs is None:
            self._place_whole(factors, tracer)
        else:
            self._place_split(mesh, runs, factors, tracer)
        # flat row -> catalog id (-1 on pad rows), and id -> flat row
        self._padded_ids = np.full(partition.n_rows, -1, np.int64)
        self._row_of: dict[int, int] = {}
        for s in range(partition.n_shards):
            off, st, ln = (partition.offsets[s], partition.starts[s],
                           partition.lengths[s])
            self._padded_ids[off:off + ln] = item_ids[st:st + ln]
            self._row_of.update(zip(item_ids[st:st + ln].tolist(),
                                    range(off, off + ln)))

    @staticmethod
    def _mirror_patterns(metas) -> np.ndarray:
        """(blocks, words, rows / blocks): the packed patterns in the
        kernel's layout, one contiguous block per device the layout splits
        over (one block otherwise), so flat row ``r`` is column ``r % m``
        of block ``r // m``.  No whole-matrix transpose or copy: the
        build's blocks are kept as they are."""
        if len(metas) > 1:
            return np.concatenate([np.asarray(m.item_bits_t)
                                   for m in metas], axis=1)[None]
        bits = metas[0].item_bits_t
        if isinstance(bits, np.ndarray):
            return bits if bits.ndim == 3 else bits[None]
        shards = sorted(bits.addressable_shards,
                        key=lambda sh: sh.index[1].start or 0)
        return np.stack([np.array(sh.data) for sh in shards])

    def _mirror_union(self, lo: int, blocks: np.ndarray,
                      bn: int) -> np.ndarray:
        """(len(blocks), words) OR of the mirrored patterns of each
        ``bn``-row block numbered from flat row ``lo``."""
        m = self._bits_host.shape[2]
        rows = lo + blocks[:, None] * bn + np.arange(bn)     # (B, bn)
        return np.bitwise_or.reduce(
            self._bits_host[rows // m, :, rows % m], axis=1)

    def _place_whole(self, factors, tracer) -> None:
        """Each group's kernel state on one device: the flat factor matrix
        (sliced per group), its alive flags and its metadata, quantized to
        int8 slabs against each meta's block width where asked (metas
        restored with slabs already attached keep them); the f32 slabs stay
        resident as the exact re-rank store."""
        clock = _clock(tracer)
        t0 = clock()
        fac_host = factors if isinstance(factors, np.ndarray) else None
        factors = jnp.asarray(factors)
        groups = self.partition.groups
        if len(groups) == 1:
            self.factors_g = [factors]
            self.alive_g = [jnp.asarray(self._alive_host)]
        else:
            self.factors_g, self.alive_g = [], []
            for g in range(len(groups)):
                lo, hi = self.partition.group_rows(g)
                self.factors_g.append(factors[lo:hi])
                self.alive_g.append(jnp.asarray(self._alive_host[lo:hi]))
        self.metas = [dataclasses.replace(m, **{
            f: jnp.asarray(getattr(m, f)) for f in _META_ARRAYS
            if getattr(m, f) is not None}) for m in self.metas]
        if self.quantize == "int8":
            for g, m in enumerate(self.metas):
                if m.quantize != "int8":
                    lo, hi = self.partition.group_rows(g)
                    self.metas[g] = quantize_meta(
                        m, fac_host[lo:hi] if fac_host is not None
                        else np.asarray(self.factors_g[g]))
        if self.metas:
            nbytes = factors.nbytes + sum(
                getattr(m, f).nbytes for m in self.metas
                for f in _META_ARRAYS if getattr(m, f) is not None)
            tracer.record_span("build_place", t0, clock(),
                               device=_device_id(None),
                               rows=self.partition.n_rows, bytes=nbytes)

    def _place_split(self, mesh, runs, factors, tracer) -> None:
        """The single group's kernel state split over the mesh's items
        axis, each device's run of rows sent from host memory straight to
        that device (one thread a device): factor rows, alive flags, packed
        patterns, block unions, spill flags and, for int8, the slab and its
        scales, each quantized from the device's own rows (its runs are
        whole blocks, so the slab is the one quantizing the whole would
        give).  Nothing that spans the catalog is put on one device.
        :meth:`query` launches the kernel once per device over its rows."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        meta = self.metas[0]
        bn = meta.bn
        quantize_here = self.quantize == "int8" and meta.quantize != "int8"
        # name -> (array, its row axis, flat rows per entry along that axis)
        state = {"factors": (factors, 0, 1),
                 "alive": (self._alive_host, 0, 1),
                 "item_bits_t": (meta.item_bits_t, 1, 1),
                 "block_union": (meta.block_union, 0, bn),
                 "block_spill": (meta.block_spill, 0, bn),
                 "spill8": (meta.spill8, 1, 1)}
        if meta.quantize == "int8":
            state.update(factors_q=(meta.factors_q, 0, 1),
                         scales=(meta.scales, 1, bn))

        def sharding(ndim, axis):
            spec = [None] * ndim
            spec[axis] = "items"
            return NamedSharding(mesh, P(*spec))

        # every piece is sliced from host memory (an index re-assembled
        # from device arrays reads them back once)
        host = {name: np.asarray(x) for name, (x, _, _) in state.items()}
        clock = _clock(tracer)

        def place(i):
            dev, lo, hi = runs[i]
            t0 = clock()
            out, nbytes = {}, 0
            for name, (_, axis, per) in state.items():
                x = host[name]
                if name == "item_bits_t" and x.ndim == 3:
                    piece = x[i]          # the build's block for this device
                else:
                    sl = [slice(None)] * x.ndim
                    sl[axis] = slice(lo // per, hi // per)
                    piece = np.ascontiguousarray(x[tuple(sl)])
                out[name] = jax.device_put(piece, dev)
                nbytes += piece.nbytes
            if quantize_here:
                q, sc = quantize_int8(host["factors"][lo:hi], block=bn)
                out["factors_q"] = jax.device_put(q, dev)
                out["scales"] = jax.device_put(sc.reshape(1, -1), dev)
                nbytes += q.nbytes + sc.nbytes
            return out, t0, clock(), nbytes

        done = _in_parallel(place, range(len(runs)))
        for (dev, lo, hi), (_, t0, t1, nbytes) in zip(runs, done):
            tracer.record_span("build_place", t0, t1, device=dev.id,
                               rows=hi - lo, bytes=nbytes)

        def whole(name, shape, axis):
            return jax.make_array_from_single_device_arrays(
                shape, sharding(len(shape), axis),
                [out[name] for out, *_ in done])

        n_rows, n_blocks = meta.n_pad, meta.n_blocks
        self.factors_g = [whole("factors", (n_rows,) + tuple(
            np.shape(factors)[1:]), 0)]
        self.alive_g = [whole("alive", (n_rows,), 0)]
        repl = {name: whole(name, (meta.words, n_rows)
                            if name == "item_bits_t"
                            else np.shape(getattr(meta, name)), axis)
                for name, (_, axis, _) in state.items()
                if name not in ("factors", "alive")}
        if quantize_here:
            k = self.factors_g[0].shape[1]
            repl.update(quantize="int8",
                        factors_q=whole("factors_q", (n_rows, k), 0),
                        scales=whole("scales", (1, n_blocks), 1))
        self.metas[0] = dataclasses.replace(meta, **repl)

    # ------------------------------------------------------------- build

    @staticmethod
    def build(factors: np.ndarray, cfg: GamConfig, *,
              item_ids: np.ndarray | None = None, n_shards: int = 1,
              min_overlap: int = 1, bucket: int = 256, mesh=None,
              partition: Partition | None = None,
              premapped=None, quantize: str = "none",
              rerank_factor: int = 4, tracer=None) -> "ShardedGamIndex":
        """Eager build: the same staged units the background compaction
        planner drives incrementally, run back to back.  ``premapped``:
        optional (tau, mask) aligned with the CALLER's row order, when the
        phi-mapping was already paid (e.g. by the repartitioner's weights).

        On a mesh each device's share of rows is mapped (in bounded chunks)
        on that device, packed on a host thread of its own and sent from
        host memory to that device alone.  ``tracer`` times the stages
        under a ``build`` span: ``build_map``, ``build_pack`` and
        ``build_place`` per device (attributes ``device``, ``rows``,
        ``bytes``) and ``build_postings`` for the posting segments."""
        if n_shards < 1:
            raise ValueError(f"n_shards must be >= 1, got {n_shards}")
        tracer = NOOP_TRACER if tracer is None else tracer
        factors = np.asarray(factors, np.float32)
        n, _ = factors.shape
        with tracer.trace_or_span("build", rows=n):
            if item_ids is None:
                item_ids = np.arange(n, dtype=np.int64)
            item_ids = np.asarray(item_ids, np.int64)
            order = None               # ids already strictly increasing
            if n > 1 and not (item_ids[1:] > item_ids[:-1]).all():
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
                runs = mesh_runs(partition, mesh)
                runs = ([(d, _catalog_rows_before(partition, lo),
                          _catalog_rows_before(partition, hi))
                         for d, lo, hi in runs] if runs
                        else [(None, 0, n)])
                tau, mask = map_catalog(factors, cfg, runs, tracer)
            else:
                tau, mask = premapped
                tau, mask = np.asarray(tau), np.asarray(mask, bool)
                if order is not None:
                    tau, mask = tau[order], mask[order]

            with tracer.span("build_postings", rows=n) as span:
                segs = _in_parallel(
                    lambda s: build_shard_segment(tau, mask, partition, s,
                                                  cfg.p, bucket),
                    range(partition.n_shards))
                span.set(bytes=sum(post.nbytes + off.nbytes
                                   for (post, off), _, _ in segs))
            spill_list = [sp for _, _, sp in segs]
            metas = [build_group_meta(tau, mask, cfg.p, partition, g,
                                      spill_list, mesh=mesh, tracer=tracer)
                     for g in range(len(partition.groups))]
            del tau, mask
            return ShardedGamIndex.assemble(
                cfg, item_ids, factors, partition, [t for t, _, _ in segs],
                [c for _, c, _ in segs], spill_list, metas,
                min_overlap=min_overlap, bucket=bucket, mesh=mesh,
                quantize=quantize, rerank_factor=rerank_factor,
                tracer=tracer)

    @staticmethod
    def assemble(cfg: GamConfig, item_ids: np.ndarray, factors: np.ndarray,
                 partition: Partition, postings, counts, spill_list, metas, *,
                 min_overlap: int, bucket: int, mesh=None,
                 quantize: str = "none", rerank_factor: int = 4,
                 tracer=None) -> "ShardedGamIndex":
        """Final stage: gather the per-shard segments (in host memory, as
        :func:`build_shard_segment` gives them), lay the factor slabs into
        the padded flat matrix, and construct the index, which places the
        kernel state."""
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

        if mesh is not None and len(partition.groups) > 1:
            # only a single group splits over the items axis — a
            # heterogeneous rebalance on a mesh deployment would otherwise
            # silently drop the item-axis placement, so say it out loud
            warnings.warn(
                "heterogeneous partition (multiple bn-groups) is not "
                "mesh-partitioned yet; serving from local devices — plan "
                "with a uniform bn to keep item-axis sharding",
                RuntimeWarning, stacklevel=2)
        return ShardedGamIndex(cfg, item_ids, postings, np.stack(counts),
                               spills, flat, alive, partition, min_overlap,
                               bucket, mesh, metas, quantize=quantize,
                               rerank_factor=rerank_factor, tracer=tracer)

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
        m = self._bits_host.shape[2]
        self._bits_host[rows_a // m, :, rows_a % m] = 0
        self._spill_host[rows_a] = False
        for g, meta in enumerate(self.metas):
            lo, hi = self.partition.group_rows(g)
            sel = rows_a[(rows_a >= lo) & (rows_a < hi)] - lo
            if sel.size == 0:
                continue
            sel_j = jnp.asarray(sel, jnp.int32)
            self.alive_g[g] = self.alive_g[g].at[sel_j].set(False)
            bn = meta.bn
            blocks = np.unique(sel // bn)
            g_spill = self._spill_host[lo:hi]
            union = self._mirror_union(lo, blocks, bn)
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
        return self.counts.sum(axis=-1, dtype=np.int32)

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
        takes the launch's ``active_words_frac`` and ``merge_steps_frac``
        once it is read), the wait
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
                dev = next(iter(fac.sharding.device_set))
                with tracer.span("launch", device=dev.id,
                                 rows=meta.n_rows):
                    args = (users, q_tau, q_mask)
                    if len(units) > 1:   # the query goes to the rows' device
                        args = jax.device_put(args, fac.sharding)
                    u, tq, qm = args
                    with tracer.span("gam_retrieve", group=g, bn=meta.bn,
                                     n_rows=meta.n_rows) as sp:
                        res = gam_retrieve(
                            u, fac, tq, qm, meta, kappa, min_overlap=mo,
                            alive=alive, rerank_factor=self.rerank_factor,
                            rerank=False)
                launched.append((res, meta.quantize == "int8", u, fac))
                # the launch's pool width: an int8 pool is re-ranked to kappa
                spans.append((sp, meta.words, res.vals.shape[1]))
                offsets.append(self.partition.group_rows(g)[0] + off)
        # int8 pools are re-ranked on the host only once every launch is
        # queued: the re-rank waits on its launch, and would otherwise keep
        # a mesh's devices from running together
        results = [collect_launch(res, u, fac, kappa, int8, tracer)
                   for res, int8, u, fac in launched]
        # the compacted overlap loop's width and the merge's steps, per
        # launch and over the batch
        looped, total, stepped, full = 0, 0, 0, 0
        for r, (sp, words, pool) in zip(results, spans):
            lw = np.asarray(r.loop_words)
            steps = int(np.asarray(r.merge_steps).sum())
            scored = pool * int(np.logical_not(np.asarray(r.skipped)).sum())
            sp.set(active_words_frac=float(lw.mean()) / words,
                   merge_steps_frac=steps / max(scored, 1))
            looped += int(lw.sum())
            total += lw.size * words
            stepped += steps
            full += scored
        words_frac = looped / max(total, 1)
        steps_frac = stepped / max(full, 1)
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
                             merge_steps_frac=steps_frac,
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
                         merge_steps_frac=steps_frac,
                         tile_skips=skips)

    def query_dense_reference(self, users: jax.Array, q_tau: jax.Array,
                              q_mask: jax.Array, kappa: int, *,
                              exact: bool = False) -> ShardTopK:
        """The superseded (Q, N)-mask path, kept as the parity oracle.

        Per-shard candidate masks from the posting tables (laid out dense
        here, uploaded per call), dense masked
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
            for s, (post, off) in enumerate(self.postings):
                cap = self.partition.caps[s]
                table, _ = csr_to_table(post, off, self.bucket, sentinel=cap)
                per_q = jax.vmap(
                    lambda tq, qm, t=jnp.asarray(table),
                    sp=jnp.asarray(self.spills[s]),
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
