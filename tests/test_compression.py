"""Compressed catalogs: codecs, the pattern-factored index, int8 slabs.

Three layers of contract, mirroring ``docs/compression.md``:

* **Codec units** — delta + group-varint posting streams and per-block int8
  quantization round-trip bit-exactly (postings) or within the advertised
  error bound (factors), across adversarial shapes: empty, single-value,
  dense runs, ids adjacent to the kernel's 2^30 row-capacity sentinel.
* **Pattern-factored index** — ``CompressedInvertedIndex`` answers
  ``query``/``posting_list`` bit-identically to the flat ``InvertedIndex``
  it was compressed from, and ``decompress()`` reconstructs the flat CSR
  byte-for-byte.
* **Serving parity** — every backend that can carry the compressed form
  (``gam-device``, ``sharded``, ``sharded-multihost``) returns ids
  bit-identical to its own f32 path and to the brute oracle on the exact
  path — including across a snapshot-v4 round trip and live delta upserts —
  with scores equal to float-summation order (the repo-wide cross-path
  standard).  The boundary sweep pins the ``_NO_ROW`` capacity guards.
"""
import numpy as np
import pytest
from conftest import CFG, unit_factors

from repro.compress import (
    CodecError,
    decode_postings,
    delta_decode,
    delta_encode,
    dequantize_int8,
    encode_postings,
    group_varint_decode,
    group_varint_encode,
    pattern_dict_decode,
    pattern_dict_encode,
    pattern_dict_nbytes,
    quantization_error_bound,
    quantize_int8,
)
from repro.core.inverted_index import (
    InvertedIndex,
    csr_to_table,
    table_to_csr,
)
from repro.kernels.gam_retrieve import (
    ROW_CAPACITY,
    RowCapacityError,
    build_retrieval_meta,
)
from repro.retriever import RetrieverSpec, open_retriever
from repro.service.repartition import Partition

KAPPA = 10


def _spec(backend, **kw):
    kw.setdefault("min_overlap", 1)
    kw.setdefault("kappa", KAPPA)
    if backend == "sharded":
        kw.setdefault("n_shards", 3)
    if backend == "sharded-multihost":
        kw.setdefault("n_shards", 4)
        kw.setdefault("n_hosts", 2)
    return RetrieverSpec(cfg=CFG, backend=backend, **kw)


def _compressed(backend, **kw):
    return _spec(backend, compress_postings=True, quantize="int8",
                 rerank_factor=4, **kw)


def _mapped(factors):
    import jax.numpy as jnp

    from repro.core.mapping import sparse_map
    tau, vals = sparse_map(jnp.asarray(np.asarray(factors, np.float32)), CFG)
    return np.asarray(tau), np.asarray(vals) != 0.0


# ------------------------------------------------------------- codec units


def _roundtrip(values):
    values = np.asarray(values, np.int64)
    buf = group_varint_encode(values)
    out = group_varint_decode(buf, values.size)
    np.testing.assert_array_equal(out, values)
    return buf


def test_group_varint_roundtrips_edge_shapes():
    _roundtrip([])
    _roundtrip([0])
    _roundtrip([2**32 - 1])
    _roundtrip([1, 255, 256, 65535, 65536, 2**24 - 1, 2**24, 2**32 - 1])
    # lengths around the 4-value group boundary
    for n in (3, 4, 5, 7, 8, 9):
        _roundtrip(np.arange(n) * 1000)


def test_group_varint_packs_small_values_to_one_byte():
    buf = _roundtrip(np.arange(64) % 200)
    # 16 control bytes + 64 single data bytes
    assert buf.size == 16 + 64


def test_group_varint_rejects_truncated_streams():
    buf = group_varint_encode(np.array([1, 2, 3, 4, 5]))
    with pytest.raises(CodecError):
        group_varint_decode(buf[:-1], 5)
    with pytest.raises(CodecError):
        group_varint_decode(buf, 9)


def test_delta_codec_is_exact_inverse():
    v = np.array([0, 0, 3, 3, 10, 2**31, 2**32 - 1], np.int64)
    np.testing.assert_array_equal(delta_decode(delta_encode(v)), v)


def test_postings_roundtrip_with_empty_slots_and_restarts():
    # slot layout: [dense run] [] [singleton] [] [] [2^30-adjacent ids]
    lists = [np.arange(500), np.array([], np.int64), np.array([7]),
             np.array([], np.int64), np.array([], np.int64),
             np.array([2**30 - 2, 2**30 - 1, 2**30, 2**30 + 1])]
    postings = np.concatenate(lists)
    offsets = np.zeros(len(lists) + 1, np.int64)
    np.cumsum([len(x) for x in lists], out=offsets[1:])
    cp = encode_postings(postings, offsets)
    post2, off2 = decode_postings(cp)
    np.testing.assert_array_equal(post2, postings)
    np.testing.assert_array_equal(off2, offsets)
    # deltas restart absolute at slot boundaries: dropping a slot must not
    # shift later slots (decode only needs the slot's own bytes)
    assert cp.counts.tolist() == [500, 0, 1, 0, 0, 4]


def test_postings_encode_validates_input():
    with pytest.raises(CodecError):    # descending within a slot
        encode_postings(np.array([5, 3]), np.array([0, 2]))
    with pytest.raises(CodecError):    # negative id
        encode_postings(np.array([-1]), np.array([0, 1]))
    with pytest.raises(CodecError):    # framing mismatch
        encode_postings(np.array([1, 2]), np.array([0, 1]))


def test_quantize_int8_error_is_within_half_scale():
    rng = np.random.default_rng(0)
    x = rng.normal(size=(256, 16)).astype(np.float32) * 3.0
    q, scales = quantize_int8(x, block=64)
    assert q.dtype == np.int8 and scales.shape == (4,)
    err = np.abs(dequantize_int8(q, scales, block=64) - x)
    bound = np.repeat(quantization_error_bound(scales), 64)[:, None]
    assert np.all(err <= bound + 1e-7)


def test_quantize_int8_zero_block_is_exact():
    x = np.zeros((64, 8), np.float32)
    q, scales = quantize_int8(x, block=64)
    assert np.all(q == 0) and scales[0] == 1.0
    np.testing.assert_array_equal(dequantize_int8(q, scales, block=64), x)


def test_pattern_dict_roundtrip_and_shrinks_clustered_patterns():
    rng = np.random.default_rng(1)
    protos = rng.integers(0, 2**32, size=(5, 4), dtype=np.uint32)
    bits = protos[rng.integers(0, 5, size=300)]
    uniq, inverse = pattern_dict_encode(bits)
    assert uniq.shape[0] == 5
    np.testing.assert_array_equal(pattern_dict_decode(uniq, inverse), bits)


def test_table_csr_bridges_are_exact_inverses():
    tau, mask = _mapped(unit_factors(200, CFG.k, 2))
    idx = InvertedIndex(tau, CFG.p, mask)
    counts = np.diff(idx.offsets)
    bucket = int(counts.max()) + 3
    table, counts2 = csr_to_table(idx.postings, idx.offsets, bucket,
                                  sentinel=-5)
    np.testing.assert_array_equal(counts2, counts)
    post, off = table_to_csr(table, counts2)
    np.testing.assert_array_equal(post, idx.postings)
    np.testing.assert_array_equal(off, idx.offsets)


# ------------------------------------------------- pattern-factored index


def test_compressed_index_query_is_bit_identical():
    tau, mask = _mapped(unit_factors(400, CFG.k, 3))
    idx = InvertedIndex(tau, CFG.p, mask)
    cidx = idx.compress()
    assert cidx.nbytes < idx.nbytes
    q_tau, q_mask = _mapped(unit_factors(20, CFG.k, 4))
    for qi in range(20):
        for mo in (1, 2, 4):
            ids_a, ov_a = idx.query(q_tau[qi], mo, q_mask[qi])
            ids_b, ov_b = cidx.query(q_tau[qi], mo, q_mask[qi])
            np.testing.assert_array_equal(ids_a, ids_b)
            np.testing.assert_array_equal(ov_a, ov_b)
            assert ids_b.dtype == ids_a.dtype and ov_b.dtype == ov_a.dtype


def test_compressed_index_posting_lists_and_decompress_roundtrip():
    tau, mask = _mapped(unit_factors(300, CFG.k, 5))
    idx = InvertedIndex(tau, CFG.p, mask)
    cidx = idx.compress()
    for s in range(CFG.p):
        np.testing.assert_array_equal(cidx.posting_list(s),
                                      idx.posting_list(s))
    flat = cidx.decompress()
    np.testing.assert_array_equal(flat.postings, idx.postings)
    np.testing.assert_array_equal(flat.offsets, idx.offsets)
    assert (flat.n_items, flat.p, flat.k) == (idx.n_items, idx.p, idx.k)


def test_compressed_index_empty_query_and_empty_catalog():
    tau, mask = _mapped(unit_factors(10, CFG.k, 6))
    cidx = InvertedIndex(tau, CFG.p, mask).compress()
    ids, ov = cidx.query(np.empty(0, np.int64), 1)
    assert ids.size == 0 and ov.size == 0
    empty = InvertedIndex(np.zeros((0, CFG.k), np.int32), CFG.p).compress()
    ids, ov = empty.query(tau[0], 1, mask[0])
    assert ids.size == 0 and ov.size == 0


# --------------------------------------------------------- serving parity


@pytest.mark.parametrize("backend", ["gam", "gam-device", "sharded",
                                     "sharded-multihost"])
def test_compressed_path_ids_match_f32_path_bitwise(backend):
    items = unit_factors(240, CFG.k, 7)
    users = unit_factors(8, CFG.k, 8)
    plain = open_retriever(_spec(backend), items)
    comp = open_retriever(_compressed(backend), items)
    for exact in (False, True):
        a = plain.query(users, KAPPA, exact=exact)
        b = comp.query(users, KAPPA, exact=exact)
        np.testing.assert_array_equal(a.ids, b.ids, err_msg=f"exact={exact}")
        np.testing.assert_allclose(b.scores, a.scores, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("backend", ["gam-device", "sharded",
                                     "sharded-multihost"])
def test_compressed_exact_path_matches_brute_oracle(backend):
    items = unit_factors(240, CFG.k, 7)
    users = unit_factors(8, CFG.k, 8)
    oracle = open_retriever(_spec("brute"), items)
    comp = open_retriever(_compressed(backend), items)
    a = oracle.query(users, KAPPA)
    b = comp.query(users, KAPPA, exact=True)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_allclose(b.scores, a.scores, rtol=1e-5, atol=1e-6)


def _catalog_bytes_per_item(retriever):
    """Serving-state bytes a row of a ``gam-device`` catalog costs: the
    factors (int8 slab and block scales, or f32 rows), the posting table
    (varint CSR when compressed) and the pattern bitsets (a dictionary of
    distinct patterns when compressed)."""
    meta = retriever._retrieve_meta
    compressed = retriever.spec.compress_postings
    if meta.quantize == "int8":
        factor = np.asarray(meta.factors_q).nbytes + np.asarray(
            meta.scales).nbytes
    else:
        factor = retriever.items.nbytes
    table = np.asarray(retriever.device_index.table)
    counts = np.asarray(retriever.device_index.counts)
    bits = np.asarray(meta.item_bits_t)
    if compressed:
        index = encode_postings(*table_to_csr(table, counts)).nbytes
        rows = np.ascontiguousarray(bits.T[:retriever.n_items])
        patterns = pattern_dict_nbytes(*pattern_dict_encode(rows))
    else:
        index = table.nbytes + counts.nbytes
        patterns = bits.nbytes
    return (factor + index + patterns) / retriever.n_items


@pytest.mark.parametrize("rerank_factor,compress", [(2, False), (4, False),
                                                    (4, True)])
def test_int8_catalog_keeps_exact_path_ids_in_fewer_bytes(rerank_factor,
                                                          compress):
    """Each int8 representation answers the exact path with the f32
    path's ids, in fewer bytes a row; with varint postings and the pattern
    dictionary, at most a quarter of f32's."""
    items = unit_factors(240, CFG.k, 7)
    users = unit_factors(8, CFG.k, 8)
    tau, mask = _mapped(items)
    bucket = int(np.bincount(tau[mask].ravel(), minlength=CFG.p).max())
    f32 = open_retriever(_spec("gam-device", bucket=bucket), items)
    int8 = open_retriever(
        _spec("gam-device", bucket=bucket, quantize="int8",
              rerank_factor=rerank_factor, compress_postings=compress),
        items)
    np.testing.assert_array_equal(
        int8.query(users, KAPPA, exact=True).ids,
        f32.query(users, KAPPA, exact=True).ids)
    assert int8.stats()["n_spill"] == f32.stats()["n_spill"] == 0
    f32_bytes = _catalog_bytes_per_item(f32)
    int8_bytes = _catalog_bytes_per_item(int8)
    assert int8_bytes < f32_bytes
    if compress:
        assert 4 * int8_bytes <= f32_bytes, (int8_bytes, f32_bytes)


@pytest.mark.parametrize("backend", ["gam", "gam-device", "sharded"])
def test_snapshot_v4_roundtrip_is_bitwise(backend, tmp_path):
    items = unit_factors(220, CFG.k, 9)
    users = unit_factors(6, CFG.k, 10)
    spec = _compressed(backend)
    ret = open_retriever(spec, items)
    before = ret.query(users, KAPPA)
    path = str(tmp_path / "snap")
    ret.snapshot(path)
    restored = open_retriever(spec, snapshot=path)
    after = restored.query(users, KAPPA)
    np.testing.assert_array_equal(before.ids, after.ids)
    np.testing.assert_array_equal(before.scores, after.scores)


def test_snapshot_quantize_mismatch_fails_loudly(tmp_path):
    items = unit_factors(100, CFG.k, 11)
    ret = open_retriever(_compressed("gam-device"), items)
    path = str(tmp_path / "snap")
    ret.snapshot(path)
    with pytest.raises(ValueError, match="quantize"):
        open_retriever(_spec("gam-device"), snapshot=path)


def test_live_delta_upserts_stay_quantized_and_bit_identical():
    items = unit_factors(200, CFG.k, 12)
    users = unit_factors(6, CFG.k, 13)
    plain = open_retriever(_spec("sharded"), items)
    comp = open_retriever(_compressed("sharded"), items)
    new_ids = np.arange(200, 260, dtype=np.int64)
    new_fac = unit_factors(60, CFG.k, 14)
    for r in (plain, comp):
        r.upsert(new_ids, new_fac)
    # the delta tier re-quantized only its own rows: base metas untouched
    assert comp.delta._meta.quantize == "int8"
    assert comp.base.metas[0].quantize == "int8"
    a, b = plain.query(users, KAPPA), comp.query(users, KAPPA)
    np.testing.assert_array_equal(a.ids, b.ids)
    np.testing.assert_allclose(b.scores, a.scores, rtol=1e-5, atol=1e-6)
    for r in (plain, comp):
        r.compact()
    a2, b2 = plain.query(users, KAPPA), comp.query(users, KAPPA)
    np.testing.assert_array_equal(a.ids, a2.ids)
    np.testing.assert_array_equal(b.ids, b2.ids)
    np.testing.assert_array_equal(np.asarray(b.scores),
                                  np.asarray(b2.scores))


# ----------------------------------------------------- row-capacity guards


def test_build_retrieval_meta_rejects_rows_past_the_sentinel():
    tau = np.zeros((4, CFG.k), np.int32)
    mask = np.ones((4, CFG.k), bool)
    # the guard fires on the PADDED row count, before any O(n_pad) work
    with pytest.raises(RowCapacityError, match="[Ss]hard the catalog"):
        build_retrieval_meta(tau, mask, CFG.p, n_rows=ROW_CAPACITY + 1,
                             bn=8)
    # exactly at capacity is legal (rows 0..2^30-1 never collide)
    meta = build_retrieval_meta(tau, mask, CFG.p, n_rows=8, bn=8)
    assert meta.n_pad == 8


def test_partition_rejects_caps_past_the_sentinel():
    at_cap = ROW_CAPACITY
    # boundary pass: total == 2^30 structural rows is the last legal layout
    Partition((8, 8), (8, 8), (8, at_cap - 8))
    with pytest.raises(RowCapacityError, match="partition"):
        Partition((8, 8), (8, 8), (16, at_cap - 8))


# ------------------------------------------------------- hypothesis suite
#
# Unlike test_properties.py (all-hypothesis, module-level importorskip),
# this module's deterministic tests above must still run when hypothesis is
# absent — so the property suite is defined conditionally instead.
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
    _HAVE_HYPOTHESIS = True
except ImportError:
    _HAVE_HYPOTHESIS = False

if _HAVE_HYPOTHESIS:
    _ADVERSARIAL_BASES = st.sampled_from([0, 1, 255, 2**16 - 1, 2**24,
                                          2**30 - 2, 2**30, 2**32 - 260])


    @pytest.mark.slow
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(0, 200),
           _ADVERSARIAL_BASES)
    def test_property_varint_roundtrips_adversarial_values(seed, n, base):
        rng = np.random.default_rng(seed)
        # mixture of tiny deltas (dense runs) and full-width values near base
        vals = base + np.sort(rng.choice(256, size=n, replace=True))
        vals = np.minimum(vals, 2**32 - 1)
        buf = group_varint_encode(vals)
        np.testing.assert_array_equal(group_varint_decode(buf, n), vals)

    @pytest.mark.slow
    @settings(max_examples=60, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 40), _ADVERSARIAL_BASES)
    def test_property_postings_roundtrip_adversarial_csr(seed, p, base):
        rng = np.random.default_rng(seed)
        counts = rng.choice([0, 0, 1, 2, 17], size=p)
        lists = [base + np.sort(rng.choice(300, size=c, replace=False))
                 for c in counts]
        postings = (np.concatenate(lists) if lists
                    else np.zeros(0, np.int64)).astype(np.int64)
        postings = np.minimum(postings, 2**32 - 1)
        offsets = np.zeros(p + 1, np.int64)
        np.cumsum(counts, out=offsets[1:])
        cp = encode_postings(postings, offsets)
        post2, off2 = decode_postings(cp)
        np.testing.assert_array_equal(post2, postings)
        np.testing.assert_array_equal(off2, offsets)

    @pytest.mark.slow
    @settings(max_examples=40, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.sampled_from([8, 32, 128]),
           st.floats(1e-6, 1e4))
    def test_property_int8_error_bounded_per_block_scale(seed, block, spread):
        rng = np.random.default_rng(seed)
        x = (rng.normal(size=(block * 3, 8)) * spread).astype(np.float32)
        q, scales = quantize_int8(x, block=block)
        err = np.abs(dequantize_int8(q, scales, block=block) - x)
        bound = np.repeat(quantization_error_bound(scales), block)[:, None]
        assert np.all(err <= bound * (1 + 1e-6) + 1e-12)

    @pytest.mark.slow
    @settings(max_examples=25, deadline=None)
    @given(st.integers(0, 2**31 - 1), st.integers(1, 120))
    def test_property_compressed_index_parity_random_catalogs(seed, n):
        rng = np.random.default_rng(seed)
        tau, mask = _mapped(rng.normal(size=(n, CFG.k)).astype(np.float32))
        idx = InvertedIndex(tau, CFG.p, mask)
        cidx = idx.compress()
        flat = cidx.decompress()
        np.testing.assert_array_equal(flat.postings, idx.postings)
        np.testing.assert_array_equal(flat.offsets, idx.offsets)
        qi = rng.integers(0, n)
        for mo in (1, 3):
            ids_a, ov_a = idx.query(tau[qi], mo, mask[qi])
            ids_b, ov_b = cidx.query(tau[qi], mo, mask[qi])
            np.testing.assert_array_equal(ids_a, ids_b)
            np.testing.assert_array_equal(ov_a, ov_b)
