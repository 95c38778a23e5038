"""Inverted index + end-to-end retrieval behaviour (paper §1.1, §6)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.inverted_index import DeviceIndex, InvertedIndex
from repro.core.mapping import GamConfig, densify, pattern_overlap, sparse_map
from repro.core.retrieval import recovery_accuracy
from repro.retriever import RetrieverSpec, open_retriever


def _gam(items, cfg, **kw):
    device = kw.pop("device", False)
    return open_retriever(
        RetrieverSpec(cfg=cfg, backend="gam-device" if device else "gam",
                      **kw),
        items=items)


def _brute(items):
    return open_retriever(
        RetrieverSpec(cfg=GamConfig(k=items.shape[1]), backend="brute"),
        items=items)


def _factors(n, k, seed):
    z = np.random.default_rng(seed).normal(size=(n, k)).astype(np.float32)
    return z / np.linalg.norm(z, axis=1, keepdims=True)


# ---------------------------------------------------------------- mapping


@pytest.mark.parametrize("scheme", ["one_hot", "parse_tree", "one_hot_dary"])
def test_sparse_map_preserves_values(scheme):
    cfg = GamConfig(k=16, scheme=scheme, d=4)
    z = jnp.asarray(_factors(8, 16, 0))
    tau, vals = sparse_map(z, cfg)
    dense = np.asarray(densify(tau, vals, cfg.p))
    # phi is a permutation of the zero-padded z: values preserved, norm too
    np.testing.assert_allclose(np.linalg.norm(dense, axis=1), 1.0, atol=1e-5)
    np.testing.assert_allclose(
        np.sort(np.abs(dense), axis=1)[:, -16:],
        np.sort(np.abs(np.asarray(z)), axis=1),
        atol=1e-6,
    )


def test_close_factors_overlap_far_factors_conflict():
    """The paper's central geometric requirement on phi."""
    cfg = GamConfig(k=12, scheme="parse_tree")
    rng = np.random.default_rng(42)
    base = _factors(1, 12, 1)[0]
    near = base + 0.05 * rng.normal(size=(64, 12)).astype(np.float32)
    far = -base + 0.05 * rng.normal(size=(64, 12)).astype(np.float32)
    tau_b, _ = sparse_map(jnp.asarray(base[None]), cfg)
    tau_n, _ = sparse_map(jnp.asarray(near), cfg)
    tau_f, _ = sparse_map(jnp.asarray(far), cfg)
    ov_near = np.asarray(pattern_overlap(tau_b, tau_n)).mean()
    ov_far = np.asarray(pattern_overlap(tau_b, tau_f)).mean()
    assert ov_near > 4 * max(ov_far, 0.5)


def _check_overlap_along_great_circle(k, seed):
    """Overlap of phi(z) with phi at nine angles from z to -z.

    A parse-tree slot encodes a coordinate: a support slot its index and
    sign, a zero-run slot its index and the last support coordinate before
    it (with that one's sign), or, before the first support coordinate,
    its index alone.  So two points share exactly the support coordinates
    of equal sign, plus some of the coordinates zero in both."""
    from repro.core.tessellation import ternary_pattern
    cfg = GamConfig(k=k, scheme="parse_tree")
    rng = np.random.default_rng(seed)
    z = rng.normal(size=(k,)).astype(np.float32)
    z /= np.linalg.norm(z)
    orth = rng.normal(size=(k,)).astype(np.float32)
    orth -= (orth @ z) * z
    orth /= np.linalg.norm(orth)
    angles = np.linspace(0, np.pi, 9)
    pts = np.stack([np.cos(a) * z + np.sin(a) * orth for a in angles])
    tau, _ = sparse_map(jnp.asarray(pts), cfg)
    tau0, _ = sparse_map(jnp.asarray(z[None]), cfg)
    ov = np.asarray(pattern_overlap(tau0, tau))
    # overlap at angle 0 is full; at pi the support signs are mirrored so
    # only matching zero-runs may still share slots — less than full
    assert ov[0] == k
    assert ov[-1] < k
    # support coordinates (nonzero pattern) never overlap at angle pi
    p0 = np.asarray(ternary_pattern(jnp.asarray(z[None])))[0]
    pats = np.asarray(ternary_pattern(jnp.asarray(pts)))
    ppi = pats[-1]
    t0, tpi = np.asarray(tau0)[0], np.asarray(tau)[-1]
    sup_slots0 = set(t0[p0 != 0].tolist())
    sup_slots_pi = set(tpi[ppi != 0].tolist())
    assert not (sup_slots0 & sup_slots_pi)
    # at every angle: the same-sign support coordinates always share their
    # slot, and past them only coordinates zero in both points can
    same_sign = ((pats == p0) & (p0 != 0)).sum(axis=1)
    both_zero = ((pats == 0) & (p0 == 0)).sum(axis=1)
    assert np.all(ov >= same_sign)
    assert np.all(ov <= same_sign + both_zero)
    # at pi every zero run follows a support coordinate of the other sign,
    # except the leading one: the antipode shares exactly the slots before
    # z's first support coordinate
    assert ov[-1] == int(np.argmax(p0 != 0))


def test_overlap_decreases_with_angle_property():
    pytest.importorskip("hypothesis")
    from hypothesis import example, given, settings
    from hypothesis import strategies as st

    @settings(max_examples=25, deadline=None)
    @given(st.integers(4, 24), st.integers(0, 2**31 - 1))
    @example(5, 51272)
    def check(k, seed):
        _check_overlap_along_great_circle(k, seed)

    check()


# ---------------------------------------------------------------- index


def test_inverted_index_matches_naive():
    cfg = GamConfig(k=8, scheme="parse_tree")
    items = _factors(200, 8, 3)
    tau, _ = sparse_map(jnp.asarray(items), cfg)
    tau = np.asarray(tau)
    idx = InvertedIndex(tau, cfg.p)
    q = tau[17]
    ids, ov = idx.query(q)
    naive_ov = (tau[:, :, None] == q[None, None, :]).sum((1, 2))
    naive_ids = np.nonzero(naive_ov >= 1)[0]
    np.testing.assert_array_equal(ids, naive_ids)
    np.testing.assert_array_equal(ov, naive_ov[naive_ids])
    assert 17 in ids  # self always a candidate


def test_device_index_matches_cpu_index():
    cfg = GamConfig(k=8, scheme="parse_tree")
    items = _factors(150, 8, 4)
    tau, _ = sparse_map(jnp.asarray(items), cfg)
    tau = np.asarray(tau)
    cpu = InvertedIndex(tau, cfg.p)
    dev = DeviceIndex.build(tau, cfg.p, bucket=256)
    for qi in (0, 7, 99):
        ids, _ = cpu.query(tau[qi], min_overlap=2)
        mask = np.asarray(dev.candidate_mask(jnp.asarray(tau[qi]), min_overlap=2))
        np.testing.assert_array_equal(np.nonzero(mask)[0], ids)


def test_device_index_spill_preserves_recall():
    cfg = GamConfig(k=6, scheme="one_hot")
    items = _factors(300, 6, 5)
    tau, _ = sparse_map(jnp.asarray(items), cfg)
    tau = np.asarray(tau)
    dev = DeviceIndex.build(tau, cfg.p, bucket=4)  # force overflow
    cpu = InvertedIndex(tau, cfg.p)
    ids, _ = cpu.query(tau[0])
    mask = np.asarray(dev.candidate_mask(jnp.asarray(tau[0])))
    assert set(ids.tolist()) <= set(np.nonzero(mask)[0].tolist())


# ---------------------------------------------------------------- retrieval


def test_gam_retriever_end_to_end():
    k, n, q, kappa = 16, 500, 40, 10
    items = _factors(n, k, 6)
    users = _factors(q, k, 7)
    brute = _brute(items).query(users, kappa)
    # the paper feeds factors "after some thresholding" (§6)
    gam = _gam(items, GamConfig(k=k, scheme="parse_tree", threshold=0.2),
               min_overlap=2)
    res = gam.query(users, kappa)
    acc = recovery_accuracy(res.ids, brute.ids).mean()
    disc = res.discarded_frac.mean()
    assert acc > 0.9, f"recovery accuracy too low: {acc}"
    assert disc > 0.4, f"not discarding enough: {disc}"
    # retrieved scores are exact inner products
    for qi in range(q):
        for slot in range(kappa):
            iid = res.ids[qi, slot]
            if iid >= 0:
                np.testing.assert_allclose(
                    res.scores[qi, slot], users[qi] @ items[iid], rtol=1e-4
                )


def test_min_overlap_trades_recall_for_discard():
    k, n = 12, 400
    items = _factors(n, k, 8)
    users = _factors(30, k, 9)
    brute = _brute(items).query(users, 10)
    r1 = _gam(items, GamConfig(k=k), min_overlap=1).query(users, 10)
    r3 = _gam(items, GamConfig(k=k), min_overlap=3).query(users, 10)
    assert r3.discarded_frac.mean() >= r1.discarded_frac.mean()
    assert (
        recovery_accuracy(r1.ids, brute.ids).mean()
        >= recovery_accuracy(r3.ids, brute.ids).mean() - 1e-9
    )


def test_device_candidate_masks_jit_path():
    k = 8
    items = _factors(120, k, 10)
    users = _factors(5, k, 11)
    gam = _gam(items, GamConfig(k=k), device=True)
    masks = np.asarray(gam.candidate_masks(users))
    assert masks.shape == (5, 120)
    res = gam.query(users, 5)
    for qi in range(5):
        cpu_cand = set(res.ids[qi][res.ids[qi] >= 0].tolist())
        assert cpu_cand <= set(np.nonzero(masks[qi])[0].tolist())


def test_whiten_flag_runs_and_scores_stay_exact():
    """Whitening (paper §5 non-uniform-tessellation realisation) changes the
    candidate sets but never the returned scores (always raw inner
    products).  NOTE: EXPERIMENTS.md records that whitening HURTS MIPS
    recovery on anisotropic data — kept as a documented negative result."""
    rng = np.random.default_rng(1)
    scale = np.array([4.0, 3.0] + [1.0] * 8, np.float32)
    v = rng.normal(size=(500, 10)).astype(np.float32) * scale
    u = rng.normal(size=(10, 10)).astype(np.float32) * scale
    gam = _gam(v, GamConfig(k=10, scheme="parse_tree", threshold=0.3),
               min_overlap=2, whiten=True)
    res = gam.query(u, 5)
    for qi in range(10):
        for slot in range(5):
            iid = res.ids[qi, slot]
            if iid >= 0:
                np.testing.assert_allclose(
                    res.scores[qi, slot], u[qi] @ v[iid], rtol=1e-4)
