"""The plain reference and the semantic-work counter against brute
counts on tiny catalogs."""
import numpy as np
import pytest

from chipbench import reference as ref


def brute_pattern(z, threshold):
    """Algorithm 2 and the parse-tree counter, one row at a time."""
    k = z.size
    zt = np.where(np.abs(z) >= np.float32(threshold), z, 0).astype(np.float64)
    order = sorted(range(k), key=lambda j: (-abs(zt[j]), j))
    best, t_star, run = -1.0, 0, 0.0
    for t, j in enumerate(order):
        run += abs(zt[j])
        zs = run / np.sqrt(t + 1)
        if zs > best:
            best, t_star = zs, t
    support = set(order[:t_star + 1])
    slots, prev = set(), 0
    for j in range(k):
        jj = j + 1
        a = (1 if zt[j] >= 0 else -1) if j in support else 0
        cur = k * jj if a == 1 else k * (k + jj) if a == -1 else prev + 1
        if zt[j] != 0:
            slots.add(cur)
        prev = cur
    return slots


def rows(n, d, seed, sigma=0.3):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((n, d)).astype(np.float32)
    x /= np.linalg.norm(x, axis=1, keepdims=True)
    return x * np.exp(sigma * rng.standard_normal((n, 1))).astype(np.float32)


@pytest.mark.parametrize("d", [8, 16, 64])
def test_patterns_equal_brute(d):
    z = rows(300, d, d)
    r, s, amb = ref.gam_patterns(z, 0.2)
    per_row = ref.split_rows(r, s, z.shape[0])
    for i in range(z.shape[0]):
        assert set(per_row[i].tolist()) == brute_pattern(z[i], 0.2)
    assert not amb.any()


def test_patterns_equal_the_program_map():
    """Cross-check with the program's own map (the reference never calls
    it; this only shows the two agree)."""
    import sys
    from pathlib import Path

    sys.path.insert(0, str(Path(__file__).resolve().parents[3] / "src"))
    import jax.numpy as jnp
    from repro.core.mapping import GamConfig, sparse_map

    z = rows(2000, 32, 7)
    tau, vals = sparse_map(jnp.asarray(z), GamConfig(k=32,
                                                     threshold=0.2))
    tau, mask = np.asarray(tau), np.asarray(vals) != 0
    r, s, _ = ref.gam_patterns(z, 0.2)
    pr, pc = np.nonzero(mask)
    np.testing.assert_array_equal(r, pr)
    np.testing.assert_array_equal(s, tau[pr, pc])


def test_threaded_catalog_map_equals_one_pass():
    z = rows(5000, 16, 3)
    a = ref.gam_patterns(z, 0.2)
    b = ref.map_catalog(z, 0.2, threads=3, chunk=700)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("mo", [1, 2, 3])
def test_candidates_equal_brute_overlap(mo):
    items, queries = rows(800, 16, 1), rows(40, 16, 2)
    r, s, _ = ref.gam_patterns(items, 0.2)
    idx = ref.PostingIndex(r, s, items.shape[0])
    pats = [brute_pattern(x, 0.2) for x in items]
    for q in queries:
        qs = brute_pattern(q, 0.2)
        want = [i for i, p in enumerate(pats) if len(p & qs) >= mo]
        got = idx.candidates(np.array(sorted(qs)), mo)
        np.testing.assert_array_equal(got, want)


def test_longest_posting_per_shard_equals_brute():
    items = rows(1001, 8, 4)
    r, s, _ = ref.gam_patterns(items, 0.2)
    per_row = ref.split_rows(r, s, items.shape[0])
    cap = -(-1001 // 3)
    want = 0
    for sh in range(3):
        counts = {}
        for i in range(sh * cap, min((sh + 1) * cap, 1001)):
            for slot in per_row[i].tolist():
                counts[slot] = counts.get(slot, 0) + 1
        want = max(want, max(counts.values()))
    assert ref.longest_posting_per_shard(r, s, 1001, 3) == want


@pytest.mark.parametrize("row_bytes,pool", [(64, 0), (16, 40)])
def test_batch_work_equals_brute_count(row_bytes, pool):
    items, queries = rows(600, 16, 5), rows(12, 16, 6)
    r, s, _ = ref.gam_patterns(items, 0.2)
    idx = ref.PostingIndex(r, s, items.shape[0])
    pats = [brute_pattern(x, 0.2) for x in items]
    qpats = [brute_pattern(q, 0.2) for q in queries]
    cand = [{i for i, p in enumerate(pats) if len(p & qp) >= 2}
            for qp in qpats]
    union = set().union(*cand)
    want_bytes = len(union) * (row_bytes + 4) + len(queries) * pool * 16 * 4
    want_flops = 2 * 16 * sum(len(c) for c in cand)
    got = ref.batch_work(idx, [np.array(sorted(q)) for q in qpats], 2,
                         dim=16, row_bytes=row_bytes, pool_rows=pool)
    assert got == (float(want_bytes), float(want_flops))


def test_bf16_rounding():
    x = np.array([1.0, 1.00390625, 1.005859375, 3.14159265, -2.5e-3],
                 np.float32)
    got = ref._bf16(x)
    # bfloat16 keeps 8 significant bits; ties go to even
    np.testing.assert_array_equal(got[:3], [1.0, 1.0, 1.0078125])
    assert abs(got[3] - 3.140625) < 1e-7
    assert np.all(np.abs(got - x) <= np.abs(x) * 2.0 ** -8)


def test_lower_precisions_lie_further_off():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2000, 64)).astype(np.float32)
    q = rng.standard_normal(64).astype(np.float32)
    exact = ref.emulate_dot(q, x, "f64")
    e3 = np.abs(ref.emulate_dot(q, x, "bf16x3") - exact).max()
    e1 = np.abs(ref.emulate_dot(q, x, "bf16") - exact).max()
    f32 = np.abs((x @ q).astype(np.float64) - exact).max()
    assert f32 < e3 < e1
