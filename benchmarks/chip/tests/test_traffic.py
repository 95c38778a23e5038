"""The traffic generator and the seeding."""
import numpy as np
import pytest

from chipbench import data, traffic


def test_open_loop_offers_the_same_work_on_every_seed():
    t = {"loop": "open", "rate_qps": 1234.0, "queries": {"kind": "fresh"}}
    a = traffic.plan(t, 10.0, data.host_rng(1, 1))
    b = traffic.plan(t, 10.0, data.host_rng(2, 1))
    c = traffic.plan(t, 10.0, data.host_rng(1, 1))
    assert a.n == b.n == 12340
    assert np.all(np.diff(a.due) >= 0) and 0 <= a.due[0] and a.due[-1] < 10
    np.testing.assert_array_equal(a.due, c.due)
    assert not np.array_equal(a.due, b.due)
    np.testing.assert_array_equal(a.qidx, np.arange(a.n))


def test_zipf_pool_repeats_hot_identities():
    t = {"loop": "open", "rate_qps": 1000.0,
         "queries": {"kind": "zipf", "pool": 100, "s": 1.1}}
    s = traffic.plan(t, 5.0, data.host_rng(4, 1))
    assert s.n_rows == 100 and s.qidx.max() < 100
    counts = np.bincount(s.qidx, minlength=100)
    assert counts[0] > counts[10] > counts[99]


def test_large_seeds_are_not_truncated():
    assert data.seed_words(5) != data.seed_words(5 + 2 ** 32)
    assert data.seed_words(2 ** 40 + 7) == data.seed_words(2 ** 40 + 7)
    with pytest.raises(ValueError):
        data.seed_words(-1)


def test_rows_from_the_seed():
    cfg = {"n_items": 50, "dim": 8, "items": {"norm_lognormal_sigma": 0.0},
           "queries": {"norm_lognormal_sigma": 0.25}}
    a = data.make_rows(2 ** 33 + 1, cfg, 20)
    b = data.make_rows(2 ** 33 + 1, cfg, 20)
    c = data.make_rows(1, cfg, 20)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    assert not np.array_equal(a[0], c[0])
    np.testing.assert_allclose(np.linalg.norm(a[0], axis=1), 1.0, rtol=1e-5)
    assert a[1].shape == (20, 8)
