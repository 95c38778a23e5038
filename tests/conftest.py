"""Shared test fixtures and catalog helpers.

The retrieval suites all need the same ingredients — the standard 16-dim
parse-tree mapping schema, unit-norm random factor catalogs, and a
deterministic per-test RNG — which used to be copy-pasted per file
(``_factors``/``CFG`` in test_service, test_retriever_contract,
test_gam_retrieve, ...).  They live here now: module-scope helpers
(importable as ``from conftest import CFG, unit_factors`` for use in
parametrize lists and module-level constants) plus fixture spellings for
test bodies.
"""
import os
import zlib

import numpy as np
import pytest

from repro.core.mapping import GamConfig

# the standard mapping schema of the retrieval test suites
CFG = GamConfig(k=16, scheme="parse_tree", threshold=0.2)

#: Parity between two differently shaped computations of the same scores
#: (a kernel tile vs the dense (Q, N) product, one shard layout vs another)
#: holds for ids exactly and for scores up to float summation order: XLA
#: picks each dot's accumulation order from its shape, on the CPU backend
#: as on the MXU.  The bound is in ulps of 1.0 (float32 eps), the largest
#: term a dot of unit-norm rows sums, taken as both the absolute and the
#: relative tolerance; the largest difference the suite shows is one such
#: ulp.  It is not ``np.testing.assert_array_max_ulp``'s ulps of each
#: value: a score that cancels to near 0 keeps the rounding of its terms,
#: which is many of its own ulps.
SCORE_MAX_ULP = 4


def assert_scores_match(actual, desired) -> None:
    """Scores equal up to float summation order (see SCORE_MAX_ULP)."""
    tol = SCORE_MAX_ULP * np.finfo(np.float32).eps
    np.testing.assert_allclose(actual, desired, rtol=tol, atol=tol)


def unit_factors(n: int, k: int = 16, seed: int = 0) -> np.ndarray:
    """(n, k) unit-norm float32 factor rows, deterministic in ``seed``."""
    z = np.random.default_rng(seed).normal(size=(n, k)).astype(np.float32)
    return z / np.linalg.norm(z, axis=1, keepdims=True)


def pytest_configure(config):
    # the mesh suites (sharding rules, dry-run wiring, index placement) need
    # eight host devices; the count locks when JAX starts its CPU backend,
    # so it is set here, before any test module is imported
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="session")
def cfg() -> GamConfig:
    return CFG


@pytest.fixture
def make_factors():
    """Factory fixture: ``make_factors(n, k=16, seed=0)``."""
    return unit_factors


@pytest.fixture
def rng(request) -> np.random.Generator:
    """Per-test seeded RNG — deterministic across runs (the seed is a crc32
    of the test's nodeid, stable unlike ``hash()``), independent across
    tests."""
    return np.random.default_rng(zlib.crc32(request.node.nodeid.encode()))


@pytest.fixture
def catalog() -> np.ndarray:
    """The shared 300-item test catalog."""
    return unit_factors(300, CFG.k, 0)


@pytest.fixture
def users() -> np.ndarray:
    """The shared 12-row query batch."""
    return unit_factors(12, CFG.k, 1)
