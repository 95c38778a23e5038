"""Catalogs and queries from ``--seed``, made on the device in one call.

A configuration file states the distribution under ``items`` and
``queries``: isotropic Gaussian directions, scaled to unit norm and then by
a log-normal norm of the given sigma (0 keeps unit norm).  The seed changes
which vectors are drawn, never how many or from what distribution.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed: int, n: int = 2) -> list[int]:
    """Whole-number seed of any size -> ``n`` 32-bit words.  JAX keys take
    32 bits, so a large seed is folded through numpy's SeedSequence rather
    than truncated."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return [int(w) for w in np.random.SeedSequence(seed).generate_state(n)]


def host_rng(seed: int, stream: int) -> np.random.Generator:
    """Numpy generator for host-side draws (arrival times, samples): one
    independent stream per purpose."""
    return np.random.default_rng(np.random.SeedSequence(seed,
                                                        spawn_key=(stream,)))


def make_rows(seed: int, config: dict, n_queries: int):
    """-> (items (n_items, dim), queries (n_queries, dim)) float32 host
    arrays, drawn on the default device in one jitted call."""
    w0, w1 = seed_words(seed)
    key = jax.random.fold_in(jax.random.key(w0), w1)
    items, queries = _draw(
        key, int(config["n_items"]), int(n_queries), int(config["dim"]),
        float(config["items"]["norm_lognormal_sigma"]),
        float(config["queries"]["norm_lognormal_sigma"]))
    return np.asarray(items), np.asarray(queries)


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _draw(key, n_items, n_queries, dim, item_sigma, query_sigma):
    def rows(k, n, sigma):
        kd, kn = jax.random.split(k)
        x = jax.random.normal(kd, (n, dim), jnp.float32)
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        if sigma:
            x = x * jnp.exp(sigma * jax.random.normal(kn, (n, 1),
                                                      jnp.float32))
        return x

    ki, kq = jax.random.split(key)
    return rows(ki, n_items, item_sigma), rows(kq, n_queries, query_sigma)
