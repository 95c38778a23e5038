"""Pallas TPU kernel: dense masked MIPS scoring (reference path).

After the inverted index produces a candidate mask, exact scores are needed
only where the mask is set.  The kernel fuses the (Q_blk x k) @ (k x N_blk)
MXU matmul with the candidate masking so the (Q, N) score tensor is written
to HBM exactly once with -inf in discarded slots — no second masking pass,
and the downstream top-k consumes it directly.

The serving hot loop no longer runs this: ``gam_retrieve`` streams item
blocks through an on-chip top-kappa accumulator, skips zero-candidate blocks
outright, and writes only O(Q * kappa) to HBM.  This kernel remains the
bit-exact dense oracle (mask + full score matrix + ``lax.top_k``) that the
streaming path is tested and benchmarked against.

Grid: (Q/BQ, N/BN); the full factor dim k rides along in VMEM (k <= a few
thousand in every paper setting; the serving LM-head path blocks the vocab
axis the same way).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["gam_score"]

NEG = -1e30


def _kernel(u_ref, v_ref, m_ref, o_ref):
    scores = jax.lax.dot_general(
        u_ref[...], v_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = jnp.where(m_ref[...] != 0, scores, NEG)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("bq", "bn", "interpret"))
def gam_score(u: jax.Array, v: jax.Array, mask: jax.Array, *,
              bq: int = 128, bn: int = 512, interpret: bool = False):
    """u: (Q, k), v: (N, k), mask: (Q, N) -> masked scores (Q, N) f32."""
    q, k = u.shape
    n = v.shape[0]
    up = _pad_to(u, bq, 0)
    vp = _pad_to(v, bn, 0)
    mp = _pad_to(_pad_to(mask.astype(jnp.int8), bq, 0), bn, 1)
    qp, np_ = up.shape[0], vp.shape[0]
    out = pl.pallas_call(
        _kernel,
        grid=(qp // bq, np_ // bn),
        in_specs=[
            pl.BlockSpec((bq, k), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, k), lambda i, j: (j, 0)),
            pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bq, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((qp, np_), jnp.float32),
        interpret=interpret,
    )(up, vp, mp)
    return out[:q, :n]
