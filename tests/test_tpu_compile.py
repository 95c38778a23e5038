"""Compile rehearsals for the chip: the retrieval path's kernels at the
widths ``chip_smoke.py`` serves, compiled for a described (not attached)
TPU v5e.  Nothing runs; the chip's compiler refuses here what it would
refuse on the chip — block shapes off the (8, 128) tiling, fast memory
past its size — at no chip time.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src import core as jax_core
from jax.sharding import SingleDeviceSharding

from repro.core.mapping import GamConfig
from repro.kernels.gam_retrieve import SKIP_MAP_TILES, _gam_retrieve
from repro.kernels.gam_score import gam_score

# chip_smoke.py's catalog: 2^20 items at d=64, parse-tree patterns
K = 64
N = 1 << 20
BN = 256
WORDS = -(-GamConfig(k=K, scheme="parse_tree", threshold=0.2).p // 32)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # else the compiler logs to /tmp
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _trace(sharding, *, n_pad, q, kappa, bn, quantized, min_overlap,
           k=K, words=WORDS):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    nb = n_pad // bn
    args = (s((q, k), jnp.float32),
            s((n_pad, k), jnp.int8 if quantized else jnp.float32),
            s((1, nb), jnp.float32) if quantized else None,
            s((q, k), jnp.int32), s((q, k), jnp.bool_), s((n_pad,), jnp.bool_),
            s((words, n_pad), jnp.uint32), s((nb, words), jnp.uint32),
            s((nb,), jnp.bool_), s((1, n_pad), jnp.int8))
    return _gam_retrieve.trace(
        *args, kappa=kappa, min_overlap=min_overlap, bq=32, bn=bn,
        words=words, n_pad=n_pad, interpret=False, loop_merge=True)


def _retrieve(sharding, **kw):
    return _trace(sharding, **kw).lower().compile()


def _primitives(jaxpr) -> set[str]:
    """Names of the primitives in ``jaxpr`` and in the jaxprs it holds."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax_core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


def _merge_branches(jaxpr) -> list[list[list[set[str]]]]:
    """For each Pallas kernel in ``jaxpr`` (nested ones too), each
    ``cond`` of its body (a ``pl.when`` is one too) as the primitives of
    each of its branches."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append([[_primitives(b.jaxpr) for b in c.params["branches"]]
                        for c in _eqns(eqn.params["jaxpr"])
                        if c.primitive.name == "cond"])
        else:
            for sub in jax_core.jaxprs_in_params(eqn.params):
                out += _merge_branches(sub)
    return out


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, nested ones too."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax_core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def _kernel_calls(compiled) -> list[str]:
    """Names of the Mosaic kernels' custom calls in the compiled program."""
    return re.findall(r"^\s*(%\S+) = .*custom_call_target=\"tpu_custom_call\"",
                      compiled.as_text(), re.M)


@pytest.mark.parametrize("n_pad,quantized,kappa,q,min_overlap", [
    (N, False, 10, 32, 2),        # phase A microbatch, pruned
    (N, False, 10, 64, 0),        # phase A check batch, exact path
    (N, True, 40, 32, 2),         # phase B: int8 slab, kappa x rerank pool
    (4 * N, False, 10, 64, 2),    # four-chip phase's one-device comparison
    # the largest launch SKIP_MAP_TILES allows over 2^22 rows, both paths
    (4 * N, False, 10, 256, 2),
    (4 * N, True, 40, 256, 2),
])
def test_gam_retrieve_compiles_for_v5e(one_chip, n_pad, quantized, kappa, q,
                                       min_overlap):
    assert (q // 32) * (n_pad // BN) <= SKIP_MAP_TILES
    compiled = _retrieve(one_chip, n_pad=n_pad, q=q, kappa=kappa, bn=BN,
                         quantized=quantized, min_overlap=min_overlap)
    assert "tpu_custom_call" in compiled.as_text()


def test_delta_segment_retrieve_compiles_for_v5e(one_chip):
    # service/delta.py pads the segment to a power of two, bn = min(256, cap)
    compiled = _retrieve(one_chip, n_pad=64, q=32, kappa=10, bn=64,
                         quantized=False, min_overlap=2)
    assert "tpu_custom_call" in compiled.as_text()


def test_gam_score_compiles_for_v5e(one_chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = gam_score.lower(s((64, K), jnp.float32),
                               s((N, K), jnp.float32),
                               s((64, N), jnp.bool_), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,quantized,kappa,n_pad", [
    (100, True, 40, 1_183_744),   # glove100-int8: int8 slab, pool of 40
    (64, False, 10, 292_864),     # lastfm64: f32 slab
    (96, True, 40, 2_497_536),    # deep96-int8: one chip's quarter
])
def test_benchmark_cell_shapes_compile_both_loops_for_v5e(one_chip, k,
                                                          quantized, kappa,
                                                          n_pad):
    """The benchmark cells' launch, 32 queries over the whole catalog (over
    one chip's quarter of it in the four-chip cell), with the compacted and
    the full overlap loop in the one program: a kernel each, both named as
    profile readers find them
    (``%_gam_retrieve…``)."""
    words = -(-GamConfig(k=k, scheme="parse_tree", threshold=0.2).p // 32)
    assert words == {100: 629, 64: 258, 96: 579}[k]
    traced = _trace(one_chip, n_pad=n_pad, q=32, kappa=kappa, bn=BN,
                    quantized=quantized, min_overlap=2, k=k, words=words)
    calls = _kernel_calls(traced.lower().compile())
    assert len(calls) == 2, calls
    assert all(c.startswith("%_gam_retrieve") for c in calls), calls
    # each kernel holds both of the merge's branches behind one cond: the
    # loop of as many steps as the tile's beating entries (a while loop
    # that rolls the accumulator) and kappa unrolled steps over the
    # accumulator and the tile together
    kernels = _merge_branches(traced.jaxpr.jaxpr)
    assert len(kernels) == 2, kernels
    loop = {"while", "roll"}
    for conds in kernels:
        merges = [c for c in conds if len(c) == 2
                  and not any("cond" in b for b in c)
                  and any(loop <= b for b in c)]
        assert len(merges) == 1, conds
        looped, unrolled = sorted(merges[0], key=lambda b: "while" in b,
                                  reverse=True)
        assert loop <= looped and not unrolled & loop, merges
        assert "concatenate" in unrolled, unrolled
