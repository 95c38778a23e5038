"""build_lowered wiring (train/prefill/decode) exercised at smoke scale on
the in-process 8-device mesh — the same code path the 512-device dry-run
scripts prove at production scale."""
import jax
import pytest

from repro.configs.base import ShapeConfig
from repro.configs.registry import get_reduced_config
from repro.launch.dryrun import (build_lowered, collective_bytes,
                                 cost_analysis_dict)

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices")


def mesh8():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


TINY = {
    "train": ShapeConfig("train_tiny", seq_len=64, global_batch=4,
                         kind="train"),
    "prefill": ShapeConfig("prefill_tiny", seq_len=64, global_batch=4,
                           kind="prefill"),
    "decode": ShapeConfig("decode_tiny", seq_len=64, global_batch=4,
                          kind="decode"),
}


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "olmoe-1b-7b",
                                  "mamba2-780m", "whisper-tiny",
                                  "internvl2-26b", "recurrentgemma-9b",
                                  "deepseek-v2-236b"])
@pytest.mark.parametrize("kind", ["train", "prefill", "decode"])
def test_build_lowered_compiles(arch, kind):
    cfg = get_reduced_config(arch).with_(vocab=512, q_chunk=32)
    shape = TINY[kind]
    mesh = mesh8()
    compiled = build_lowered(cfg, shape, mesh).compile()
    cost = cost_analysis_dict(compiled)
    assert cost.get("flops", 0) > 0
    # the per-partition module must be a real SPMD program
    txt = compiled.as_text()
    assert isinstance(collective_bytes(txt), dict)


def test_decode_batch1_seq_shard_lowers():
    """long-context decode (batch 1) with sequence-sharded cache."""
    cfg = get_reduced_config("tinyllama-1.1b").with_(
        vocab=512, attn_kind="sliding", window=32)
    shape = ShapeConfig("long_tiny", seq_len=128, global_batch=1,
                        kind="decode")
    compiled = build_lowered(cfg, shape, mesh8()).compile()
    assert cost_analysis_dict(compiled).get("flops", 0) > 0
