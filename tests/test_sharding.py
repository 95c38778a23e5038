"""Sharding rules + miniature-mesh integration: a scaled-down production
mesh (4 devices in-process) trains and serves sharded without changing any
model code — the same code path the 512-chip dry-run proves at scale."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs.registry import get_reduced_config
from repro.launch.steps import (
    abstract_params, make_serve_step,
    make_train_step, shape_adapted_config,
)
from repro.models.model import Model
from repro.sharding.specs import (
    batch_specs, cache_specs, fsdp_specs, param_specs, param_shardings,
)
from repro.training.optimizer import adamw_init

pytestmark = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs 8 host devices")


def small_mesh():
    return jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def test_param_specs_shard_the_right_dims():
    cfg = get_reduced_config("olmoe-1b-7b")
    model = Model(cfg)
    params = abstract_params(model)
    specs = param_specs(params)
    flat = {jax.tree_util.keystr(kp): s for kp, s in
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    assert flat["['embed']"] == P("model", None)
    moe_gate = [v for k, v in flat.items() if "moe" in k and "'gate'" in k][0]
    assert moe_gate[1] == "model"      # experts axis
    wq = [v for k, v in flat.items() if "'wq'" in k][0]
    assert wq[-1] == "model"


def test_fsdp_adds_data_axis():
    cfg = get_reduced_config("tinyllama-1.1b")
    model = Model(cfg)
    params = abstract_params(model)
    mesh = small_mesh()
    specs = fsdp_specs(params, mesh)
    flat = {jax.tree_util.keystr(kp): s for kp, s in
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    wq = [v for k, v in flat.items() if "'wq'" in k][0]
    assert "model" in tuple(wq) or ("model",) in tuple(wq)
    assert any(ax == ("data",) or ax == "data" or
               (isinstance(ax, tuple) and "data" in ax)
               for ax in tuple(wq) if ax), wq


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "olmoe-1b-7b",
                                  "mamba2-780m", "recurrentgemma-9b"])
def test_sharded_train_step_runs(arch):
    """One real sharded train step on the 2x4 mini-mesh."""
    cfg = get_reduced_config(arch).with_(vocab=512)
    model = Model(cfg)
    mesh = small_mesh()
    params = model.init(jax.random.PRNGKey(0))
    p_shard = param_shardings(mesh, params)
    params = jax.device_put(params, p_shard)
    opt = jax.device_put(adamw_init(params),
                         type(adamw_init(params))(
                             step=jax.sharding.NamedSharding(mesh, P()),
                             mu=param_shardings(mesh, params),
                             nu=param_shardings(mesh, params)))
    tokens = np.random.default_rng(0).integers(0, cfg.vocab, (4, 33))
    batch = {"tokens": jnp.asarray(tokens, jnp.int32)}
    b_shard = batch_specs(cfg, mesh, batch)
    batch = jax.device_put(batch, b_shard)
    with mesh:
        step = jax.jit(make_train_step(model), donate_argnums=(0, 1))
        params2, opt2, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-780m"])
def test_sharded_serve_step_runs(arch):
    cfg = get_reduced_config(arch).with_(vocab=512)
    model = Model(cfg)
    mesh = small_mesh()
    params = jax.device_put(model.init(jax.random.PRNGKey(0)),
                            param_shardings(mesh, model.init(
                                jax.random.PRNGKey(0))))
    cache = model.init_cache(batch=4, capacity=64)
    c_shard = cache_specs(cfg, mesh, cache, seq_shard=False)
    cache = jax.device_put(cache, c_shard)
    tokens = jnp.zeros((4, 1), jnp.int32)
    with mesh:
        step = jax.jit(make_serve_step(model), donate_argnums=(1,))
        nxt, cache2 = step(params, cache, tokens)
    assert nxt.shape == (4, 1)
    assert int(cache2["len"]) == 1


def test_long_context_seq_sharding_lowers():
    """batch-1 decode shards the cache sequence dim on data."""
    cfg = shape_adapted_config(get_reduced_config("tinyllama-1.1b"),
                               type("S", (), {"name": "long_500k"})())
    assert cfg.attn_kind == "sliding"
    model = Model(cfg)
    mesh = small_mesh()
    cache = jax.eval_shape(lambda: model.init_cache(batch=1, capacity=1024))
    c_shard = cache_specs(cfg, mesh, cache, seq_shard=True)
    flat = {jax.tree_util.keystr(kp): s.spec for kp, s in
            jax.tree_util.tree_flatten_with_path(c_shard)[0]}
    k_spec = [v for k, v in flat.items() if k.endswith("['k']")][0]
    assert k_spec[2] == "data"
