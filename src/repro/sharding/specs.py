"""Path-regex -> PartitionSpec rules for parameters, optimizer states,
batches, and KV caches.

Conventions (megatron-style 2D: data x model, + pod for multi-pod):
  * attention head / FFN hidden / expert / vocab dims shard on ``model``;
  * batch shards on ("pod","data");
  * batch-1 long-context decode shards the cache sequence dim on ``data``
    (sequence parallelism) instead of the batch dim.
"""
from __future__ import annotations

import re

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import data_axes

__all__ = ["param_specs", "param_shardings", "batch_specs", "cache_specs",
           "named"]

# (path regex, spec builder taking ndim) — first match wins.
_RULES: list[tuple[str, object]] = [
    # embeddings / unembedding
    (r"\['embed'\]$", lambda nd: P("model", None)),
    (r"\['lm_head'\]$", lambda nd: P(None, "model")),
    (r"\['img_proj'\]$", lambda nd: P(None, "model")),
    (r"\['frontend_proj'\]$", lambda nd: P(None, None)),
    # attention projections (stacked: leading L axis)
    (r"\['w[qkv]'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['b[qkv]'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['wo'\]$", lambda nd: P(*(None,) * (nd - 2), "model", None)),
    # MLA
    (r"\['wq_a'\]$", lambda nd: P(*(None,) * nd)),
    (r"\['wq_b'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['wkv_a'\]$", lambda nd: P(*(None,) * nd)),
    (r"\['wk_b'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['wv_b'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    # MoE: experts across the model axis (expert parallelism)
    (r"\['router'\]$", lambda nd: P(*(None,) * nd)),
    (r"\['moe'\]\['(gate|up|down)'\]$",
     lambda nd: P(*(None,) * (nd - 3), "model", None, None)),
    (r"\['shared'\]\['(gate|up)'\]$",
     lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['shared'\]\['down'\]$",
     lambda nd: P(*(None,) * (nd - 2), "model", None)),
    # dense MLP
    (r"\['mlp'\]\['(gate|up)'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['mlp'\]\['down'\]$", lambda nd: P(*(None,) * (nd - 2), "model", None)),
    # SSM
    (r"\['in_proj'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['out_proj'\]$", lambda nd: P(*(None,) * (nd - 2), "model", None)),
    # RG-LRU
    (r"\['in_(x|gate)'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['w_[ai]'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['b_[ai]'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['lam'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
    (r"\['rec'\]\['out'\]$", lambda nd: P(*(None,) * (nd - 2), "model", None)),
    (r"\['conv_[wb]'\]$", lambda nd: P(*(None,) * (nd - 1), "model")),
]


def _spec_for(path: str, ndim: int, overrides=()):
    for pat, action in overrides:
        if re.search(pat, path):
            if action == "replicate":
                return P(*(None,) * ndim)
            raise ValueError(f"unknown override action {action!r}")
    for pat, fn in _RULES:
        if re.search(pat, path):
            return fn(ndim)
    return P(*(None,) * ndim)          # replicate (norms, scalars, biases)


def param_specs(params, overrides=()) -> object:
    """Pytree of PartitionSpecs matching ``params`` (works on SDS trees)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for kp, leaf in flat:
        path = jax.tree_util.keystr(kp)
        nd = len(leaf.shape)
        specs.append(_spec_for(path, nd, overrides))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _sanitize(spec: P, shape, mesh) -> P:
    """Drop axis assignments that don't divide the dim."""
    out = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= mesh.shape[a]
        out.append(ax if dim % size == 0 else None)
    return P(*out)


def named(mesh, tree_specs, tree):
    """PartitionSpec tree -> NamedSharding tree, sanitized against shapes."""
    return jax.tree.map(
        lambda s, x: NamedSharding(mesh, _sanitize(s, x.shape, mesh)),
        tree_specs, tree,
        is_leaf=lambda s: isinstance(s, P),
    )


def fsdp_specs(params, mesh, overrides=()) -> object:
    """Param specs + ZeRO/FSDP data-axis sharding: the first dim not already
    sharded whose size divides the data-parallel axis product gets "data"
    (and "pod" too when divisible) — params and optimizer states then scale
    with the full chip count, the production default for >=1B models."""
    dp = data_axes(mesh)
    dp_all = 1
    for a in dp:
        dp_all *= mesh.shape[a]
    dp_one = mesh.shape["data"]

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    base = jax.tree_util.tree_flatten(param_specs(params, overrides))[0]
    out = []
    for (kp, leaf), spec in zip(flat, base):
        path = jax.tree_util.keystr(kp)
        if any(re.search(pat, path) and act == "replicate"
               for pat, act in overrides):
            out.append(P(*(None,) * len(leaf.shape)))
            continue
        dims = list(tuple(spec) + (None,) * (len(leaf.shape) - len(spec)))
        # choose the largest eligible dim for the data shard
        cand = sorted(
            (i for i, (d, ax) in enumerate(zip(leaf.shape, dims))
             if ax is None and d >= dp_one),
            key=lambda i: -leaf.shape[i],
        )
        for i in cand:
            if leaf.shape[i] % dp_all == 0:
                dims[i] = dp
                break
            if leaf.shape[i] % dp_one == 0:
                dims[i] = "data"
                break
        out.append(P(*dims))
    return jax.tree_util.tree_unflatten(treedef, out)


def param_shardings(mesh, params, *, fsdp: bool = True, overrides=()):
    specs = (fsdp_specs(params, mesh, overrides) if fsdp
             else param_specs(params, overrides))
    return named(mesh, specs, params)


def batch_specs(cfg: ModelConfig, mesh, batch) -> object:
    """Input-batch sharding: batch dim over ("pod","data") when divisible."""
    dp = data_axes(mesh)

    def spec(x):
        s = P(dp, *(None,) * (len(x.shape) - 1))
        return NamedSharding(mesh, _sanitize(s, x.shape, mesh))

    return jax.tree.map(spec, batch)


def cache_specs(cfg: ModelConfig, mesh, cache, *, seq_shard: bool) -> object:
    """KV/state-cache sharding.

    Layout per leaf: (L, B, S, ...) for kv-like, (L, B, ...) for states.
    ``seq_shard=True`` (batch-1 long-context) shards S on "data" instead of B.
    """
    dp = data_axes(mesh)

    def spec(path, x):
        nd = len(x.shape)
        name = jax.tree_util.keystr(path)
        if nd == 0:
            return NamedSharding(mesh, P())
        if "len" in name:
            return NamedSharding(mesh, P())
        dims: list = [None] * nd
        seq_axis = None
        if any(k in name for k in ("'k'", "'v'", "cross_k", "cross_v")):
            seq_axis = 2
        elif any(k in name for k in ("c_kv", "k_rope")):
            seq_axis = 2
        if seq_shard:
            if seq_axis is not None:
                dims[seq_axis] = "data"
            # state caches (ssm/rec/conv): shard widest model dim on "model"
            elif "'ssm'" in name and nd >= 3:
                dims[2] = "model"      # heads
        else:
            if nd >= 2:
                dims[1] = dp           # batch over (pod, data)
        # model-dim sharding for kv heads happens only when divisible
        s = P(*dims)
        return NamedSharding(mesh, _sanitize(s, x.shape, mesh))

    flat, treedef = jax.tree_util.tree_flatten_with_path(cache)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(kp, leaf) for kp, leaf in flat])
