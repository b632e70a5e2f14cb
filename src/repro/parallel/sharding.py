"""Sharding rules: parameter / batch PartitionSpecs per mesh.

Axes:
  data   — data parallelism + FSDP (params' non-model dim sharded here)
  model  — tensor parallelism: heads / FFN / experts / vocab

The rules (DP+TP+EP+FSDP) are name-based over the parameter tree.  Stacked
layer-group params get a leading ``None`` axis.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.common import ModelConfig
from repro.models.transformer import ShardCtx

FSDP = "data"
TP = "model"


def _dp_axes(mesh: Mesh) -> Tuple[str, ...]:
    return (FSDP,) if FSDP in mesh.axis_names else ()


def shard_ctx_for_mesh(mesh: Mesh) -> ShardCtx:
    return ShardCtx(mesh=mesh, dp_axes=_dp_axes(mesh), tp_axis=TP)


def _rule_for(name: str, shape: Tuple[int, ...], cfg: ModelConfig,
              stacked: bool) -> P:
    """PartitionSpec for one (unstacked-rank) parameter by name."""
    r = len(shape) - (1 if stacked else 0)
    base: Tuple = ()
    if name == "embed":
        base = (TP, FSDP)
    elif name == "lm_head":
        base = (FSDP, TP)
    elif name in ("wq", "wk", "wv", "up", "w_in", "wz", "wi", "wf",
                  "wo_gate"):
        base = (FSDP, TP) if r == 2 else (None,)
    elif name in ("wo", "down"):
        base = (TP, FSDP)
    elif name in ("w_gate", "w_up"):
        base = (TP, FSDP, None) if r == 3 else (FSDP, TP)   # moe vs dense
    elif name == "w_down":
        base = (TP, None, FSDP) if r == 3 else (TP, FSDP)
    elif name == "router":
        base = (FSDP, None)
    elif name in ("wa", "wx", "w_out"):
        base = (TP, FSDP)
    elif name == "conv":
        base = (None, TP)
    elif name == "lam":
        base = (TP,)
    else:   # ln*, norms, biases, rz, bf — replicate
        base = tuple(None for _ in range(r))
    base = tuple(base[:r]) + tuple(None for _ in range(r - len(base)))
    if stacked:
        base = (None,) + base
    return P(*base)


def _divisible(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop sharding on axes the shape does not divide evenly (robustness:
    tiny smoke configs; odd head counts)."""
    fixed = []
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            fixed.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        n = int(np.prod([mesh.shape[a] for a in axes]))
        fixed.append(ax if dim % n == 0 else None)
    return P(*fixed)


def param_pspecs(cfg: ModelConfig, params: Any, mesh: Mesh) -> Any:
    """PartitionSpec pytree matching ``params``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        name = None
        for entry in reversed(path):
            if hasattr(entry, "key"):
                name = entry.key
                break
        in_groups = any(getattr(e, "key", None) == "groups" for e in path)
        spec = _rule_for(name or "", leaf.shape, cfg, stacked=in_groups)
        specs.append(_divisible(spec, leaf.shape, mesh))
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_pspecs(cfg: ModelConfig, mesh: Mesh) -> Dict[str, P]:
    dp = _dp_axes(mesh)
    return {"inputs": P(dp), "targets": P(dp)}


def named(mesh: Mesh, spec_tree: Any) -> Any:
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))
