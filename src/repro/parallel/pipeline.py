"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

For models whose layer count × width exceeds what DP×TP can hold, the layer
stack is split into S stages sharded over a ``stage`` axis; microbatches
flow through the classic (n_micro + S − 1)-tick schedule, with activations
handed between stages by ``jax.lax.ppermute`` (TPU-native neighbor
exchange — no NCCL-style send/recv emulation).

This is substrate for the 1000+-node runnability requirement (DESIGN.md
§8.5); the default configs use DP×TP(×EP), and PP composes with them by
adding the axis to the mesh.  ``pipeline_apply`` is validated against
sequential execution in tests/test_pipeline.py.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array, mesh: Mesh,
                   stage_axis: str = "stage") -> jax.Array:
    """Run ``x`` through S pipeline stages.

    stage_params: pytree with leading dim S (one slice per stage), sharded
    over ``stage_axis``; x: (n_micro, mb, ...) microbatched input,
    replicated across stages.  Returns (n_micro, mb, ...) outputs.
    """
    S = mesh.shape[stage_axis]
    n_micro = x.shape[0]
    assert n_micro >= S, "need at least one microbatch per stage"
    perm = [(i, (i + 1) % S) for i in range(S)]

    def spmd(params_local, x_all):
        params_local = jax.tree.map(lambda a: a[0], params_local)
        stage = jax.lax.axis_index(stage_axis)
        ticks = n_micro + S - 1
        buf = jnp.zeros_like(x_all[0])            # inter-stage register
        outs = jnp.zeros_like(x_all)

        def tick(t, carry):
            buf, outs = carry
            # stage 0 feeds microbatch t (when in range); others take buf
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inp = jnp.where(stage == 0, x_all[mb_idx], buf)
            out = stage_fn(params_local, inp)
            # last stage commits microbatch t-(S-1) (when in range)
            out_idx = jnp.clip(t - (S - 1), 0, n_micro - 1)
            commit = (stage == S - 1) & (t >= S - 1)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(commit, out, outs[out_idx]), out_idx, 0)
            buf = jax.lax.ppermute(out, stage_axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast them
        outs = jax.lax.psum(
            jnp.where(stage == S - 1, outs, jnp.zeros_like(outs)), stage_axis)
        return outs

    fn = jax.shard_map(
        spmd, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(stage_axis), stage_params,
                               is_leaf=lambda a: hasattr(a, "shape")),
                  P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x)
