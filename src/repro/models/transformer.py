"""Model assembly: pattern-grouped layer stacks, forward passes, losses.

The forward pass iterates the config's pattern program (see
``repro.models.common``): one ``lax.scan`` per group, heterogeneous layer
kinds inside the pattern.  Three entry points:

  forward_train(cfg, params, batch)            -> loss-ready logits
  prefill(cfg, params, inputs)                 -> (last logits, caches)
  decode_step(cfg, params, caches, tok, pos)   -> (logits, new caches)

``ShardCtx`` carries mesh information; when present, activations get
sharding constraints and MoE layers run expert-parallel under shard_map.
Without it a MoE layer computes the experts it holds
(``repro.models.moe.held_moe_ffn``), and ``prefill`` and ``decode_step``
with ``counts=True`` also return its counters, summed over the layers.
Their layer scans leave the held experts' weights stacked and hand each
layer its index, so that the expert layer slices out only the experts it
runs.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models import recurrent as rec
from repro.models.attention import (decode_attention, init_cache,
                                    prefill_attention)
from repro.models.common import LayerSpec, ModelConfig, rms_norm
from repro.models.moe import (EXPERT_WEIGHTS, add_counts, dense_ffn,
                              held_moe_ffn, moe_ffn)


@dataclass(frozen=True)
class ShardCtx:
    mesh: Mesh
    dp_axes: Tuple[str, ...] = ("data",)     # batch axes
    tp_axis: str = "model"

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.tp_axis]


def _constrain(x: jax.Array, ctx: Optional[ShardCtx], spec) -> jax.Array:
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Layer application (training / prefill form)
# ---------------------------------------------------------------------------
def _ffn_part(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
              ctx: Optional[ShardCtx]
              ) -> Tuple[jax.Array, Optional[jax.Array]]:
    """The FFN sublayer with its residual, and the MoE counters of a layer
    of held experts (None for any other)."""
    h = rms_norm(x, p["ln2"], cfg.norm_eps)
    counts = None
    if cfg.moe is not None:
        if ctx is None:
            B, S, D = h.shape
            y, counts = held_moe_ffn(cfg, _moe_params(p), h.reshape(B * S, D))
            y = y.reshape(B, S, D)
        elif cfg.moe.held is not None:
            raise ValueError(f"{cfg.name}: a share of held experts runs "
                             f"without a mesh")
        else:
            dp = ctx.dp_axes
            pspec_x = P(dp, None, None)
            especs = {
                "router": P(None, None),
                "w_gate": P(ctx.tp_axis, None, None),
                "w_up": P(ctx.tp_axis, None, None),
                "w_down": P(ctx.tp_axis, None, None),
            }
            fn = jax.shard_map(
                functools.partial(moe_ffn, cfg, axis_name=ctx.tp_axis,
                                  axis_size=ctx.tp_size),
                mesh=ctx.mesh,
                in_specs=(especs, pspec_x),
                out_specs=pspec_x,
                check_vma=False,
            )
            y = fn(_moe_params(p), h)
    else:
        y = dense_ffn(p, h)
    return x + y, counts


def _moe_params(p: Dict[str, Any]) -> Dict[str, Any]:
    return {k: p[k] for k in ("router", *EXPERT_WEIGHTS, "layer") if k in p}


def apply_layer_train(cfg: ModelConfig, spec: LayerSpec, p: Dict[str, Any],
                      x: jax.Array, positions: jax.Array,
                      ctx: Optional[ShardCtx]) -> jax.Array:
    if spec.kind == "attn":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        attn_out, _ = prefill_attention(cfg, p, h, spec.window, positions)
        x = x + attn_out
        x, _ = _ffn_part(cfg, p, x, ctx)
    elif spec.kind == "mlstm":
        x = rec.mlstm_block(cfg, p, x)
    elif spec.kind == "slstm":
        x = rec.slstm_block(cfg, p, x)
    elif spec.kind == "rglru":
        x = rec.rglru_block(cfg, p, x)
        if spec.has_ffn:
            x, _ = _ffn_part(cfg, p, x, ctx)
    else:
        raise ValueError(spec.kind)
    return x


def _remat(cfg: ModelConfig, fn):
    if cfg.remat == "none":
        return fn
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)


def apply_groups_train(cfg: ModelConfig, params: Dict[str, Any], x: jax.Array,
                       positions: jax.Array, ctx: Optional[ShardCtx]) -> jax.Array:
    for gi, (pattern, reps) in enumerate(cfg.blocks):
        stacked = params["groups"][gi]

        def body(xc, layer_params, pattern=pattern):
            for spec, p in zip(pattern, layer_params):
                xc = apply_layer_train(cfg, spec, p, xc, positions, ctx)
            return xc, None

        body = _remat(cfg, body)
        if reps == 1:
            x, _ = body(x, jax.tree.map(lambda a: a[0], stacked))
        else:
            x, _ = jax.lax.scan(body, x, stacked, length=reps)
    return x


# ---------------------------------------------------------------------------
# Embedding / logits
# ---------------------------------------------------------------------------
def embed(cfg: ModelConfig, params, tokens_or_embeds: jax.Array,
          ctx: Optional[ShardCtx]) -> jax.Array:
    if tokens_or_embeds.dtype in (jnp.int32, jnp.int64):
        x = params["embed"][tokens_or_embeds] * (cfg.d_model ** 0.5)
        x = x.astype(cfg.jdtype())
    else:
        x = tokens_or_embeds.astype(cfg.jdtype())   # frontend stub: embeddings
    if ctx is not None:
        x = _constrain(x, ctx, P(ctx.dp_axes, None, None))
    return x


def logits_fn(cfg: ModelConfig, params, x: jax.Array,
              ctx: Optional[ShardCtx]) -> jax.Array:
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = x @ head.astype(x.dtype)
    if ctx is not None:
        logits = _constrain(logits, ctx, P(ctx.dp_axes, None, ctx.tp_axis))
    return logits


def forward_train(cfg: ModelConfig, params, inputs: jax.Array,
                  ctx: Optional[ShardCtx] = None) -> jax.Array:
    """inputs: (B, S) int tokens or (B, S, D) frontend embeddings."""
    B, S = inputs.shape[:2]
    x = embed(cfg, params, inputs, ctx)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    x = apply_groups_train(cfg, params, x, positions, ctx)
    return logits_fn(cfg, params, x, ctx)


def lm_loss(cfg: ModelConfig, params, inputs: jax.Array, targets: jax.Array,
            ctx: Optional[ShardCtx] = None) -> jax.Array:
    logits = forward_train(cfg, params, inputs, ctx)
    # fused stable CE: exp/log temps fuse into the vocab reductions — no
    # materialized fp32 (B,S,V) copy (matters at 262k vocab)
    lmax = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
    shifted = (logits - lmax).astype(jnp.float32)
    lse = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1))
    tgt = jnp.take_along_axis(shifted, targets[..., None].astype(jnp.int32),
                              axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


# ---------------------------------------------------------------------------
# Caches / decode
# ---------------------------------------------------------------------------
def init_layer_state(cfg: ModelConfig, spec: LayerSpec, batch: int,
                     max_seq: int):
    if spec.kind == "attn":
        return init_cache(cfg, spec.window, batch, max_seq, cfg.jdtype())
    if spec.kind == "mlstm":
        return rec.mlstm_init_state(cfg, batch)
    if spec.kind == "slstm":
        return rec.slstm_init_state(cfg, batch)
    if spec.kind == "rglru":
        return rec.rglru_init_state(cfg, batch)
    raise ValueError(spec.kind)


def init_caches(cfg: ModelConfig, batch: int, max_seq: int):
    """Nested (per group, per pattern position) stacked caches."""
    groups = []
    for pattern, reps in cfg.blocks:
        per_pos = []
        for spec in pattern:
            one = init_layer_state(cfg, spec, batch, max_seq)
            per_pos.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (reps,) + a.shape), one))
        groups.append(tuple(per_pos))
    return tuple(groups)


def apply_layer_decode(cfg: ModelConfig, spec: LayerSpec, p, x, cache,
                       position, ctx):
    """One layer's decode step: (x, new state, MoE counters or None)."""
    if spec.kind == "attn":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        attn_out, new_cache = decode_attention(cfg, p, h, cache, position)
        x = x + attn_out
        x, counts = _ffn_part(cfg, p, x, ctx)
        return x, new_cache, counts
    if spec.kind == "mlstm":
        return (*rec.mlstm_step(cfg, p, x, cache), None)
    if spec.kind == "slstm":
        return (*rec.slstm_step(cfg, p, x, cache), None)
    if spec.kind == "rglru":
        x, st = rec.rglru_step(cfg, p, x, cache)
        counts = None
        if spec.has_ffn:
            x, counts = _ffn_part(cfg, p, x, ctx)
        return x, st, counts
    raise ValueError(spec.kind)


def _over_layers(cfg: ModelConfig, ctx: Optional[ShardCtx], body, x,
                 stacked, states, reps: int):
    """``body(x, layer_params, layer_states) -> (x, (new states, counts))``
    over a group's ``reps`` layers: returns x, the stacked new states and
    the counts summed over the layers.  ``stacked`` and ``states`` are the
    group's per-layer stacks.  A layer of held experts (no mesh) gets those
    experts' weights whole, with its index under ``"layer"``: a layer's
    slice of them would be copied where the expert layer runs an expert
    only if it is routed to."""
    experts = None
    if cfg.moe is not None and ctx is None:
        experts = tuple({k: p[k] for k in EXPERT_WEIGHTS if k in p}
                        for p in stacked)
        stacked = tuple({k: v for k, v in p.items() if k not in EXPERT_WEIGHTS}
                        for p in stacked)

    def step(xc, xs):
        layer_params, layer_states, i = xs
        if experts is not None:
            layer_params = tuple(dict(p, layer=i, **e) if e else p
                                 for p, e in zip(layer_params, experts))
        return body(xc, layer_params, layer_states)

    if reps == 1:
        first = jax.tree.map(lambda a: a[0], (stacked, states))
        x, (ncs, k) = step(x, first + (0,))
        return x, jax.tree.map(lambda a: a[None], ncs), k
    index = None if experts is None else jnp.arange(reps)
    x, (ncs, k) = jax.lax.scan(step, x, (stacked, states, index), length=reps)
    return x, ncs, None if k is None else jnp.sum(k, axis=0)


def decode_step(cfg: ModelConfig, params, caches, tokens: jax.Array,
                position: jax.Array, ctx: Optional[ShardCtx] = None,
                counts: bool = False):
    """tokens: (B,) int32; position: scalar int32. Returns (logits, caches),
    and with ``counts`` the MoE counters (int32[len(moe.COUNTERS)] summed
    over the layers, None for a model without held experts) after them."""
    x = embed(cfg, params, tokens[:, None], ctx)
    new_groups = []
    total = None
    for gi, (pattern, reps) in enumerate(cfg.blocks):
        def body(xc, layer_params, layer_caches, pattern=pattern):
            new_lc, k = [], None
            for spec, p, c in zip(pattern, layer_params, layer_caches):
                xc, nc, kl = apply_layer_decode(cfg, spec, p, xc, c, position,
                                                ctx)
                new_lc.append(nc)
                k = add_counts(k, kl)
            return xc, (tuple(new_lc), k)

        x, ncs, k = _over_layers(cfg, ctx, body, x, params["groups"][gi],
                                 caches[gi], reps)
        new_groups.append(ncs)
        total = add_counts(total, k)
    logits = logits_fn(cfg, params, x, ctx)
    if counts:
        return logits[:, 0], tuple(new_groups), total
    return logits[:, 0], tuple(new_groups)


def prefill(cfg: ModelConfig, params, inputs: jax.Array,
            ctx: Optional[ShardCtx] = None, max_seq: Optional[int] = None,
            counts: bool = False):
    """Run the full prompt, building caches.  Returns (last logits, caches),
    and with ``counts`` the MoE counters as ``decode_step`` does.

    inputs: (B, S) tokens or (B, S, D) embeddings.
    """
    B, S = inputs.shape[:2]
    max_seq = max_seq or S
    x = embed(cfg, params, inputs, ctx)
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    new_groups = []
    total = None
    for gi, (pattern, reps) in enumerate(cfg.blocks):
        def body(xc, layer_params, _, pattern=pattern):
            new_lc, k = [], None
            for spec, p in zip(pattern, layer_params):
                xc, st, kl = apply_layer_prefill(cfg, spec, p, xc, positions,
                                                 max_seq, ctx)
                new_lc.append(st)
                k = add_counts(k, kl)
            return xc, (tuple(new_lc), k)

        x, ncs, k = _over_layers(cfg, ctx, body, x, params["groups"][gi],
                                 None, reps)
        new_groups.append(ncs)
        total = add_counts(total, k)
    logits = logits_fn(cfg, params, x[:, -1:], ctx)
    if counts:
        return logits[:, 0], tuple(new_groups), total
    return logits[:, 0], tuple(new_groups)


def apply_layer_prefill(cfg: ModelConfig, spec: LayerSpec, p, x, positions,
                        max_seq, ctx):
    """One layer over the prompt: (x, state, MoE counters or None)."""
    B, S = x.shape[:2]
    if spec.kind == "attn":
        h = rms_norm(x, p["ln1"], cfg.norm_eps)
        cache = init_cache(cfg, spec.window, B, max_seq, cfg.jdtype())
        attn_out, new_cache = prefill_attention(cfg, p, h, spec.window,
                                                positions, cache)
        x = x + attn_out
        x, counts = _ffn_part(cfg, p, x, ctx)
        return x, new_cache, counts
    if spec.kind == "mlstm":
        return (*rec.mlstm_block(cfg, p, x, return_state=True), None)
    if spec.kind == "slstm":
        return (*rec.slstm_block(cfg, p, x, return_state=True), None)
    if spec.kind == "rglru":
        x, st = rec.rglru_block(cfg, p, x, return_state=True)
        counts = None
        if spec.has_ffn:
            x, counts = _ffn_part(cfg, p, x, ctx)
        return x, st, counts
    raise ValueError(spec.kind)
