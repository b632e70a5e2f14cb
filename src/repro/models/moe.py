"""Mixture-of-Experts FFN: the experts one device holds, and expert
parallelism over a mesh.

Without a mesh (:func:`held_moe_ffn`) the layer holds the experts
``cfg.moe.held`` names, one expert-parallel share of the layer, or all of
them.  It routes every token over all ``n_experts``.  A held expert to
which the pass routes no token is neither read nor computed; each other
held expert runs on every token of the pass (a capacity of T rows an
expert, so no token is ever dropped), and its output is weighted by the
token's routing weight, which is 0 where the token is not routed to it.
A decode step's one token lands on half a held expert a layer on average,
so the step reads few of the held experts' matrices.  What absent experts
would add is left to the devices that hold them; nothing here stands in
for them.  It also returns the counts of :data:`COUNTERS`.

On a mesh (:func:`moe_ffn`), strategy "EP-as-TP": activations are
replicated across the ``model`` mesh axis (as they already are for tensor parallelism); experts are
sharded across it.  Every device routes the full local token set, computes
*its* experts' contributions through a sort-based fixed-capacity dispatch
(no (T, E, C) one-hot — O(T·k) memory), and the contributions are combined
with the same all-reduce that tensor parallelism already pays.  For top-k≥2
this moves strictly fewer bytes than a token all-to-all (2·D vs k·D per
token) and composes with XLA's collective fusion.

The mesh path's routed computation is ragged; it uses fixed per-expert
capacity C = max(min_cap, ceil(T·k/E · capacity_factor)) with token dropping
(standard dropping MoE), realized with scatter(mode="drop") /
gather(mode="fill").
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig

#: what :func:`held_moe_ffn` counts, in the order of its counts vector:
#: (token, expert) pairs routed; of those, pairs on a held expert; held
#: pairs that got no row (0: the layer is dropless); held experts that got
#: at least one token; held experts whose weights the pass read
COUNTERS = ("moe.routed_pairs", "moe.held_pairs", "moe.dropped",
            "moe.held_experts_hit", "moe.held_experts_read")
#: an expert's matrices in a layer's parameters
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")
#: the router's matmul precision: float32 logits, where a TPU's default
#: for float32 operands is one bfloat16 pass, which can flip near-ties
ROUTER_PRECISION = "highest"


def add_counts(a: Optional[jax.Array], b: Optional[jax.Array]
               ) -> Optional[jax.Array]:
    """Two sets of counters summed, where there are any (None: none)."""
    return b if a is None else a if b is None else a + b


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    return max(min(T, 32), int(math.ceil(T * k / E * cf)))


def route(cfg: ModelConfig, router_w: jax.Array, x: jax.Array
          ) -> Tuple[jax.Array, jax.Array]:
    """x: (T, D) -> (weights (T,k), experts (T,k)); deterministic.

    Logits in float32 at ``ROUTER_PRECISION``; the softmax runs over the
    top-k logits alone, which is the full softmax renormalised over the
    chosen k."""
    m = cfg.moe
    logits = jnp.matmul(x.astype(jnp.float32), router_w,
                        precision=ROUTER_PRECISION)    # (T, E)
    top_w, top_e = jax.lax.top_k(logits, m.top_k)
    top_w = jax.nn.softmax(top_w, axis=-1)
    return top_w, top_e


def moe_ffn_local(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                  e0: int, e_local: int) -> jax.Array:
    """MoE FFN over the expert slice [e0, e0+e_local).

    x: (T, D); expert weights in ``p`` are the *local* slices
    (e_local, D, F)/(e_local, F, D).  Returns this slice's contribution
    (T, D) — caller psums across the expert-sharding axis.
    """
    m = cfg.moe
    T, D = x.shape
    k = m.top_k
    C = _capacity(T, k, m.n_experts, m.capacity_factor)

    top_w, top_e = route(cfg, p["router"], x)
    flat_e = top_e.reshape(-1)                       # (T·k,)
    flat_w = top_w.reshape(-1).astype(x.dtype)
    flat_tok = jnp.repeat(jnp.arange(T), k)

    le = flat_e - e0
    mine = (le >= 0) & (le < e_local)
    key = jnp.where(mine, le, e_local)               # sentinel = not mine
    order = jnp.argsort(key, stable=True)
    key_s = key[order]
    tok_s = flat_tok[order]
    w_s = flat_w[order]
    # position within each expert's segment (sorted, so first-occurrence math)
    first = jnp.searchsorted(key_s, key_s, side="left")
    seg_pos = jnp.arange(T * k) - first
    keep = (key_s < e_local) & (seg_pos < C)
    dest = jnp.where(keep, key_s * C + seg_pos, e_local * C)  # overflow slot

    buf = jnp.zeros((e_local * C, D), x.dtype)
    buf = buf.at[dest].set(x[tok_s], mode="drop")
    buf = buf.reshape(e_local, C, D)

    h = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"])
    act = jax.nn.silu(h) * u
    y = jnp.einsum("ecf,efd->ecd", act, p["w_down"]).reshape(e_local * C, D)

    rows = y.at[dest].get(mode="fill", fill_value=0)  # (T·k, D) gathered back
    contrib = rows * (w_s * keep.astype(x.dtype))[:, None]
    out = jnp.zeros((T, D), x.dtype).at[tok_s].add(contrib)
    return out


def held_moe_ffn(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array
                 ) -> Tuple[jax.Array, jax.Array]:
    """The held experts' part of the layer over tokens x (T, D).

    ``p``'s expert tensors (:data:`EXPERT_WEIGHTS`) hold the ``n`` experts
    of ``cfg.moe.held_range``, (n, D, F)/(n, F, D); or, where ``p["layer"]``
    is a layer's index, the stack of every layer's, (L, n, D, F)/
    (L, n, F, D), out of which each expert's matrices are sliced where they
    are used, so that no layer's share is copied whole.  Only the held
    experts to which some token is routed run, in ascending order, each
    adding its gate times its SwiGLU into a float32 sum.  Returns that part
    (T, D) and the int32 counts of :data:`COUNTERS`."""
    m = cfg.moe
    e0, n = m.held_range
    T = x.shape[0]
    layer = p.get("layer")
    w = [p[k] if layer is not None else p[k][None] for k in EXPERT_WEIGHTS]
    layer = 0 if layer is None else layer
    with jax.named_scope("moe.route"):
        top_w, top_e = route(cfg, p["router"], x)                # (T, k)
        hit = top_e[..., None] == e0 + jnp.arange(n)             # (T, k, n)
        gates = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)
        gates = gates.astype(x.dtype)
        rows = jnp.sum(hit, axis=(0, 1))                 # pairs per expert

    def expert(e, carry):
        acc, read = carry
        wg, wu, wd = (jax.lax.dynamic_slice(a, (layer, e, 0, 0),
                                            (1, 1) + a.shape[2:])[0, 0]
                      for a in w)
        y = (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
        return acc + gates[:, e, None].astype(jnp.float32) * y, read + 1

    with jax.named_scope("moe.experts"):
        carry = (jnp.zeros(x.shape, jnp.float32), jnp.int32(0))
        for e in range(n):
            carry = jax.lax.cond(rows[e] > 0, functools.partial(expert, e),
                                 lambda c: c, carry)
        out, read = carry
    counts = jnp.stack([jnp.int32(T * m.top_k), jnp.sum(rows),
                        jnp.sum(jnp.maximum(rows - T, 0)),
                        jnp.sum(rows > 0), read]).astype(jnp.int32)
    return out.astype(x.dtype), counts


def dense_ffn(p: Dict[str, Any], x: jax.Array) -> jax.Array:
    """SwiGLU FFN. x: (..., D)."""
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def moe_ffn(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
            axis_name: str, axis_size: int) -> jax.Array:
    """MoE FFN over (B, S, D) activations, inside shard_map: experts are
    sharded over ``axis_name`` (static size ``axis_size``), ``p``'s expert
    tensors are local slices and the result is psummed."""
    B, S, D = x.shape
    e_local = cfg.moe.n_experts // axis_size
    e0 = jax.lax.axis_index(axis_name) * e_local
    out = moe_ffn_local(cfg, p, x.reshape(B * S, D), e0, e_local)
    return jax.lax.psum(out, axis_name).reshape(B, S, D)
