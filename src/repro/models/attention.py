"""Attention substrate: GQA, RoPE, qk-norm, sliding-window / global layers.

Three execution paths, chosen by shape and window:

* **banded** (window layers, train/prefill): the sequence is chunked at the
  window size and each query chunk attends to exactly two key chunks (its own
  and the previous one) gathered into a banded tensor — one einsum, no scan,
  true O(S·w) FLOPs.  This is the pure-JAX analog of the Pallas
  sliding-window kernel in ``repro.kernels.swa`` (the models run this path;
  the kernel is on no model's path yet).
* **chunked-full** (global layers, train/prefill): scan over query chunks,
  full einsum against all keys per chunk — O(S²) FLOPs, O(S·chunk) memory.
* **decode**: one query token against a KV cache; window layers use a ring
  buffer of size w (O(w) per token), global layers read the full cache
  (O(S) per token).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import ModelConfig, rms_norm, rope

NEG_INF = -1e30


def _split_heads(x: jax.Array, n: int, dh: int) -> jax.Array:
    return x.reshape(*x.shape[:-1], n, dh)


def qkv_project(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                positions: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B, S, D) -> q (B,S,H,dh), k/v (B,S,KV,dh) with RoPE + qk-norm."""
    q = _split_heads(x @ p["wq"], cfg.n_heads, cfg.dh)
    k = _split_heads(x @ p["wk"], cfg.n_kv_heads, cfg.dh)
    v = _split_heads(x @ p["wv"], cfg.n_kv_heads, cfg.dh)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
    k = rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    return q, k, v


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (..., Sq, KV, G, dh), k: (..., Sk, KV, dh) -> (..., KV, G, Sq, Sk)."""
    return jnp.einsum("...qkgd,...skd->...kgqs", q, k,
                      preferred_element_type=jnp.float32)


def _gqa_context(probs: jax.Array, v: jax.Array) -> jax.Array:
    """probs: (..., KV, G, Sq, Sk), v: (..., Sk, KV, dh) -> (..., Sq, KV, G, dh)."""
    return jnp.einsum("...kgqs,...skd->...qkgd", probs, v)


def full_attention_chunked(q: jax.Array, k: jax.Array, v: jax.Array,
                           q_chunk: int) -> jax.Array:
    """Causal full attention, scanned over query chunks (O(S·c) memory)."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = dh ** -0.5
    c = min(q_chunk, S)
    if S % c != 0:
        pad = c - S % c
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nq = q.shape[1] // c
    qc = q.reshape(B, nq, c, KV, G, dh)
    kpos = jnp.arange(S)

    def body(_, xs):
        i, qi = xs                                     # qi: (B, c, KV, G, dh)
        s = _gqa_scores(qi, k) * scale                 # (B, KV, G, c, S)
        qpos = i * c + jnp.arange(c)
        mask = kpos[None, :] <= qpos[:, None]          # causal
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        probs = jax.nn.softmax(s, axis=-1)
        out = _gqa_context(probs.astype(v.dtype), v)   # (B, c, KV, G, dh)
        return None, out

    if nq == 1:
        _, out = body(None, (jnp.int32(0), qc[:, 0]))
        outs = out[:, None]
    else:
        _, outs = jax.lax.scan(body, None,
                               (jnp.arange(nq), jnp.moveaxis(qc, 1, 0)))
        outs = jnp.moveaxis(outs, 0, 1)
    out = outs.reshape(B, nq * c, H, dh)[:, :S]
    return out


def banded_window_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            window: int) -> jax.Array:
    """Sliding-window causal attention in one einsum (no scan).

    Chunk size = window; each query chunk attends to [prev chunk ‖ own
    chunk], masked to the causal window.  FLOPs: 2·S·2w·H·dh per matmul —
    truly sub-quadratic.
    """
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    scale = dh ** -0.5
    c = window
    pad = (-S) % c
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Sp = S + pad
    nq = Sp // c
    qc = q.reshape(B, nq, c, KV, G, dh)
    # banded keys/values: [chunk i-1 ‖ chunk i] for each chunk i
    kprev = jnp.pad(k, ((0, 0), (c, 0), (0, 0), (0, 0)))[:, :-c]
    kc = jnp.concatenate([kprev.reshape(B, nq, c, KV, dh),
                          k.reshape(B, nq, c, KV, dh)], axis=2)  # (B,nq,2c,KV,dh)
    vprev = jnp.pad(v, ((0, 0), (c, 0), (0, 0), (0, 0)))[:, :-c]
    vc = jnp.concatenate([vprev.reshape(B, nq, c, KV, dh),
                          v.reshape(B, nq, c, KV, dh)], axis=2)
    s = jnp.einsum("bnqkgd,bnskd->bnkgqs", qc, kc,
                   preferred_element_type=jnp.float32) * scale
    # relative mask: key global pos = (n-1)c + s_idx; query = n·c + q_idx
    qi = jnp.arange(c)[:, None]
    si = jnp.arange(2 * c)[None, :]
    delta = (c + qi) - si                 # q_pos - k_pos
    band = (delta >= 0) & (delta < window)
    # first chunk's "previous" keys are padding — mask them out
    nvalid = jnp.arange(nq)[:, None, None] > 0
    valid = band[None] & (nvalid | (si[None] >= c))
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bnkgqs,bnskd->bnqkgd", probs.astype(vc.dtype), vc)
    return out.reshape(B, Sp, H, dh)[:, :S]


def attention_train(cfg: ModelConfig, q, k, v, window: Optional[int]) -> jax.Array:
    B, S, H, dh = q.shape
    if window is not None and S > window:
        return banded_window_attention(q, k, v, window)
    return full_attention_chunked(q, k, v, cfg.q_chunk)


# ---------------------------------------------------------------------------
# Decode (single-token) attention with KV caches
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, spec_window: Optional[int], batch: int,
               max_seq: int, dtype) -> Dict[str, jax.Array]:
    """KV cache for one attention layer (unstacked).

    Window layers use a ring buffer of size ``window`` with per-slot global
    positions; global layers use the full sequence buffer.
    """
    size = min(spec_window, max_seq) if spec_window else max_seq
    return {
        "k": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.dh), dtype),
        "v": jnp.zeros((batch, size, cfg.n_kv_heads, cfg.dh), dtype),
        "pos": jnp.full((size,), -1, jnp.int32),   # global position per slot
    }


def decode_attention(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                     cache: Dict[str, jax.Array], position: jax.Array
                     ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """x: (B, 1, D); returns (context (B,1,H*dh), new cache)."""
    B = x.shape[0]
    H, KV, dh = cfg.n_heads, cfg.n_kv_heads, cfg.dh
    G = H // KV
    pos1 = jnp.full((B, 1), position, jnp.int32)
    q, k_new, v_new = qkv_project(cfg, p, x, pos1)
    size = cache["k"].shape[1]
    slot = position % size
    k = jax.lax.dynamic_update_slice(cache["k"], k_new, (0, slot, 0, 0))
    v = jax.lax.dynamic_update_slice(cache["v"], v_new, (0, slot, 0, 0))
    pos = jax.lax.dynamic_update_slice(cache["pos"],
                                       position[None].astype(jnp.int32), (slot,))
    qg = q.reshape(B, 1, KV, G, dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k,
                   preferred_element_type=jnp.float32) * (dh ** -0.5)
    valid = (pos >= 0) & (pos <= position)
    s = jnp.where(valid[None, None, None, None, :], s, NEG_INF)
    probs = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", probs.astype(v.dtype), v)
    ctx = ctx.reshape(B, 1, H * dh)
    return ctx @ p["wo"], {"k": k, "v": v, "pos": pos}


def prefill_attention(cfg: ModelConfig, p: Dict[str, Any], x: jax.Array,
                      window: Optional[int], positions: jax.Array,
                      cache: Optional[Dict[str, jax.Array]] = None
                      ) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Training/prefill attention; fills the cache if given."""
    q, k, v = qkv_project(cfg, p, x, positions)
    out = attention_train(cfg, q, k, v, window)
    B, S = x.shape[:2]
    new_cache = None
    if cache is not None:
        size = cache["k"].shape[1]
        if size >= S:
            new_cache = {
                "k": jax.lax.dynamic_update_slice(cache["k"], k, (0, 0, 0, 0)),
                "v": jax.lax.dynamic_update_slice(cache["v"], v, (0, 0, 0, 0)),
                "pos": jax.lax.dynamic_update_slice(
                    cache["pos"], jnp.arange(S, dtype=jnp.int32), (0,)),
            }
        else:  # ring buffer smaller than the prefill: keep the tail
            tail_k = k[:, -size:]
            tail_v = v[:, -size:]
            tail_p = jnp.arange(S - size, S, dtype=jnp.int32)
            # ring alignment: global position p lives in slot p % size
            roll = (S - size) % size
            new_cache = {
                "k": jnp.roll(tail_k, shift=roll, axis=1),
                "v": jnp.roll(tail_v, shift=roll, axis=1),
                "pos": jnp.roll(tail_p, shift=roll, axis=0),
            }
    out = out.reshape(B, S, cfg.n_heads * cfg.dh)
    return out @ p["wo"], new_cache
