"""The plain reference: a dense GQA decoder in float32, and what the check
reads from it.

It imports nothing of the program.  Its weights are drawn again from
``PRNGKey(0)`` by the recipe the served weights were drawn with (the key
tree below: embedding, output head, then one key per layer and sixteen per
layer's matrices; each matrix ``normal(key) * fan_in ** -0.5`` rounded to
the served dtype; norm gains 1), one layer at a time, so the reference
never holds the whole model.  Every matrix product runs at
``precision="highest"``.

The forward follows the published layer: RMSNorm, q/k/v projections,
qk-RMSNorm where the model has it, rotary embedding on the leading
``rope_fraction`` of each head (rotate-half pairs), causal grouped-query
softmax attention, output projection, residual; RMSNorm, SwiGLU, residual;
final RMSNorm and the output head.

``quant`` computes the same forward with every weight matrix and every
matrix input rounded to int8 or fp8 (e4m3), each with a scale per row or
column: the control, one precision step below the served bfloat16.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from harness.models import Sizes

NEG = -1e30
#: the key the served weights are drawn from (the program's, not the seed's)
WEIGHTS_KEY = 0
#: tokens of activations computed at once
BLOCK_TOKENS = 8192
#: vocabulary columns of the output head per product
HEAD_PIECE = 8192
#: positions whose logits are taken at once
ROW_CHUNK = 1024


def _draw(key, shape, scale, dtype):
    # the served weights were drawn op by op, outside any jit: do the same,
    # so each rounding happens where it happened there
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


class Weights:
    """The served weights, drawn again from their key one piece at a time."""

    def __init__(self, s: Sizes):
        self.s = s
        self.dtype = jnp.dtype(s.dtype)
        k_emb, k_out, k_layers = jax.random.split(
            jax.random.PRNGKey(WEIGHTS_KEY), 3)
        _, k_group = jax.random.split(k_layers)
        self.k_emb, self.k_out = k_emb, k_out
        self.layer_keys = jax.random.split(jax.random.fold_in(k_group, 0),
                                           s.layers)

    def embed(self) -> jax.Array:
        s = self.s
        return _draw(self.k_emb, (s.vocab, s.d_model), s.d_model ** -0.5,
                     self.dtype)

    def head(self) -> jax.Array:
        s = self.s
        if s.tie_embeddings:
            return self.embed().T
        return _draw(self.k_out, (s.d_model, s.vocab), s.d_model ** -0.5,
                     self.dtype)

    def layer(self, i: int) -> Dict[str, jax.Array]:
        s = self.s
        ks = jax.random.split(self.layer_keys[i], 16)
        D, q, kv, F = (s.d_model, s.heads * s.head_dim,
                       s.kv_heads * s.head_dim, s.d_ff)
        return {
            "wq": _draw(ks[0], (D, q), D ** -0.5, self.dtype),
            "wk": _draw(ks[1], (D, kv), D ** -0.5, self.dtype),
            "wv": _draw(ks[2], (D, kv), D ** -0.5, self.dtype),
            "wo": _draw(ks[3], (q, D), q ** -0.5, self.dtype),
            "w_gate": _draw(ks[9], (D, F), D ** -0.5, self.dtype),
            "w_up": _draw(ks[10], (D, F), D ** -0.5, self.dtype),
            "w_down": _draw(ks[11], (F, D), F ** -0.5, self.dtype),
        }


# ---------------------------------------------------------------------------
# forward pieces (float32, or rounded to the control's precision)
# ---------------------------------------------------------------------------
def _round(x: jax.Array, quant: Optional[str], axis: int) -> jax.Array:
    """``x`` rounded to ``quant`` with one scale per slice along ``axis``."""
    if quant is None:
        return x
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    if quant == "int8":
        scale = jnp.where(amax > 0, amax / 127.0, 1.0)
        return jnp.clip(jnp.round(x / scale), -127, 127) * scale
    if quant == "fp8":
        scale = jnp.where(amax > 0, amax / 448.0, 1.0)
        return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) \
            * scale
    raise ValueError(quant)


def _mm(a: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    a = _round(a, quant, -1)
    w = _round(w.astype(jnp.float32), quant, 0)
    return jnp.matmul(a, w, precision="highest")


def _rms(x: jax.Array, eps: float) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x: jax.Array, theta: float, fraction: float) -> jax.Array:
    """x: (B, S, heads, dh); rotate-half on the leading ``fraction``."""
    S, dh = x.shape[1], x.shape[-1]
    rot = int(dh * fraction)
    rot -= rot % 2
    if rot == 0:
        return x
    half = rot // 2
    freqs = theta ** (-np.arange(half, dtype=np.float64) / half)
    ang = np.arange(S, dtype=np.float64)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


@partial(jax.jit, static_argnames=("s", "quant"))
def _layer(x: jax.Array, w: Dict[str, jax.Array], s: Sizes,
           quant: Optional[str]) -> jax.Array:
    B, S, D = x.shape
    H, KV, dh = s.heads, s.kv_heads, s.head_dim
    h = _rms(x, s.norm_eps)
    q = _mm(h, w["wq"], quant).reshape(B, S, H, dh)
    k = _mm(h, w["wk"], quant).reshape(B, S, KV, dh)
    v = _mm(h, w["wv"], quant).reshape(B, S, KV, dh)
    if s.qk_norm:
        q, k = _rms(q, s.norm_eps), _rms(k, s.norm_eps)
    q = _rope(q, s.rope_theta, s.rope_fraction)
    k = _rope(k, s.rope_theta, s.rope_fraction)
    q = q.reshape(B, S, KV, H // KV, dh)
    att = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                     precision="highest") * dh ** -0.5
    causal = np.tril(np.ones((S, S), bool))
    att = jax.nn.softmax(jnp.where(causal, att, NEG), axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", att, v, precision="highest")
    x = x + _mm(ctx.reshape(B, S, H * dh), w["wo"], quant)
    h = _rms(x, s.norm_eps)
    ffn = jax.nn.silu(_mm(h, w["w_gate"], quant)) * _mm(h, w["w_up"], quant)
    return x + _mm(ffn, w["w_down"], quant)


@partial(jax.jit, static_argnames=("s",))
def _embed(table: jax.Array, tokens: jax.Array, s: Sizes) -> jax.Array:
    return table[tokens].astype(jnp.float32) * s.d_model ** 0.5


@partial(jax.jit, static_argnames=("s",))
def _final(x: jax.Array, rows: jax.Array, cols: jax.Array, s: Sizes
           ) -> jax.Array:
    """The final norm at positions ``(rows, cols)`` of the activations."""
    return _rms(x[rows, cols], s.norm_eps)


_head_mm = jax.jit(_mm, static_argnames=("quant",))


def _logits(xn: jax.Array, pieces: List[jax.Array], quant: Optional[str]
            ) -> jax.Array:
    """The output head, one slice of the vocabulary at a time, so that no
    float32 copy of the whole head is ever made."""
    return jnp.concatenate([_head_mm(xn, w, quant) for w in pieces], axis=1)


# ---------------------------------------------------------------------------
# what the check reads
# ---------------------------------------------------------------------------
def served_gaps(s: Sizes, histories: Sequence[Sequence[int]],
                served_from: Sequence[int], quants: Sequence[str] = (),
                shape: Optional[Tuple[int, int, int]] = None
                ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """For every served token, how far its reference logit lies below the
    reference's best at that position.

    ``histories[i]`` is a session's whole context, prompt and served tokens
    in order, and its tokens from index ``served_from[i]`` on were served.
    Returns the gaps of the served tokens, in session order, and for each
    control precision in ``quants`` the gaps of the token that the control
    puts first at the same positions.  ``shape`` (sessions, tokens, served
    tokens) pads the batch to the most a run can hand over, so that every
    run computes on the same shapes and compiles nothing the cache does
    not hold.
    """
    served_n = sum(len(h) - f for h, f in zip(histories, served_from))
    n, S, R = shape or (len(histories), max(len(h) for h in histories),
                        served_n)
    if len(histories) > n or max(len(h) for h in histories) > S \
            or served_n > R:
        raise ValueError(f"histories exceed the reference's shape "
                         f"{(n, S, R)}")
    per = max(1, min(n, BLOCK_TOKENS // S))
    n = -(-n // per) * per
    toks = np.zeros((n, S), np.int32)
    for i, h in enumerate(histories):
        toks[i, :len(h)] = h
    rows = np.concatenate([np.full(len(h) - f, i) for i, (h, f)
                           in enumerate(zip(histories, served_from))])
    cols = np.concatenate([np.arange(f - 1, len(h) - 1) for h, f
                           in zip(histories, served_from)])
    served = toks[rows, cols + 1]
    m = len(rows)
    pad = R - m + (-R % ROW_CHUNK if R > ROW_CHUNK else 0)
    rows, cols, served = (np.pad(a, (0, pad)) for a in (rows, cols, served))
    blocks = [slice(b, b + per) for b in range(0, n, per)]
    runs = [None] + list(quants)
    w = Weights(s)
    with jax.default_matmul_precision("highest"):
        table = w.embed()
        xs = {q: [_embed(table, jnp.asarray(toks[b]), s) for b in blocks]
              for q in runs}
        del table
        for i in range(s.layers):
            lw = w.layer(i)
            for q in runs:
                xs[q] = [_layer(x, lw, s, q) for x in xs[q]]
            del lw
        head = w.head()
        pieces = [head[:, v:v + HEAD_PIECE]
                  for v in range(0, s.vocab, HEAD_PIECE)]
        del head
        x_all = {q: jnp.concatenate(xs[q]) for q in runs}
        del xs
        ref_best, ref_served, firsts = [], [], {q: [] for q in quants}
        for c in range(0, len(rows), ROW_CHUNK):
            r = jnp.asarray(rows[c:c + ROW_CHUNK])
            k = jnp.asarray(cols[c:c + ROW_CHUNK])
            ref = _logits(_final(x_all[None], r, k, s), pieces, None)
            ref_best.append(np.asarray(jnp.max(ref, -1)))
            got = jnp.asarray(served[c:c + ROW_CHUNK])
            ref_served.append(np.asarray(
                jnp.take_along_axis(ref, got[:, None], -1)[:, 0]))
            for q in quants:
                ctl = _logits(_final(x_all[q], r, k, s), pieces, q)
                top = jnp.argmax(ctl, -1)
                firsts[q].append(np.asarray(
                    jnp.take_along_axis(ref, top[:, None], -1)[:, 0]))
    best = np.concatenate(ref_best)[:m]
    gaps = best - np.concatenate(ref_served)[:m]
    controls = {q: best - np.concatenate(firsts[q])[:m] for q in quants}
    return gaps, controls


def histories_of(sessions: List[Tuple[List[int], List[List[int]]]]
                 ) -> Tuple[List[List[int]], List[int]]:
    """(prompt, [tokens of each served turn]) per session -> the sessions'
    whole histories and where their served tokens start."""
    hist, start = [], []
    for prompt, turns in sessions:
        h = list(prompt)
        start.append(len(h))
        for t in turns:
            h.extend(t)
        hist.append(h)
    return hist, start
