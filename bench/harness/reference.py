"""The plain reference: a decoder in float32, and what the check reads
from it.

It imports nothing of the program.  The configuration's family
(``bench/families/<family>.py``) draws the served weights again, one
layer at a time, by the recipe they were drawn with, and computes one
layer's forward; so the reference never holds the whole model.  This
module embeds the tokens, runs the family's layers over blocks of
sessions, and takes the final RMSNorm and the output head, one slice of
the vocabulary at a time.  Every matrix product runs at
``precision="highest"``.

``quant`` computes the same forward with every weight matrix and every
matrix input rounded to int8 or fp8 (e4m3), each with a scale per row or
column (:func:`mm`): the control, one precision step below the served
bfloat16.
"""

from __future__ import annotations

from functools import partial
from types import ModuleType
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

#: the key the served weights are drawn from (the program's, not the seed's)
WEIGHTS_KEY = 0
#: tokens of activations computed at once
BLOCK_TOKENS = 8192
#: vocabulary columns of the output head per product
HEAD_PIECE = 8192
#: positions whose logits are taken at once
ROW_CHUNK = 1024


def draw(key, shape, scale, dtype):
    """A served weight matrix, drawn as the program drew it."""
    # the served weights were drawn op by op, outside any jit: do the same,
    # so each rounding happens where it happened there
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


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


def mm(a: jax.Array, w: jax.Array, quant: Optional[str]) -> jax.Array:
    """``a @ w`` in float32 at the highest precision, each side rounded to
    ``quant`` first."""
    a = _round(a, quant, -1)
    w = _round(w.astype(jnp.float32), quant, 0)
    return jnp.matmul(a, w, precision="highest")


def rms(x: jax.Array, eps: float) -> jax.Array:
    """RMSNorm with unit gains."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


@partial(jax.jit, static_argnames=("s",))
def _embed(table: jax.Array, tokens: jax.Array, s: Any) -> jax.Array:
    return table[tokens].astype(jnp.float32) * s.d_model ** 0.5


@partial(jax.jit, static_argnames=("s",))
def _final(x: jax.Array, rows: jax.Array, cols: jax.Array, s: Any
           ) -> jax.Array:
    """The final norm at positions ``(rows, cols)`` of the activations."""
    return rms(x[rows, cols], s.norm_eps)


_head_mm = jax.jit(mm, static_argnames=("quant",))


def _logits(xn: jax.Array, pieces: List[jax.Array], quant: Optional[str]
            ) -> jax.Array:
    """The output head, one slice of the vocabulary at a time, so that no
    float32 copy of the whole head is ever made."""
    return jnp.concatenate([_head_mm(xn, w, quant) for w in pieces], axis=1)


# ---------------------------------------------------------------------------
# what the check reads
# ---------------------------------------------------------------------------
def served_gaps(family: ModuleType, s: Any,
                histories: Sequence[Sequence[int]],
                served_from: Sequence[int], quants: Sequence[str] = (),
                shape: Optional[Tuple[int, int, int]] = None
                ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
    """For every served token, how far its reference logit lies below the
    reference's best at that position, in the model ``family`` describes
    at sizes ``s`` (its ``Sizes``).

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
    w = family.Weights(s)
    with jax.default_matmul_precision("highest"):
        table = w.embed()
        xs = {q: [_embed(table, jnp.asarray(toks[b]), s) for b in blocks]
              for q in runs}
        del table
        for i in range(s.layers):
            lw = w.layer(i)
            for q in runs:
                xs[q] = [family.layer(x, lw, s, q) for x in xs[q]]
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
