"""The dense GQA decoder family: grouped-query attention with SwiGLU
feed-forward in every layer.

What the harness calls, and nothing else:

* :class:`Sizes`, read from the configuration file alone;
* :func:`program_config`, the program's ``ModelConfig``, each width as
  the repo's base and the published config have it;
* :func:`sizes`;
* :func:`request_flops`, the model FLOPs behind ``step_mfu``;
* :class:`Weights` and :func:`layer`, the plain reference's pieces.

FLOPs: a forward of one token at position ``p`` (0-based, so it attends to
``p + 1`` keys) costs two FLOPs per weight it multiplies plus the causal
attention over its keys.  The output head is counted once per generated
token.  Recomputation is not counted: a request needs the forwards of the
tokens that entered the session's context since its last forward, plus one
per generated token after the first.

Reference: the served weights are drawn again from the program's key by
the program's recipe (embedding, output head, then one key per layer and
sixteen per layer's matrices; each matrix ``normal(key) * fan_in ** -0.5``
rounded to the served dtype; norm gains 1), one layer at a time.  The
layer follows the published one: RMSNorm, q/k/v projections, qk-RMSNorm
where the model has it, rotary embedding on the leading ``rope_fraction``
of each head (rotate-half pairs), causal grouped-query softmax attention,
output projection, residual; RMSNorm, SwiGLU, residual.  Every matrix
product runs at ``precision="highest"``; ``quant`` rounds every weight
matrix and matrix input as :func:`harness.reference.mm` does.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from harness import models
from harness.reference import WEIGHTS_KEY, draw, mm, rms
from harness.spec import SpecError

#: fields that are widths: they must equal the repo's own config, which
#: must equal the published value (no width is ever cut)
WIDTHS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "d_ff", "vocab")
NEG = -1e30


@dataclass(frozen=True)
class Sizes:
    """What the FLOP count and the plain reference need of a dense GQA
    decoder."""
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    norm_eps: float
    rope_theta: float
    rope_fraction: float
    qk_norm: bool
    tie_embeddings: bool
    dtype: str                  # of the served weights


def program_config(name: str, cfg_file: Dict[str, Any], smoke: bool = False):
    """The ``ModelConfig`` the cell runs.  ``smoke`` keeps the repo's
    CPU-sized smoke widths and depth, with the file's other settings."""
    cfg = models.build(name, cfg_file, WIDTHS, smoke)
    if cfg.family != "dense" or cfg.moe is not None:
        raise SpecError(f"{name}: the dense_gqa family describes dense "
                        f"decoders only")
    return cfg


def sizes(cfg_file: Dict[str, Any], smoke: bool = False) -> Sizes:
    """:class:`Sizes` as the file states them, or of the smoke-sized config
    (CPU rehearsals only)."""
    if smoke:
        c = program_config(cfg_file["name"], cfg_file, smoke=True)
        return Sizes(layers=c.n_layers, d_model=c.d_model, heads=c.n_heads,
                     kv_heads=c.n_kv_heads, head_dim=c.dh, d_ff=c.d_ff,
                     vocab=c.vocab, norm_eps=c.norm_eps,
                     rope_theta=c.rope_theta, rope_fraction=c.rope_fraction,
                     qk_norm=c.qk_norm, tie_embeddings=c.tie_embeddings,
                     dtype=c.dtype)
    f = models.fields(cfg_file)
    return Sizes(layers=int(f["n_layers"]), d_model=int(f["d_model"]),
                 heads=int(f["n_heads"]), kv_heads=int(f["n_kv_heads"]),
                 head_dim=int(f["head_dim"]), d_ff=int(f["d_ff"]),
                 vocab=int(f["vocab"]), norm_eps=float(f["norm_eps"]),
                 rope_theta=float(f["rope_theta"]),
                 rope_fraction=float(f["rope_fraction"]),
                 qk_norm=bool(f["qk_norm"]),
                 tie_embeddings=bool(f["tie_embeddings"]), dtype=f["dtype"])


# ---------------------------------------------------------------------------
# FLOPs
# ---------------------------------------------------------------------------
def _layer_weights(s: Sizes) -> int:
    """Multiplied weights of one layer (q, k, v, o and the three FFN)."""
    q = s.heads * s.head_dim
    kv = s.kv_heads * s.head_dim
    return s.d_model * q + 2 * s.d_model * kv + q * s.d_model \
        + 3 * s.d_model * s.d_ff


def _span_flops(s: Sizes, start: int, stop: int) -> int:
    """Forwards at positions ``start .. stop - 1``, in closed form."""
    n = stop - start
    if n <= 0:
        return 0
    pos_sum = (start + stop - 1) * n // 2 + n        # sum of (pos + 1)
    return s.layers * (2 * _layer_weights(s) * n
                       + 4 * s.heads * s.head_dim * pos_sum)


def request_flops(s: Sizes, processed: int, history: int, n: int) -> int:
    """FLOPs one replica needs for a request: ``history`` tokens of context
    of which the first ``processed`` were forwarded by earlier turns, and
    ``n`` generated tokens."""
    return _span_flops(s, processed, history + n - 1) \
        + n * 2 * s.d_model * s.vocab


# ---------------------------------------------------------------------------
# the plain reference's pieces
# ---------------------------------------------------------------------------
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
        return draw(self.k_emb, (s.vocab, s.d_model), s.d_model ** -0.5,
                    self.dtype)

    def head(self) -> jax.Array:
        s = self.s
        if s.tie_embeddings:
            return self.embed().T
        return draw(self.k_out, (s.d_model, s.vocab), s.d_model ** -0.5,
                    self.dtype)

    def layer(self, i: int) -> Dict[str, jax.Array]:
        s = self.s
        ks = jax.random.split(self.layer_keys[i], 16)
        D, q, kv, F = (s.d_model, s.heads * s.head_dim,
                       s.kv_heads * s.head_dim, s.d_ff)
        return {
            "wq": draw(ks[0], (D, q), D ** -0.5, self.dtype),
            "wk": draw(ks[1], (D, kv), D ** -0.5, self.dtype),
            "wv": draw(ks[2], (D, kv), D ** -0.5, self.dtype),
            "wo": draw(ks[3], (q, D), q ** -0.5, self.dtype),
            "w_gate": draw(ks[9], (D, F), D ** -0.5, self.dtype),
            "w_up": draw(ks[10], (D, F), D ** -0.5, self.dtype),
            "w_down": draw(ks[11], (F, D), F ** -0.5, self.dtype),
        }


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
def layer(x: jax.Array, w: Dict[str, jax.Array], s: Sizes,
          quant: Optional[str]) -> jax.Array:
    """One layer's forward of the activations ``x`` (B, S, D)."""
    B, S, D = x.shape
    H, KV, dh = s.heads, s.kv_heads, s.head_dim
    h = rms(x, s.norm_eps)
    q = mm(h, w["wq"], quant).reshape(B, S, H, dh)
    k = mm(h, w["wk"], quant).reshape(B, S, KV, dh)
    v = mm(h, w["wv"], quant).reshape(B, S, KV, dh)
    if s.qk_norm:
        q, k = rms(q, s.norm_eps), rms(k, s.norm_eps)
    q = _rope(q, s.rope_theta, s.rope_fraction)
    k = _rope(k, s.rope_theta, s.rope_fraction)
    q = q.reshape(B, S, KV, H // KV, dh)
    att = jnp.einsum("bqkgd,bskd->bkgqs", q, k,
                     precision="highest") * dh ** -0.5
    causal = np.tril(np.ones((S, S), bool))
    att = jax.nn.softmax(jnp.where(causal, att, NEG), axis=-1)
    ctx = jnp.einsum("bkgqs,bskd->bqkgd", att, v, precision="highest")
    x = x + mm(ctx.reshape(B, S, H * dh), w["wo"], quant)
    h = rms(x, s.norm_eps)
    ffn = jax.nn.silu(mm(h, w["w_gate"], quant)) * mm(h, w["w_up"], quant)
    return x + mm(ffn, w["w_down"], quant)
