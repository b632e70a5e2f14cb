"""The Qwen3 sparse-expert decoder family, as one chip's expert-parallel
share: grouped-query attention as in ``dense_gqa``, then in every layer a
mixture of SwiGLU experts of which this chip holds a contiguous range.

What the harness calls, and nothing else:

* :class:`Sizes`, read from the configuration file alone;
* :func:`program_config`, the program's ``ModelConfig``: each width as the
  repo's base and the published config have it, the router at its
  published width (``published.num_experts``) and the experts held here
  (``config.num_experts``, from ``program.first_held_expert``);
* :func:`sizes`;
* :func:`request_flops`, the model FLOPs behind ``step_mfu``;
* :class:`Weights` and :func:`layer`, the plain reference's pieces;

and, for the expert layer's roofline once the harness reads the program's
counters, :func:`expert_flops` and :func:`expert_bytes`.

The layer: the attention sublayer of ``dense_gqa`` (its layer run with a
feed-forward of zeros, which adds exactly 0, also under the controls'
rounding: silu(0) * 0 = 0); then RMSNorm, the router (a bias-free D x E
product), the top-k of its E logits, a softmax over those k (the
published ``norm_topk_prob``), and for every held expert that a token
chose, that expert's SwiGLU output times the token's weight; residual.
Nothing is dropped.  What the absent experts would add is left out, in the
program and here alike.  Every matrix product is float32 at
``precision="highest"``; ``quant`` rounds every weight matrix and matrix
input, the router's too, as :func:`harness.reference.mm` does.

Weights: drawn again from the program's key by the program's recipe:
``dense_gqa``'s for embedding, head and attention; the router from the
layer's ninth key in float32; each held expert ``e``'s gate, up and down
matrices from the layer's tenth, eleventh and twelfth keys with ``e``
folded in, so that a share's experts are those of the whole layer.

FLOPs: attention and the head as ``dense_gqa`` counts them, plus per
forwarded token and layer the router's 2 D E and 6 D F for each pair of
the token and a held expert it is routed to.  A token makes k n / E such
pairs in expectation (0.5 with 8 of 128 held and top-8); the count uses
that expectation, not the pairs a run routed.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from harness import models, spec
from harness.reference import draw, mm, rms
from harness.spec import SpecError

dense = spec.family("dense_gqa")

#: fields that are widths: they must equal the repo's own config, which
#: must equal the published value (no width is ever cut)
WIDTHS = ("d_model", "n_heads", "n_kv_heads", "head_dim", "vocab")
#: the attention sublayer's weights in a reference layer
ATTN = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


@dataclass(frozen=True)
class Sizes:
    """What the FLOP count and the plain reference need of a share of a
    sparse-expert decoder."""
    #: the attention sublayer, as ``dense_gqa`` describes it (no FFN)
    attn: Any
    layers: int
    d_model: int
    vocab: int
    norm_eps: float
    experts: int                # routed: the router's width
    top_k: int
    d_expert: int
    first_held: int
    held: int
    dtype: str                  # of the served weights


def _experts(name: str, cfg_file: Dict[str, Any]) -> tuple:
    """(routed experts, top-k, expert width, first held, held) as the file
    states them."""
    c = cfg_file["config"]
    if not (c["norm_topk_prob"] and c["decoder_sparse_step"] == 1
            and c["mlp_only_layers"] == []):
        raise SpecError(f"{name}: the qwen3_moe family describes models "
                        f"whose every layer is sparse, top-k renormalised")
    routed = int(cfg_file["published"]["num_experts"])
    first = int(cfg_file["program"]["first_held_expert"])
    held = int(c["num_experts"])
    if first < 0 or held < 1 or first + held > routed:
        raise SpecError(f"{name}: experts {first}..{first + held - 1} are "
                        f"not among the {routed} routed")
    return (routed, int(c["num_experts_per_tok"]),
            int(c["moe_intermediate_size"]), first, held)


def program_config(name: str, cfg_file: Dict[str, Any], smoke: bool = False):
    """The ``ModelConfig`` the cell runs.  ``smoke`` keeps the repo's
    CPU-sized smoke widths, depth and experts, with the file's other
    settings, and holds the share of them that gives a token as many held
    experts in expectation as the file's share does."""
    from repro.configs import get_config
    cfg = models.build(name, cfg_file, WIDTHS, smoke)
    if cfg.family != "moe" or cfg.moe is None:
        raise SpecError(f"{name}: the qwen3_moe family describes sparse-"
                        f"expert decoders only")
    routed, top_k, width, first, held = _experts(name, cfg_file)
    base = cfg_file["program"]["base"]
    full = get_config(base).moe
    for field, v in (("n_experts", routed), ("top_k", top_k),
                     ("d_expert", width)):
        if getattr(full, field) != v:
            raise SpecError(f"{name}: {field} is {v} in the file and "
                            f"{getattr(full, field)} in the repo's {base}")
    m = cfg.moe
    if smoke:
        first = 0
        held = max(1, held * top_k * m.n_experts // (routed * m.top_k))
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(m, held=(first, held)))


def sizes(cfg_file: Dict[str, Any], smoke: bool = False) -> Sizes:
    """:class:`Sizes` as the file states them, or of the smoke-sized config
    (CPU rehearsals only)."""
    if smoke:
        c = program_config(cfg_file["name"], cfg_file, smoke=True)
        m = c.moe
        attn = dense.Sizes(
            layers=c.n_layers, d_model=c.d_model, heads=c.n_heads,
            kv_heads=c.n_kv_heads, head_dim=c.dh, d_ff=0, vocab=c.vocab,
            norm_eps=c.norm_eps, rope_theta=c.rope_theta,
            rope_fraction=c.rope_fraction, qk_norm=c.qk_norm,
            tie_embeddings=c.tie_embeddings, dtype=c.dtype)
        routed, top_k, width, (first, held) = (m.n_experts, m.top_k,
                                               m.d_expert, m.held)
    else:
        f = models.fields(cfg_file)
        attn = dense.Sizes(
            layers=int(f["n_layers"]), d_model=int(f["d_model"]),
            heads=int(f["n_heads"]), kv_heads=int(f["n_kv_heads"]),
            head_dim=int(f["head_dim"]), d_ff=0, vocab=int(f["vocab"]),
            norm_eps=float(f["norm_eps"]), rope_theta=float(f["rope_theta"]),
            rope_fraction=float(f["rope_fraction"]),
            qk_norm=bool(f["qk_norm"]),
            tie_embeddings=bool(f["tie_embeddings"]), dtype=f["dtype"])
        routed, top_k, width, first, held = _experts(cfg_file["name"],
                                                     cfg_file)
    return Sizes(attn=attn, layers=attn.layers, d_model=attn.d_model,
                 vocab=attn.vocab, norm_eps=attn.norm_eps, experts=routed,
                 top_k=top_k, d_expert=width, first_held=first, held=held,
                 dtype=attn.dtype)


# ---------------------------------------------------------------------------
# FLOPs and bytes
# ---------------------------------------------------------------------------
def request_flops(s: Sizes, processed: int, history: int, n: int) -> int:
    """FLOPs one replica needs for a request: ``history`` tokens of context
    of which the first ``processed`` were forwarded by earlier turns, and
    ``n`` generated tokens.  Held pairs are counted at their expectation,
    ``top_k * held / experts`` a token."""
    tokens = max(0, history + n - 1 - processed)
    D = s.d_model
    per_token = 2 * D * s.experts \
        + 6 * D * s.d_expert * s.top_k * s.held // s.experts
    return dense.request_flops(s.attn, processed, history, n) \
        + tokens * s.layers * per_token


def expert_flops(s: Sizes, routed_pairs: int, held_pairs: int) -> int:
    """FLOPs of the expert layers' work that the counters ``moe.routed_pairs``
    and ``moe.held_pairs`` record: the router on each routed token, a
    SwiGLU on each held pair."""
    return routed_pairs // s.top_k * 2 * s.d_model * s.experts \
        + held_pairs * 6 * s.d_model * s.d_expert


def expert_bytes(s: Sizes, held_experts_hit: int, layer_passes: int) -> int:
    """Bytes the expert layers need to read: each held expert that a pass
    routed a token to (``moe.held_experts_hit``) reads its three matrices
    in the served dtype once, and each pass of a layer (``layers`` times the
    prefills and decode steps) reads the float32 router.  Held experts that
    no token chose are not counted, so the roofline reads the same work
    whatever implements it; activations are left out (a token's are
    ``D / (3 F)`` of one expert's weights)."""
    item = jnp.dtype(s.dtype).itemsize
    return held_experts_hit * 3 * s.d_model * s.d_expert * item \
        + layer_passes * s.d_model * s.experts * 4


# ---------------------------------------------------------------------------
# the plain reference's pieces
# ---------------------------------------------------------------------------
class Weights:
    """The served weights, drawn again from their key one piece at a time."""

    def __init__(self, s: Sizes):
        self.s = s
        self.dtype = jnp.dtype(s.dtype)
        # its recipe draws a 1-wide FFN (0 wide has no scale), replaced by
        # zeros in layer()
        self.attn = dense.Weights(dataclasses.replace(s.attn, d_ff=1))

    def embed(self) -> jax.Array:
        return self.attn.embed()

    def head(self) -> jax.Array:
        return self.attn.head()

    def layer(self, i: int) -> Dict[str, jax.Array]:
        s = self.s
        D, F = s.d_model, s.d_expert
        w = self.attn.layer(i)
        w.update(w_gate=jnp.zeros((D, 1)), w_up=jnp.zeros((D, 1)),
                 w_down=jnp.zeros((1, D)))
        ks = jax.random.split(self.attn.layer_keys[i], 16)
        held = range(s.first_held, s.first_held + s.held)

        def experts(k, shape, scale):
            return jnp.stack([draw(jax.random.fold_in(k, e), shape, scale,
                                   self.dtype) for e in held])
        w["router"] = draw(ks[8], (D, s.experts), D ** -0.5, jnp.float32)
        w["expert_gate"] = experts(ks[9], (D, F), D ** -0.5)
        w["expert_up"] = experts(ks[10], (D, F), D ** -0.5)
        w["expert_down"] = experts(ks[11], (F, D), F ** -0.5)
        return w


@partial(jax.jit, static_argnames=("s", "quant"))
def layer(x: jax.Array, w: Dict[str, jax.Array], s: Sizes,
          quant: Optional[str]) -> jax.Array:
    """One layer's forward of the activations ``x`` (B, S, D)."""
    x = dense.layer(x, {k: w[k] for k in ATTN}, s.attn, quant)
    h = rms(x, s.norm_eps)
    top, chosen = jax.lax.top_k(mm(h, w["router"], quant), s.top_k)
    gate = jax.nn.softmax(top, axis=-1)                   # (B, S, k)
    out = jnp.zeros_like(x)
    for j in range(s.held):
        g = jnp.sum(jnp.where(chosen == s.first_held + j, gate, 0.0), -1)
        y = jax.nn.silu(mm(h, w["expert_gate"][j], quant)) \
            * mm(h, w["expert_up"][j], quant)
        out = out + g[..., None] * mm(y, w["expert_down"][j], quant)
    return x + out
