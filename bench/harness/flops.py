"""Model FLOPs the served work needs, from the configuration's sizes alone.

A forward of one token at position ``p`` (0-based, so it attends to
``p + 1`` keys) of a dense GQA decoder with SwiGLU feed-forward costs two
FLOPs per weight it multiplies plus the causal attention over its keys.
The output head is counted once per generated token.  Recomputation is not
counted: a request needs the forwards of the tokens that entered the
session's context since its last forward, plus one per generated token
after the first.
"""

from __future__ import annotations

from harness.models import Sizes


def layer_weights(s: Sizes) -> int:
    """Multiplied weights of one layer (q, k, v, o and the three FFN)."""
    q = s.heads * s.head_dim
    kv = s.kv_heads * s.head_dim
    return s.d_model * q + 2 * s.d_model * kv + q * s.d_model \
        + 3 * s.d_model * s.d_ff


def token_flops(s: Sizes, pos: int) -> int:
    """One token's forward through every layer, output head excluded."""
    attn = 4 * s.heads * s.head_dim * (pos + 1)     # QK^T and PV
    return s.layers * (2 * layer_weights(s) + attn)


def head_flops(s: Sizes) -> int:
    return 2 * s.d_model * s.vocab


def span_flops(s: Sizes, start: int, stop: int) -> int:
    """Forwards at positions ``start .. stop - 1``, in closed form."""
    n = stop - start
    if n <= 0:
        return 0
    pos_sum = (start + stop - 1) * n // 2 + n        # sum of (pos + 1)
    return s.layers * (2 * layer_weights(s) * n
                       + 4 * s.heads * s.head_dim * pos_sum)


def request_flops(s: Sizes, processed: int, history: int, n: int) -> int:
    """FLOPs one replica needs for a request: ``history`` tokens of context
    of which the first ``processed`` were forwarded by earlier turns, and
    ``n`` generated tokens."""
    return span_flops(s, processed, history + n - 1) + n * head_flops(s)
