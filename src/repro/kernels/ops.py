"""Jitted public wrappers around the Pallas kernels.

On TPU these call the compiled kernels (interpret=False); in this CPU
container they run in interpret mode, which executes the kernel bodies in
Python for correctness validation against ``ref.py``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.fingerprint import fingerprint_pallas
from repro.kernels.mlstm import mlstm_pallas
from repro.kernels.rglru import rglru_pallas
from repro.kernels.swa import swa_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("window",))
def sliding_window_attention(q, k, v, *, window: int):
    """GQA sliding-window attention.
    q: (B, S, H, dh); k/v: (B, S, KV, dh) -> (B, S, H, dh)."""
    B, S, H, dh = q.shape
    KV = k.shape[2]
    G = H // KV
    pad = (-S) % window
    if pad:
        zq = ((0, 0), (0, pad), (0, 0), (0, 0))
        q, k, v = jnp.pad(q, zq), jnp.pad(k, zq), jnp.pad(v, zq)
    Sp = S + pad
    qp = q.reshape(B, Sp, KV, G, dh).transpose(0, 2, 3, 1, 4)
    qp = qp.reshape(B * KV * G, Sp, dh)
    kp = jnp.repeat(k.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * KV * G, Sp, dh)
    vp = jnp.repeat(v.transpose(0, 2, 1, 3), G, axis=1).reshape(
        B * KV * G, Sp, dh)
    out = swa_pallas(qp, kp, vp, window=window, interpret=not _on_tpu())
    out = out.reshape(B, KV, G, Sp, dh).transpose(0, 3, 1, 2, 4)
    return out.reshape(B, Sp, H, dh)[:, :S]


@functools.partial(jax.jit, static_argnames=("chunk",))
def mlstm_chunkwise(q, k, v, it, ft, *, chunk: int = 256):
    """Chunkwise mLSTM. q/k/v: (B, S, H, dh); it/ft: (B, S, H)."""
    B, S, H, dh = q.shape
    c = min(chunk, S)
    pad = (-S) % c
    if pad:
        z4 = ((0, 0), (0, pad), (0, 0), (0, 0))
        z3 = ((0, 0), (0, pad), (0, 0))
        q, k, v = jnp.pad(q, z4), jnp.pad(k, z4), jnp.pad(v, z4)
        it = jnp.pad(it, z3)
        ft = jnp.pad(ft, z3, constant_values=30.0)  # forget≈1 on padding
    Sp = S + pad

    def plane(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, Sp, -1)

    out = mlstm_pallas(plane(q), plane(k), plane(v),
                       plane(it[..., None]), plane(ft[..., None]),
                       chunk=c, interpret=not _on_tpu())
    return out.reshape(B, H, Sp, dh).transpose(0, 2, 1, 3)[:, :S]


@functools.partial(jax.jit, static_argnames=("t_blk",))
def rglru_scan(a, x, *, t_blk: int = 128):
    """Gated linear recurrence y_t = a_t·y_{t-1} + x_t. a/x: (B, S, W)."""
    B, S, W = a.shape
    tb = min(t_blk, S)
    pad = (-S) % tb
    if pad:
        z = ((0, 0), (0, pad), (0, 0))
        a = jnp.pad(a, z)   # a=0 on padding: resets do not leak
        x = jnp.pad(x, z)
    y = rglru_pallas(a, x, t_blk=tb, interpret=not _on_tpu())
    return y[:, :S]


def to_words(x):
    """uint32 words of any array: 16-bit floats widen bit for bit, 32-bit
    types bitcast, anything else converts."""
    if x.dtype in (jnp.bfloat16, jnp.float16):
        return jax.lax.bitcast_convert_type(x, jnp.uint16).astype(jnp.uint32)
    if x.dtype in (jnp.float32, jnp.int32, jnp.uint32):
        return jax.lax.bitcast_convert_type(x, jnp.uint32)
    return x.astype(jnp.uint32)


@jax.jit
def fingerprint(x):
    """uint32 digest of any array (bitcast to words first)."""
    return fingerprint_pallas(to_words(x).reshape(-1), interpret=not _on_tpu())
