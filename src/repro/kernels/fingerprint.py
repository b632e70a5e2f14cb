"""State-attestation fingerprint Pallas kernel.

The paper's disaggregated-memory checksums (§6.1), adapted to the TPU data
plane (DESIGN.md §3): an order-independent hash-reduce over a parameter/
gradient shard, computed on-device each training step and attested through
uBFT's CTBcast by the replicated training coordinator.  Memory-bound by
design — it reads every word exactly once.

The words are laid out as lane-aligned ``(rows, 128)`` int32 tiles,
zero-padded (a zero word mixes to zero, so padding never changes the
digest).  Grid: 1-D over row blocks; the ``(8, 128)`` output block stays
resident across the grid and accumulates per-lane partial sums, which the
wrapper folds into one word.  All arithmetic is int32: wraparound addition
and multiplication give the same bits as uint32 mod 2**32 (Mosaic has no
unsigned reductions), and the final bitcast yields the uint32 digest.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MIX = 0x9E3779B9  # golden-ratio Weyl constant (matches runtime.attest)
_MIX_I32 = MIX - (1 << 32)  # the same bits as a signed word
LANES = 128
SUBLANES = 8
BLOCK_ROWS = 2048  # rows of 128 words one grid step reads (1 MiB)


def _fp_kernel(x_ref, o_ref):
    @pl.when(pl.program_id(0) == 0)
    def _reset():
        o_ref[...] = jnp.zeros_like(o_ref)

    w = x_ref[...]
    w = w * jnp.int32(_MIX_I32) ^ jax.lax.shift_right_logical(w, 16)
    o_ref[...] += w.reshape(-1, SUBLANES, LANES).sum(axis=0)


def fingerprint_pallas(words: jax.Array, *,
                       interpret: bool = False) -> jax.Array:
    """words: (N,) uint32 (bitcast upstream); returns (1,) uint32 digest.

    Callers pick ``interpret`` by platform through ``repro.kernels.ops``."""
    n = words.shape[0]
    rows = -(-max(n, 1) // LANES)
    rows = -(-rows // SUBLANES) * SUBLANES
    blk = min(BLOCK_ROWS, rows)
    rows = -(-rows // blk) * blk
    w = jax.lax.bitcast_convert_type(words, jnp.int32)
    w = jnp.pad(w, (0, rows * LANES - n)).reshape(rows, LANES)
    partial = pl.pallas_call(
        _fp_kernel,
        grid=(rows // blk,),
        in_specs=[pl.BlockSpec((blk, LANES), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SUBLANES, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((SUBLANES, LANES), jnp.int32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(w)
    return jax.lax.bitcast_convert_type(jnp.sum(partial), jnp.uint32)[None]
