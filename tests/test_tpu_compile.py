"""Compiles for a TPU v5e that is described, not attached.

The chip's compiler refuses what interpret mode and the CPU backend accept
(unsigned reductions in a kernel, unaligned blocks, programs larger than
the device's memory), so the kernels and steps of the main path are
compiled here at their real widths.  Nothing runs: these tests say that
the programs compile and fit, not what they compute or how fast.

The topology is described inside module-scoped fixtures only: the TPU
library may be loaded by one process at a time, and a test worker that
loads it keeps it until it exits.
"""

import functools
import os

import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.fingerprint import fingerprint_pallas  # noqa: E402
from repro.models.common import init_params  # noqa: E402
from repro.models.transformer import (decode_step, init_caches,  # noqa: E402
                                      lm_loss, prefill)

#: HBM of one TPU v5e chip (Google Cloud documentation, "TPU v5e")
V5E_HBM_BYTES = 16 * 2**30
#: gemma3-1b's embedding table, in 32-bit words: 262,144 × 1,152
GEMMA3_EMBED_WORDS = 262_144 * 1_152


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import compilation_cache, topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def gemma3():
    cfg = get_config("gemma3-1b")
    return cfg, jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))


def _placed(tree, sharding):
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
        tree)


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("n", [1_000, 2**20 + 77, GEMMA3_EMBED_WORDS],
                         ids=["under_block", "ragged_rows", "gemma3_embed"])
def test_fingerprint_kernel_compiles(one_chip, n):
    words = jax.ShapeDtypeStruct((n,), jnp.uint32, sharding=one_chip)
    fn = jax.jit(functools.partial(fingerprint_pallas, interpret=False))
    lowered = fn.lower(words)
    assert "tpu_custom_call" in lowered.as_text()
    compiled = lowered.compile()
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_gemma3_serving_steps_fit_one_chip(one_chip, gemma3):
    cfg, shapes = gemma3
    params = _placed(shapes, one_chip)
    max_seq = 16 + 8 * 8 + 8      # chip_smoke's serving run
    caches = _placed(jax.eval_shape(lambda: init_caches(cfg, 1, max_seq)),
                     one_chip)
    tok = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    step = jax.jit(lambda p, c, t, q: decode_step(cfg, p, c, t, q)).lower(
        params, caches, tok, pos).compile()
    assert _device_bytes(step) < V5E_HBM_BYTES
    ids = jax.ShapeDtypeStruct((1, 16), jnp.int32, sharding=one_chip)
    pf = jax.jit(lambda p, i: prefill(cfg, p, i, max_seq=max_seq)).lower(
        params, ids).compile()
    assert _device_bytes(pf) < V5E_HBM_BYTES


def test_gemma3_decode_loop_fits_one_chip(one_chip, gemma3):
    """The token server's decode loop (``serve.greedy_tokens``), 8 tokens
    as in chip_smoke's serving run: one loop on the device, not unrolled."""
    from repro.launch.serve import greedy_tokens
    cfg, shapes = gemma3
    params = _placed(shapes, one_chip)
    max_seq = 16 + 8 * 8 + 8
    caches = _placed(jax.eval_shape(lambda: init_caches(cfg, 1, max_seq)),
                     one_chip)
    tok = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    lowered = jax.jit(lambda p, c, t, q: greedy_tokens(cfg, p, c, t, q, 8)
                      ).lower(params, caches, tok, pos)
    step = jax.jit(lambda p, c, t, q: decode_step(cfg, p, c, t, q)).lower(
        params, caches, tok, pos)
    # one loop around the step's own (its layer scans): 7 steps, one body
    assert lowered.as_text().count("stablehlo.while") == \
        step.as_text().count("stablehlo.while") + 1
    compiled = lowered.compile()
    # only the tokens leave the loop (one padded tile), not the caches
    assert compiled.memory_analysis().output_size_in_bytes <= 4096
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_moe_decode_loop_copies_no_layer_of_experts(one_chip):
    """The token server's decode loop for the sparse-expert benchmark
    model (qwen3-moe-235b-a22b's widths, 10 layers, 8 of 128 experts
    held): no conditional, loop or custom call takes a layer's share of
    the held experts, and the program's scratch memory holds less than one
    such share (302 MB), so none is copied out of the layer stack."""
    import dataclasses

    from hlo_operands import control_flow_operands
    from repro.launch.serve import greedy_tokens
    from repro.models.common import default_blocks
    base = get_config("qwen3-moe-235b-a22b")
    cfg = dataclasses.replace(base, n_layers=10, blocks=default_blocks(10),
                              moe=dataclasses.replace(base.moe, held=(0, 8)))
    params = _placed(jax.eval_shape(
        lambda: init_params(cfg, jax.random.PRNGKey(0))), one_chip)
    caches = _placed(jax.eval_shape(lambda: init_caches(cfg, 1, 512)),
                     one_chip)
    tok = jax.ShapeDtypeStruct((1,), jnp.int32, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    compiled = jax.jit(lambda p, c, t, q: greedy_tokens(cfg, p, c, t, q, 32)
                       ).lower(params, caches, tok, pos).compile()
    n, D, F = 8, cfg.d_model, cfg.moe.d_expert
    share = {(n, D, F), (n, F, D)}
    assert not [o for o in control_flow_operands(compiled.as_text())
                if tuple(d for d in o[2] if d != 1) in share]
    assert compiled.memory_analysis().temp_size_in_bytes < 3 * n * D * F * 2
    assert _device_bytes(compiled) < V5E_HBM_BYTES


def test_gemma3_sharded_loss_compiles_on_2x2(topo, gemma3):
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_mesh
    from repro.parallel.sharding import (named, param_pspecs,
                                         shard_ctx_for_mesh)
    cfg, shapes = gemma3
    mesh = make_mesh((2, 2), ("data", "model"), devices=topo.devices)
    ctx = shard_ctx_for_mesh(mesh)
    shardings = named(mesh, param_pspecs(cfg, shapes, mesh))
    params = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, shardings)
    rows = NamedSharding(mesh, P("data"))
    ids = jax.ShapeDtypeStruct((4, 64), jnp.int32, sharding=rows)
    compiled = jax.jit(lambda p, i, t: lm_loss(cfg, p, i, t, ctx),
                       in_shardings=(shardings, rows, rows)).lower(
        params, ids, ids).compile()
    whole = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(shapes))
    # each device holds a share of the parameters, not all of them
    assert compiled.memory_analysis().argument_size_in_bytes < whole / 2
    assert "all-gather" in compiled.as_text()
