"""Per-architecture smoke tests (deliverable f): every assigned arch at a
reduced same-family config runs one train step and one prefill+decode step
on CPU, asserting output shapes and finiteness."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke_config, list_archs
from repro.models.common import init_params, params_count
from repro.models.transformer import decode_step, lm_loss, prefill
from repro.optim.adamw import AdamWConfig, adamw_init, adamw_update
from repro.runtime.steps import make_train_step

pytestmark = pytest.mark.slow

ARCHS = list_archs()
B, S = 2, 24


def _inputs(cfg, key):
    if cfg.frontend:
        return jax.random.normal(key, (B, S, cfg.d_model), jnp.float32)
    return jax.random.randint(key, (B, S), 0, cfg.vocab)


@pytest.mark.parametrize("arch", ARCHS)
def test_full_config_is_exact_assignment(arch):
    cfg = get_config(arch)
    cfg.validate()
    spec = {
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "qwen3-moe-235b-a22b": (94, 4096, 64, 4, 1536, 151936),
        "qwen3-8b": (36, 4096, 32, 8, 12288, 151936),
        "chatglm3-6b": (28, 4096, 32, 2, 13696, 65024),
        "gemma3-4b": (34, 2560, 8, 4, 10240, 262144),
        "gemma3-1b": (26, 1152, 4, 1, 6912, 262144),
        "musicgen-large": (48, 2048, 32, 32, 8192, 2048),
        "chameleon-34b": (48, 8192, 64, 8, 22016, 65536),
        "xlstm-1.3b": (48, 2048, 4, 4, 0, 50304),
        "recurrentgemma-2b": (26, 2560, 10, 1, 7680, 256000),
    }[arch]
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.d_ff, cfg.vocab) == spec


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_train_step(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    opt = adamw_init(params, AdamWConfig())
    step = jax.jit(make_train_step(cfg))
    batch = {"inputs": _inputs(cfg, key),
             "targets": jax.random.randint(jax.random.PRNGKey(1), (B, S),
                                           0, cfg.vocab)}
    new_params, new_opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(metrics["grad_fp"]) != 0
    # params actually moved
    moved = any(
        not np.allclose(np.asarray(a, np.float32), np.asarray(b, np.float32))
        for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(new_params)))
    assert moved


@pytest.mark.parametrize("arch", ARCHS)
def test_smoke_prefill_then_decode(arch):
    cfg = get_smoke_config(arch)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    inputs = _inputs(cfg, key)
    logits, caches = jax.jit(
        lambda p, i: prefill(cfg, p, i, max_seq=S + 8))(params, inputs)
    assert logits.shape == (B, cfg.vocab)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    dstep = jax.jit(lambda p, c, t, pos: decode_step(cfg, p, c, t, pos))
    for i in range(3):
        logits, caches = dstep(params, caches, tok, jnp.int32(S + i))
        assert logits.shape == (B, cfg.vocab)
        assert np.isfinite(np.asarray(logits, np.float32)).all()
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


def test_train_loss_decreases_small_model():
    """A few steps of real training on the structured pipeline reduce loss."""
    from repro.data import DataConfig, TokenPipeline
    cfg = get_smoke_config("qwen3-8b")
    pipe = TokenPipeline(DataConfig(vocab=cfg.vocab, seq_len=32,
                                    global_batch=8, seed=3))
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params, AdamWConfig(lr=3e-3))
    step = jax.jit(make_train_step(cfg, opt_cfg=AdamWConfig(lr=3e-3)))
    losses = []
    for i in range(30):
        b = pipe.global_batch(i)
        params, opt, m = step(params, opt, {k: jnp.asarray(v)
                                            for k, v in b.items()})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.2, losses[:3] + losses[-3:]
