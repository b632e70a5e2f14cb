"""The expert layer that holds a share of the experts
(``repro.models.moe.held_moe_ffn``), on the CPU at the smoke size of
qwen3-moe-235b-a22b (8 experts, top-2).

The shares of a layer add up to the whole layer, as a plain float32
reference computes it; the layer drops no token when every token is
routed to the same experts; its counters reach the host through the token
server's one read of the tokens; and the dense models' serving programs
return no counters at all.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.configs import get_smoke_config
from repro.launch import serve
from repro.models.common import LayerSpec, init_layer_params, rms_norm
from repro.models.moe import COUNTERS, held_moe_ffn, moe_ffn_local
from repro.models.transformer import _ffn_part

T = 64


def moe_config(held=None, dtype="float32"):
    cfg = get_smoke_config("qwen3-moe-235b-a22b")
    return dataclasses.replace(
        cfg, dtype=dtype, moe=dataclasses.replace(cfg.moe, held=held))


def layer_params(cfg):
    return init_layer_params(cfg, LayerSpec("attn"), jax.random.PRNGKey(3))


def reference(cfg, p, x):
    """The whole layer's expert output in float32, one token at a time:
    softmax over the top-k router logits, each chosen expert's SwiGLU."""
    m = cfg.moe
    x = np.asarray(x, np.float64)
    logits = x @ np.asarray(p["router"], np.float64)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        top = np.argsort(-logits[t])[:m.top_k]
        w = np.exp(logits[t, top] - logits[t, top].max())
        w /= w.sum()
        for e, we in zip(top, w):
            g = x[t] @ np.asarray(p["w_gate"][e], np.float64)
            u = x[t] @ np.asarray(p["w_up"][e], np.float64)
            h = g / (1 + np.exp(-g)) * u
            out[t] += we * (h @ np.asarray(p["w_down"][e], np.float64))
    return out


def test_shares_add_up_to_the_whole_layer():
    whole = moe_config()
    pw = layer_params(whole)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, T // 2, whole.d_model))
    h = rms_norm(x, pw["ln2"], whole.norm_eps).reshape(T, -1)
    want = np.asarray(x) + reference(whole, pw, h).reshape(x.shape)
    got_whole, _ = _ffn_part(whole, pw, x, None)
    np.testing.assert_allclose(got_whole, want, rtol=1e-4, atol=1e-4)
    parts, held = [], 0
    for first in range(0, 8, 2):
        cfg = moe_config(held=(first, 2))
        p = layer_params(cfg)
        # a share's experts are the whole layer's, and its router is whole
        np.testing.assert_array_equal(p["router"], pw["router"])
        np.testing.assert_array_equal(p["w_up"], pw["w_up"][first:first + 2])
        y, counts = _ffn_part(cfg, p, x, None)
        parts.append(np.asarray(y - x))          # the residual counted once
        held += int(counts[COUNTERS.index("moe.held_pairs")])
    np.testing.assert_allclose(np.asarray(x) + sum(parts), want, rtol=1e-4,
                               atol=1e-4)
    assert held == T * whole.moe.top_k          # every pair held once


def test_no_token_is_dropped_when_all_choose_the_same_experts():
    cfg = moe_config(held=(0, 2))
    p = layer_params(cfg)
    # positive inputs, a router that prefers experts 0 and 1 for each of them
    sign = jnp.where(jnp.arange(8) < 2, 1.0, -1.0)
    p["router"] = jnp.abs(p["router"]) * sign
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(2), (T, cfg.d_model)))
    out, counts = held_moe_ffn(cfg, p, x)
    whole = moe_config()
    pw = dict(layer_params(whole), router=p["router"])
    want = reference(whole, pw, x)
    np.testing.assert_allclose(out, want, rtol=1e-4, atol=1e-5)
    c = dict(zip(COUNTERS, np.asarray(counts).tolist()))
    assert c == {"moe.routed_pairs": 2 * T, "moe.held_pairs": 2 * T,
                 "moe.dropped": 0, "moe.held_experts_hit": 2}
    # the mesh path's capacity holds 32 rows an expert here: it drops
    capped = moe_ffn_local(cfg, p, x, 0, 2)
    assert not np.allclose(capped, want, rtol=1e-4, atol=1e-5)


def _serve(monkeypatch, arch, cfg=None):
    """``serve.run`` at smoke size (``cfg`` in place of the arch's), with
    the jitted programs it builds, by name."""
    jits = {}
    real = serve.jit_as

    def jit_as(name, fn, **jit_kw):
        jits[name] = real(name, fn, **jit_kw)
        return jits[name]
    monkeypatch.setattr(serve, "jit_as", jit_as)
    if cfg is not None:
        monkeypatch.setattr(serve, "get_smoke_config", lambda _: cfg)
    res = serve.run(serve.parse_args(
        ["--arch", arch, "--smoke", "--requests", "2", "--batch", "1",
         "--prompt-len", "16", "--gen", "8"]))
    return res, jits


def test_counters_reach_the_host_with_the_tokens(monkeypatch):
    cfg = moe_config(held=(0, 2), dtype="bfloat16")
    res, _ = _serve(monkeypatch, "qwen3-moe-235b-a22b", cfg)
    hist = list(range(1, 21))
    quiet = res.decode("s", hist, 5)
    with spans.record() as rec:
        toks = res.decode("s", hist, 5)
        res.decode("s", hist, 1)
    assert toks == quiet                   # recording changes no token
    c = rec.counters
    forwarded = c["serve.prefill_tokens"] + c["serve.decode_steps"]
    assert forwarded == 2 * 20 + 4
    layers, k = cfg.n_layers, cfg.moe.top_k
    assert c["moe.routed_pairs"] == forwarded * k * layers
    assert c["moe.dropped"] == 0
    assert 0 < c["moe.held_experts_hit"] <= c["moe.held_pairs"] \
        < c["moe.routed_pairs"]
    # one read of the tokens a request, counters with it
    assert sum(s[0] == "serve.sync" for s in rec.spans) == 2


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma3-1b"])
def test_dense_serving_programs_return_no_counters(monkeypatch, arch):
    res, jits = _serve(monkeypatch, arch)
    ids = jnp.zeros((1, 16), jnp.int32)
    tok, caches, counts = jax.eval_shape(jits["prefill"], res.params, ids)
    assert counts is None
    toks, counts = jits["decode_loop"].eval_shape(res.params, caches, tok,
                                                  jnp.int32(16), 8)
    assert counts is None and toks.shape == (8,)
    with spans.record() as rec:
        res.decode("s", list(range(16)), 8)
    assert not any(name.startswith("moe.") for name in rec.counters)


def test_moe_serving_programs_return_the_counters(monkeypatch):
    cfg = moe_config(held=(0, 2), dtype="bfloat16")
    res, jits = _serve(monkeypatch, "qwen3-moe-235b-a22b", cfg)
    ids = jnp.zeros((1, 16), jnp.int32)
    tok, caches, counts = jax.eval_shape(jits["prefill"], res.params, ids)
    assert counts.shape == (len(COUNTERS),) and counts.dtype == jnp.int32
    _, counts = jits["decode_loop"].eval_shape(res.params, caches, tok,
                                               jnp.int32(16), 8)
    assert counts.shape == (len(COUNTERS),)
