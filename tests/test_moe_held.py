"""The expert layer that holds a share of the experts
(``repro.models.moe.held_moe_ffn``), on the CPU at the smoke size of
qwen3-moe-235b-a22b (8 experts, top-2).

The shares of a layer add up to the whole layer, as a plain float32
reference computes it; the layer drops no token when every token is
routed to the same experts; it reads only the held experts that some
token is routed to, and computes what every held expert on every token
computes; the decode loop hands no layer's share of the experts to a
conditional or a loop, where XLA would copy it; its counters reach the
host through the token server's one read of the tokens; and the dense
models' serving programs return no counters at all.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hlo_operands import control_flow_operands
from repro import spans
from repro.configs import get_smoke_config
from repro.launch import serve
from repro.models import transformer
from repro.models.common import (LayerSpec, init_layer_params, init_params,
                                 rms_norm)
from repro.models.moe import (COUNTERS, EXPERT_WEIGHTS, held_moe_ffn,
                              moe_ffn_local, route)
from repro.models.transformer import _ffn_part, init_caches, prefill

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


def all_experts(cfg, p, x):
    """The held part as every held expert run on every token computes it,
    the layer's formula before it skipped the experts that no token is
    routed to.  Returns it with the counts, every held expert read."""
    m = cfg.moe
    e0, n = m.held_range
    w = {k: p[k] if "layer" not in p else p[k][p["layer"]]
         for k in EXPERT_WEIGHTS}
    top_w, top_e = route(cfg, p["router"], x)
    hit = top_e[..., None] == e0 + jnp.arange(n)
    gates = jnp.sum(jnp.where(hit, top_w[..., None], 0.0), axis=1)
    rows = jnp.sum(hit, axis=(0, 1))
    h = jnp.einsum("td,edf->etf", x, w["w_gate"])
    u = jnp.einsum("td,edf->etf", x, w["w_up"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u, w["w_down"])
    out = jnp.einsum("te,etd->td", gates.astype(x.dtype), y)
    counts = jnp.stack([jnp.int32(x.shape[0] * m.top_k), jnp.sum(rows),
                        jnp.sum(jnp.maximum(rows - x.shape[0], 0)),
                        jnp.sum(rows > 0), jnp.int32(n)]).astype(jnp.int32)
    return out, counts


def bf16_ulp(v):
    """One bfloat16 ulp at the magnitude of each of ``v``'s rows."""
    top = np.maximum(np.abs(v).max(axis=-1, keepdims=True), 2.0 ** -126)
    return 2.0 ** (np.floor(np.log2(top)) - 7)


@pytest.mark.parametrize("held, routed, tokens", [
    ((0, 4), (5, 6), 1),
    ((0, 4), (1, 6), 1),
    ((0, 4), (0, 3), 1),
    ((2, 2), (2, 3), 1),
    ((0, 4), (1, 6), T),
    ((0, 4), None, T),
    (None, None, T),
], ids=["T1-no-held", "T1-one-held", "T1-two-held", "T1-all-held",
        "T64-one-held", "T64-routed", "T64-all-experts-held"])
def test_only_the_routed_held_experts_are_read(held, routed, tokens):
    """A token routed to ``routed`` (the router's own choice where None):
    the layer reads just the held experts hit, and its output is the
    all-experts formula's, bit for bit where no held expert is hit, else
    within one bfloat16 ulp of each token's largest value: the two
    programs round at different points."""
    cfg = moe_config(held=held, dtype="bfloat16")
    p = layer_params(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, cfg.d_model))
    if routed is not None:
        # positive inputs, a router that prefers the experts of ``routed``
        chosen = jnp.isin(jnp.arange(cfg.moe.n_experts), jnp.array(routed))
        p["router"] = jnp.abs(p["router"]) * jnp.where(chosen, 1.0, -1.0)
        x = jnp.abs(x)
    x = x.astype(cfg.jdtype())
    got, counts = jax.jit(lambda p, x: held_moe_ffn(cfg, p, x))(p, x)
    want, formula = jax.jit(lambda p, x: all_experts(cfg, p, x))(p, x)
    c = dict(zip(COUNTERS, np.asarray(counts).tolist()))
    f = dict(zip(COUNTERS, np.asarray(formula).tolist()))
    e0, n = cfg.moe.held_range
    if routed is not None:
        assert c["moe.held_experts_hit"] == sum(e0 <= e < e0 + n
                                                for e in routed)
    assert c["moe.held_experts_read"] == c["moe.held_experts_hit"]
    assert {k: c[k] for k in COUNTERS[:4]} == {k: f[k] for k in COUNTERS[:4]}
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    if c["moe.held_experts_hit"] == 0:
        np.testing.assert_array_equal(got, 0.0)
        np.testing.assert_array_equal(want, 0.0)
    else:
        assert np.all(np.abs(got - want) <= bf16_ulp(want))


@pytest.mark.parametrize("held", [(0, 4), None], ids=["half", "all"])
def test_greedy_tokens_are_the_all_experts_loops(monkeypatch, held):
    """Prefill and 11 decode steps of the smoke model in bfloat16 serve the
    tokens that the same loop on the all-experts formula serves; summed
    over the steps, the counters agree, and the steps read only the held
    experts hit, fewer than every held expert of every layer."""
    cfg = moe_config(held=held, dtype="bfloat16")
    params = init_params(cfg, jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(4), (1, 16), 0, cfg.vocab)
    steps = 11

    def serve_once(params, prompt):
        logits, caches, first = prefill(cfg, params, prompt, max_seq=32,
                                        counts=True)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        toks, counts = serve.greedy_tokens(cfg, params, caches, tok,
                                           jnp.int32(16), steps + 1)
        return toks, first, counts

    got = jax.jit(serve_once)(params, prompt)
    monkeypatch.setattr(transformer, "held_moe_ffn", all_experts)
    # a new function: jit would reuse the first trace of ``serve_once``
    want = jax.jit(lambda *a: serve_once(*a))(params, prompt)
    np.testing.assert_array_equal(got[0], want[0])
    n, layers = cfg.moe.held_range[1], cfg.n_layers
    for run, (mine, formula) in zip(("prefill", "decode"),
                                    zip(got[1:], want[1:])):
        c = dict(zip(COUNTERS, np.asarray(mine).tolist()))
        f = dict(zip(COUNTERS, np.asarray(formula).tolist()))
        assert {k: c[k] for k in COUNTERS[:4]} == \
            {k: f[k] for k in COUNTERS[:4]}, run
        assert c["moe.held_experts_read"] == c["moe.held_experts_hit"], run
    passes = steps * layers
    assert c["moe.routed_pairs"] == passes * cfg.moe.top_k
    assert f["moe.held_experts_read"] == passes * n
    assert 0 < c["moe.held_experts_read"] < passes * n


def test_decode_loop_hands_no_layer_of_experts_to_control_flow():
    """The lowered decode loop of the smoke MoE model: no conditional,
    loop or custom call takes a layer's held-expert matrices (n, D, F) or
    (n, F, D), which XLA would copy out of the layer stack every layer and
    step; the conditionals take the stacks whole, each slicing out one
    expert's matrices where its products use them."""
    cfg = moe_config(dtype="bfloat16")
    n, D, F, L = (cfg.moe.held_range[1], cfg.d_model, cfg.moe.d_expert,
                  cfg.n_layers)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_caches(cfg, 1, 32))
    one = jax.ShapeDtypeStruct((1,), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    hlo = jax.jit(lambda p, c, t, i: serve.greedy_tokens(cfg, p, c, t, i, 8)
                  ).lower(params, caches, one, pos).compile().as_text()
    operands = control_flow_operands(hlo)
    layer_share = {(n, D, F), (n, F, D)}
    copied = [o for o in operands
              if tuple(d for d in o[2] if d != 1) in layer_share]
    assert not copied, copied[:3]
    whole = {(L, n, D, F), (L, n, F, D)}
    assert sum(op.startswith("conditional") and a in whole
               for op, _, a in operands) >= n


@pytest.mark.parametrize("arch", ["qwen3-8b", "gemma3-1b"])
def test_dense_decode_loops_take_no_conditional(arch):
    """A dense layer's decode takes no branch: only the layer scan's and
    the decode loop's while loops hold control flow."""
    cfg = get_smoke_config(arch)
    params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: init_caches(cfg, 1, 32))
    hlo = jax.jit(lambda p, c, t, i: serve.greedy_tokens(cfg, p, c, t, i, 8)
                  ).lower(params, caches, jax.ShapeDtypeStruct((1,), jnp.int32),
                          jax.ShapeDtypeStruct((), jnp.int32)
                          ).compile().as_text()
    assert {op.split()[0] for op, _, _ in control_flow_operands(hlo)} == \
        {"while"}


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
                 "moe.dropped": 0, "moe.held_experts_hit": 2,
                 "moe.held_experts_read": 2}
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
