"""Tests of the sparse-expert family (``bench/families/qwen3_moe.py``) and
of the two cells added with it, on the CPU.

The reference's weights are the served ones, bit for bit, for the share of
experts the program holds; its layer computes what the program's layer
computes (both in float32), and the served model's logits, prefill and
then decode through the cache, stay within bfloat16's reach of the
reference's full forward.  The FLOP count matches a hand count, and a
configuration whose widths differ from the repo's model is refused.  Both
new cells run correctly at smoke size, and the controls recorded on the
chip read not correct by the cells' limits.
"""

from __future__ import annotations

import copy
import dataclasses
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]

from harness import check, reference, runner, spec  # noqa: E402

NAME = "qwen3-235b-a22b-e8-d10"
CELLS = ["qwen3-235b-a22b-e8-d10.chat", "chatglm3-6b-d14.score"]


@pytest.fixture(scope="module")
def moe():
    bench = spec.load_benchmark()
    return spec.config_family(bench, NAME), spec.config_file(bench, NAME)


def _program(family, cfg_file, dtype=None):
    """The smoke-sized program config, its Sizes and its served params."""
    import jax
    from repro.models.common import init_params
    cfg = family.program_config(NAME, cfg_file, smoke=True)
    s = family.sizes(cfg_file, smoke=True)
    if dtype is not None:
        cfg = dataclasses.replace(cfg, dtype=dtype)
        s = dataclasses.replace(s, dtype=dtype,
                                attn=dataclasses.replace(s.attn, dtype=dtype))
    return cfg, s, init_params(cfg, jax.random.PRNGKey(reference.WEIGHTS_KEY))


def test_reference_weights_are_the_served_share(moe):
    family, cfg_file = moe
    cfg, s, params = _program(family, cfg_file)
    assert cfg.moe.held == (0, 2) and (s.first_held, s.held) == (0, 2)
    w = family.Weights(s)
    pairs = [(params["embed"], w.embed()), (params["lm_head"], w.head())]
    layers = params["groups"][0][0]
    for i in range(s.layers):
        mine = w.layer(i)
        for k in ("wq", "wk", "wv", "wo", "router"):
            pairs.append((layers[k][i], mine[k]))
        for k in ("gate", "up", "down"):
            pairs.append((layers[f"w_{k}"][i], mine[f"expert_{k}"]))
    for served, drawn in pairs:
        assert served.dtype == drawn.dtype and served.shape == drawn.shape
        np.testing.assert_array_equal(np.asarray(served), np.asarray(drawn))


def test_reference_layer_computes_the_program_layer(moe):
    import jax
    import jax.numpy as jnp
    from repro.models.common import LayerSpec
    from repro.models.transformer import apply_layer_prefill
    family, cfg_file = moe
    cfg, s, params = _program(family, cfg_file, dtype="float32")
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, s.d_model))
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    for i in range(s.layers):
        p = jax.tree.map(lambda a: a[i], params["groups"][0][0])
        with jax.default_matmul_precision("highest"):
            got, _, counts = apply_layer_prefill(cfg, LayerSpec("attn"), p, x,
                                                 pos, 24, None)
            want = family.layer(x, family.Weights(s).layer(i), s, None)
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
        assert int(counts[0]) == 2 * 24 * s.top_k


def _reference_logits(family, s, toks, rows):
    """The reference's full forward of ``toks`` (1, S): logits at ``rows``."""
    import jax
    import jax.numpy as jnp
    w = family.Weights(s)
    with jax.default_matmul_precision("highest"):
        x = reference._embed(w.embed(), jnp.asarray(toks), s)
        for i in range(s.layers):
            x = family.layer(x, w.layer(i), s, None)
        xn = reference._final(x, jnp.zeros(len(rows), jnp.int32),
                              jnp.asarray(rows), s)
        return np.asarray(reference._logits(xn, [w.head()], None))


def test_served_logits_match_the_reference_full_forward(moe):
    """Prefill of 16 tokens, then 8 decode steps through the cache, in the
    served bfloat16; the reference in float32.  0.08 is about 10 bfloat16
    roundings (2^-8 each) of logits of unit size through 2 layers and the
    head; the widest gap the test reads is below a third of it."""
    import jax
    import jax.numpy as jnp
    from repro.models.transformer import decode_step, prefill
    family, cfg_file = moe
    cfg, s, params = _program(family, cfg_file)
    toks = np.random.default_rng(7).integers(0, s.vocab, (1, 24))
    logits, caches = prefill(cfg, params, jnp.asarray(toks[:, :16]),
                             max_seq=32)
    got = [logits[0]]
    for t in range(16, 23):
        logits, caches = decode_step(cfg, params, caches,
                                     jnp.asarray(toks[:, t]), jnp.int32(t))
        got.append(logits[0])
    got = np.asarray(jnp.stack(got), np.float32)
    want = _reference_logits(family, s, toks, list(range(15, 23)))
    assert np.abs(want).max() > 1.0
    np.testing.assert_allclose(got, want, atol=0.08, rtol=0)


def test_flop_count_matches_hand_count(moe):
    family, cfg_file = moe
    s = family.sizes(cfg_file)
    assert (s.layers, s.d_model, s.experts, s.top_k, s.d_expert, s.held) == \
        (10, 4096, 128, 8, 1536, 8)
    D, q, kv, V = 4096, 64 * 128, 4 * 128, 151936
    attn = D * q + 2 * D * kv + q * D                # no dense FFN
    # per token and layer: router 2 D E; half a held pair of 6 D F
    moe_part = 2 * D * 128 + 3 * D * 1536

    def by_hand(processed, history, n):
        total = 0
        for p in range(processed, history + n - 1):
            total += 10 * (2 * attn + 4 * 64 * 128 * (p + 1) + moe_part)
        return total + n * 2 * D * V

    for args in [(0, 128, 32), (159, 160, 32), (0, 448, 1), (3, 4, 1)]:
        assert family.request_flops(s, *args) == by_hand(*args)
    # the expert layer's work, from its counters
    assert family.expert_flops(s, 80, 5) == \
        10 * 2 * D * 128 + 5 * 6 * D * 1536
    assert family.expert_bytes(s, 5, 10) == \
        5 * 3 * D * 1536 * 2 + 10 * D * 128 * 4


@pytest.mark.parametrize("where, key, value", [
    ("config", "hidden_size", 2048),
    ("config", "num_attention_heads", 32),
    ("config", "head_dim", 64),
    ("config", "moe_intermediate_size", 768),
    ("config", "num_experts_per_tok", 4),
    ("published", "num_experts", 64),
    ("config", "num_experts", 129),
    ("config", "norm_topk_prob", False),
])
def test_a_width_that_differs_from_the_repo_model_is_refused(
        moe, where, key, value):
    family, cfg_file = moe
    family.program_config(NAME, cfg_file).validate()
    bad = copy.deepcopy(cfg_file)
    bad[where][key] = value
    with pytest.raises(spec.SpecError):
        family.program_config(NAME, bad)


@pytest.fixture
def cpu_run(monkeypatch):
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off in tests")

    def go(workload, seed=2**31 + 7, seconds=1.5):
        lines = []
        result, numbers = runner.execute(
            workload, seed, seconds, False, time.perf_counter(),
            platform="cpu", smoke=True, log=lines.append)
        return result, numbers, lines
    return go


@pytest.mark.parametrize("workload", CELLS)
def test_cpu_rehearsal_runs_the_new_cell_correctly(cpu_run, workload):
    bench = spec.load_benchmark()
    result, numbers, lines = cpu_run(workload)
    assert result["correct"] is True, numbers
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in spec.metrics_for(bench, workload, "end_to_end")}
    assert any(l.endswith("compilations in the window: 0") for l in lines)


def test_fault_held_experts_dropped(cpu_run, monkeypatch):
    """The program leaves out what its held experts add: the check sees the
    expert layer, and the run reads not correct."""
    from repro.models import transformer
    real = transformer.held_moe_ffn

    def dropped(cfg, p, x):
        out, counts = real(cfg, p, x)
        return out * 0, counts
    monkeypatch.setattr(transformer, "held_moe_ffn", dropped)
    result, numbers, _ = cpu_run(CELLS[0])
    assert result["correct"] is False
    assert numbers["logit_gap_mean"][0] > numbers["logit_gap_mean"][1]
    assert numbers["replicas_differ"][0] == 0   # consensus still held


@pytest.mark.parametrize("workload", CELLS)
def test_recorded_controls_are_not_correct_by_the_new_cells_limits(workload):
    """Every served token's gap, recorded on a TPU v5e at the cell's size by
    ``bench/calibrate.py`` (the program's, and on the same histories the
    int8 and fp8 controls'), judged by ``bench/limits/<cell>.json``: the
    program is correct on every seed, each control on none.  In the MoE
    cell, so is the program that drops what its held experts add."""
    rec = spec.load_json(os.path.join(HERE, "data", f"{workload}.gaps.json"))
    limits = spec.limits_file(workload)
    exact = {k: 0.0 for k in ("replicas_differ", "histories_differ",
                              "executions_missing", "unanswered")}

    def correct(gaps):
        values = dict(exact, **check.gap_numbers(np.asarray(gaps)))
        return check.judge(values, limits)[0]
    assert len(rec) >= 3
    for row in rec:
        for name, gaps in row.items():
            if name != "seed":
                assert correct(gaps) == (name == "program"), \
                    (row["seed"], name)
