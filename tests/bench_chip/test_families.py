"""Tests of the model families (``bench/families/<family>.py``), on the CPU.

The dense GQA family reproduces, bit for bit, the reference's gaps (the
program's and both controls') and the FLOP counts recorded before its code
moved into the family.  A second family needs only its file: a toy family
written to a temporary directory is found by name and drives the
reference and ``step_mfu`` with no dense code involved.  A configuration
that names no family, or a family with no file, is refused.
"""

from __future__ import annotations

import json
import os
import sys
import textwrap
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [os.path.join(ROOT, "bench"), os.path.join(ROOT, "src")]

from harness import runner, spec  # noqa: E402
from harness.reference import served_gaps  # noqa: E402

PINNED = spec.load_json(os.path.join(HERE, "data", "dense_gqa.pinned.json"))
CONFIGS = sorted(PINNED["configs"])


def _histories(case, vocab):
    rng = np.random.default_rng(case["seed"])
    hist = [rng.integers(0, vocab, case["prompt"] + case["served"]).tolist()
            for _ in range(case["sessions"])]
    return hist, [case["prompt"]] * case["sessions"]


def _config(name):
    bench = spec.load_benchmark()
    return spec.config_family(bench, name), spec.config_file(bench, name)


@pytest.mark.parametrize("case", [c["name"] for c in PINNED["cases"]])
@pytest.mark.parametrize("config", CONFIGS)
def test_dense_gqa_reproduces_the_recorded_gaps(config, case):
    family, cfg_file = _config(config)
    s = family.sizes(cfg_file, smoke=True)
    (c,) = [c for c in PINNED["cases"] if c["name"] == case]
    hist, start = _histories(c, s.vocab)
    gaps, ctl = served_gaps(family, s, hist, start, PINNED["quants"],
                            shape=tuple(c["shape"]))
    want = PINNED["configs"][config]["served_gaps"][case]
    got = {"program": gaps, **ctl}
    assert set(got) == set(want)
    for k, v in got.items():
        assert v.dtype == np.float32, k
        assert v.tolist() == want[k], k


@pytest.mark.parametrize("config", CONFIGS)
def test_dense_gqa_reproduces_the_recorded_flop_counts(config):
    family, cfg_file = _config(config)
    rows = PINNED["configs"][config]["request_flops"]
    for smoke in (True, False):
        s = family.sizes(cfg_file, smoke=smoke)
        got = [[p, h, n, family.request_flops(s, p, h, n)]
               for p, h, n, _ in rows["smoke" if smoke else "full"]]
        assert got == rows["smoke" if smoke else "full"]
    assert len(rows["full"]) > 50


# ---------------------------------------------------------------------------
# a second family needs only its file
# ---------------------------------------------------------------------------
TOY = '''
"""A toy family: one-hot embedding, identity layers, one head matrix
that puts token (t + 1) % vocab first after token t."""
from dataclasses import dataclass

import jax.numpy as jnp


@dataclass(frozen=True)
class Sizes:
    layers: int
    d_model: int
    vocab: int
    norm_eps: float
    dtype: str


def program_config(name, cfg_file, smoke=False):
    raise NotImplementedError("the toy family serves no program")


def sizes(cfg_file, smoke=False):
    c = cfg_file["config"]
    return Sizes(layers=c["layers"], d_model=c["vocab"], vocab=c["vocab"],
                 norm_eps=1e-6, dtype="float32")


def request_flops(s, processed, history, n):
    return 7 * (history + n - 1 - processed) + 3 * n


class Weights:
    def __init__(self, s):
        self.s = s

    def embed(self):
        return jnp.eye(self.s.vocab, dtype=jnp.float32)

    def head(self):
        return jnp.roll(jnp.eye(self.s.vocab, dtype=jnp.float32), 1, axis=1)

    def layer(self, i):
        return {}


def layer(x, w, s, quant):
    return x
'''


def test_a_family_in_its_own_file_drives_the_reference_and_step_mfu(
        tmp_path, monkeypatch):
    step_mfu = spec.metric_reader("step_mfu")
    (tmp_path / "families").mkdir()
    (tmp_path / "families" / "toy.py").write_text(textwrap.dedent(TOY))
    monkeypatch.setattr(spec, "BENCH", str(tmp_path))
    toy = spec.family("toy")
    # only the toy's file is there to be found
    with pytest.raises(spec.SpecError):
        spec.family("dense_gqa")
    s = toy.sizes({"config": {"layers": 3, "vocab": 16}})
    follow = [5, 6, 7, 8, 9]              # each token the head's first
    other = [2, 9, 4, 4, 11, 0]           # none of them after the first
    gaps, ctl = served_gaps(toy, s, [follow, other], [2, 1], ["int8"])
    # logits after token t: sqrt(16) / sqrt(1 + eps) on (t + 1) % 16, else 0
    top = 4.0 / np.sqrt(np.float32(1 + 1e-6))
    want = [0.0] * 3 + [top] * 5
    np.testing.assert_allclose(gaps, want, rtol=1e-6)
    assert ctl["int8"].tolist() == [0.0] * 8   # the control's first is best

    req = lambda p, h, n: SimpleNamespace(  # noqa: E731
        processed=p, history=h, tokens=[0] * n)
    w = SimpleNamespace(done=lambda: [req(0, 8, 2), req(9, 10, 3)],
                        seconds=2.0, profiler_s=0.0)
    run = runner.Run(sizes=s, family=toy, replicas=3, window=w, setup_s=1.0,
                     peaks={"bf16_flops_per_s": 1e9})
    flops = 7 * 9 + 3 * 2 + 7 * 3 + 3 * 3
    assert step_mfu.read(run) == pytest.approx(100 * 3 * flops / 2e9)


def _bench_with(tmp_path, program):
    path = tmp_path / "cfg.json"
    path.write_text(json.dumps({"name": "cfg", "program": program}))
    return {"configs": [{"name": "cfg", "file": str(path)}]}, str(path)


def test_a_configuration_without_a_family_is_refused(tmp_path):
    bench, path = _bench_with(tmp_path, {"base": "qwen3-8b"})
    with pytest.raises(spec.SpecError, match="program.family") as e:
        spec.config_family(bench, "cfg")
    assert path in str(e.value)


def test_a_family_with_no_file_is_refused(tmp_path):
    bench, _ = _bench_with(tmp_path, {"family": "no_such_family"})
    want = os.path.join(spec.BENCH, "families", "no_such_family.py")
    with pytest.raises(spec.SpecError) as e:
        spec.config_family(bench, "cfg")
    assert want in str(e.value)
