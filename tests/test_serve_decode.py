"""The token server's decode (``repro.launch.serve``): the prefill picks the
first token and one loop on the device the other n - 1.

At the smoke sizes of gemma3-1b (sliding-window caches) and qwen3-8b, the
decode that ``serve.run`` returns gives exactly the tokens of the
host-driven greedy loop it replaced (one jitted ``decode_step`` and one
host read per token); its counters say how many steps ran on the device
and in how many loops, and one token runs no decode program at all.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans
from repro.launch import serve
from repro.models.transformer import decode_step, prefill

PROMPT, GEN, REQUESTS = 16, 8, 2
#: the smoke models' layer weights are scaled up by this: at their drawn
#: size the tied embedding picks the input token again, every step, which
#: a wrong position or a wrong token fed back would not change
LAYER_SCALE = 4.0


def scaled_init(real):
    def init_params(cfg, key):
        p = real(cfg, key)
        p["groups"] = jax.tree.map(lambda a: a * LAYER_SCALE, p["groups"])
        return p
    return init_params


class Served:
    """``serve.run`` at the smoke size, with the numbers of tokens its
    decode loop was traced for (a trace is a compilation)."""

    def __init__(self, arch: str):
        self.traced = []
        real = serve.greedy_tokens

        def greedy_tokens(cfg, params, caches, tok, pos, n):
            self.traced.append(n)
            return real(cfg, params, caches, tok, pos, n)

        self.mp = pytest.MonkeyPatch()
        self.mp.setattr(serve, "greedy_tokens", greedy_tokens)
        self.mp.setattr(serve, "init_params", scaled_init(serve.init_params))
        self.res = serve.run(serve.parse_args(
            ["--arch", arch, "--smoke", "--requests", str(REQUESTS),
             "--batch", "1", "--prompt-len", str(PROMPT), "--gen", str(GEN)]))
        self.traced_by_run = list(self.traced)
        self.max_seq = PROMPT + GEN * REQUESTS + 8
        cfg = self.res.cfg
        self.pf = jax.jit(lambda p, i: prefill(cfg, p, i,
                                               max_seq=self.max_seq))
        self.ds = jax.jit(lambda p, c, t, q: decode_step(cfg, p, c, t, q))

    def host_greedy(self, hist, n):
        """The per-token loop: the host reads every token and dispatches
        the next step (and one past the last)."""
        params = self.res.params
        logits, caches = self.pf(params, jnp.asarray([hist], jnp.int32))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        out = []
        for i in range(n):
            out.append(int(tok[0]))
            logits, caches = self.ds(params, caches, tok,
                                     jnp.int32(len(hist) + i))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return out


@pytest.fixture(scope="module", params=["gemma3-1b", "qwen3-8b"])
def served(request):
    s = Served(request.param)
    yield s
    s.mp.undo()


def test_run_compiles_the_loop_for_its_own_tokens(served):
    assert served.traced_by_run == [GEN]
    assert all(len(t) == GEN for t in served.res.tokens)
    assert served.res.replicas_identical


@pytest.mark.parametrize("n", [1, 2, 7])
@pytest.mark.parametrize("length", [5, 13])
def test_loop_gives_the_host_loop_tokens(served, n, length):
    hist = np.random.default_rng(length).integers(
        0, served.res.cfg.vocab, size=length).tolist()
    before = list(served.traced)
    with spans.record() as rec:
        got = served.res.decode("s", hist, n)
    assert got == served.host_greedy(hist, n)
    assert n < 7 or len(set(got)) > 2      # the tokens depend on the steps
    assert all(type(t) is int for t in got)
    names = [s[0] for s in rec.spans]
    c = rec.counters
    assert c["serve.prefills"] == 1 and c["serve.prefill_tokens"] == length
    if n == 1:
        # no decode program runs, and none is compiled
        assert names == ["serve.prefill", "serve.sync"]
        assert "serve.decode_steps" not in c and "serve.decode_loops" not in c
        assert 1 not in served.traced
    else:
        assert names == ["serve.prefill", "serve.step", "serve.sync"]
        assert c["serve.decode_steps"] == n - 1
        assert c["serve.decode_loops"] == 1
        # one compilation per number of tokens, kept for later requests
        assert served.traced.count(n) == 1
        assert served.traced[:len(before)] == before
