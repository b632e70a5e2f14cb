"""Serving launcher: a uBFT-replicated token server (deliverable b's
end-to-end driver — the paper's kind is SMR/serving).

  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-1b --smoke \\
      --requests 20 --batch 4

Three replicas hold the same model; client requests are ordered through
uBFT consensus; the client accepts f+1 matching token streams, so a
Byzantine replica cannot forge a generation.  Prints per-request latency:
replication overhead is microseconds on top of model time.  The SMR
latency is the simulator's virtual time; wall and compile seconds are the
host clock around the real model work.
"""

from __future__ import annotations

import argparse
import time
from dataclasses import dataclass
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import spans
from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.common import ModelConfig, init_params
from repro.models.moe import COUNTERS, add_counts
from repro.models.transformer import decode_step, prefill
from repro.runtime.server import ReplicatedServer


@dataclass
class ServeResult:
    cfg: ModelConfig
    params: Any
    #: ``decode(session, hist, n) -> tokens``: the replicas' decode function
    decode: Callable[[str, List[int], int], List[int]]
    #: (session, prompt, n) per request, in submission order
    requests: List[Tuple[str, List[int], int]]
    #: reply tokens per request (None: shed by admission control)
    tokens: List[Optional[List[int]]]
    #: simulated µs from submission to the f+1-matched reply, per request
    smr_latency_us: List[float]
    #: replies the clients accepted on f+1 matching responses
    matched: int
    #: the 2f+1 replicas hold identical session state
    replicas_identical: bool
    #: host seconds for the request loop (compiles included)
    wall_s: float
    #: host seconds spent compiling prefill/decode programs
    compile_s: float


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--batch", type=int, default=4, help="client sessions")
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=8)
    return ap.parse_args(argv)


def jit_as(name: str, fn: Callable, **jit_kw) -> Callable:
    """``jax.jit(fn, **jit_kw)`` under ``name``: its HLO module, and the
    device trace's ``XLA Modules`` line, read ``jit_<name>``."""
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn, **jit_kw)


def greedy_tokens(cfg: ModelConfig, params, caches, tok: jax.Array,
                  pos: jax.Array, n: int) -> Tuple[jax.Array, Any]:
    """The ``n`` greedy tokens that start with ``tok`` (int32[1], the
    prefill's choice), decoded on the device: ``n - 1`` steps of
    ``decode_step``, the k-th fed the token before it at position
    ``pos + k - 1``.  Returns int32[n] and the MoE counters summed over the
    steps (None for a model without held experts)."""
    out = jnp.zeros((n,), jnp.int32).at[0].set(tok[0])
    counts = None if cfg.moe is None else jnp.zeros(len(COUNTERS), jnp.int32)

    def body(i, carry):
        caches, tok, out, counts = carry
        logits, caches, k = decode_step(cfg, params, caches, tok,
                                        pos + i - 1, counts=True)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        return caches, tok, out.at[i].set(tok[0]), add_counts(counts, k)

    return jax.lax.fori_loop(1, n, body, (caches, tok, out, counts))[2:]


def run(args: argparse.Namespace) -> ServeResult:
    """Serve ``args.requests`` greedy generations through 2f+1 replicas.

    Sessions take turns; each session's first request carries a random
    prompt (seeded), later ones continue the session with no prompt."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    max_seq = args.prompt_len + args.gen * args.requests + 8

    def first_token(p, ids):
        counts = None
        if cfg.moe is None:
            logits, caches = prefill(cfg, p, ids, max_seq=max_seq)
        else:
            logits, caches, counts = prefill(cfg, p, ids, max_seq=max_seq,
                                             counts=True)
        return jnp.argmax(logits, -1).astype(jnp.int32), caches, counts

    pf = jit_as("prefill", first_token)
    dl = jit_as("decode_loop",
                lambda p, c, t, pos, n: greedy_tokens(cfg, p, c, t, pos, n),
                static_argnums=4)
    # compiled programs: prefill once per history length (ROADMAP S2), the
    # decode loop once per number of tokens
    execs: dict = {}
    compile_s = 0.0

    def compiled(key, jitted, *example):
        nonlocal compile_s
        exe = execs.get(key)
        if exe is None:
            t = time.perf_counter()
            exe = execs[key] = jitted.lower(*example).compile()
            compile_s += time.perf_counter() - t
        return exe

    def decode_fn(session: str, hist, n: int):
        """Deterministic greedy decode of n tokens after `hist`: the prefill
        picks the first, one loop on the device the other n - 1, and the
        host reads them once (with a MoE model's counters, while spans are
        recorded)."""
        with spans.span("serve.prefill", tokens=len(hist)):
            ids = jnp.asarray([hist], jnp.int32)
            toks, caches, counts = compiled(("prefill", len(hist)), pf,
                                            params, ids)(params, ids)
        spans.count("serve.prefills")
        spans.count("serve.prefill_tokens", len(hist))
        if n > 1:
            with spans.span("serve.step"):
                pos = jnp.int32(len(hist))
                toks, more = compiled(("decode_loop", n), dl, params, caches,
                                      toks, pos, n)(params, caches, toks, pos)
            counts = (counts, more)
            spans.count("serve.decode_loops")
            spans.count("serve.decode_steps", n - 1)
        with spans.span("serve.sync"):     # the host waits for the tokens
            if cfg.moe is not None and spans.recording():
                toks, counts = jax.device_get((toks, counts))
                for name, k in zip(COUNTERS,
                                   np.sum(jax.tree.leaves(counts), axis=0)):
                    spans.count(name, int(k))
            # [:n]: a request may ask for no token at all
            return np.asarray(toks)[:n].tolist()

    server = ReplicatedServer.build(decode_fn)
    clients = [server.cluster.new_client() for _ in range(args.batch)]
    rng = np.random.default_rng(0)
    requests, tokens, lats = [], [], []
    t0 = time.perf_counter()
    for r in range(args.requests):
        cl = clients[r % len(clients)]
        sid = f"s{r % len(clients)}"
        prompt = rng.integers(0, cfg.vocab, size=args.prompt_len).tolist() \
            if r < len(clients) else []
        toks, lat = server.generate(cl, sid, prompt, args.gen)
        requests.append((sid, prompt, args.gen))
        tokens.append(toks)
        lats.append(lat)
    wall_s = time.perf_counter() - t0
    snaps = [r.app.snapshot() for r in server.cluster.replicas]
    return ServeResult(
        cfg=cfg, params=params, decode=decode_fn, requests=requests,
        tokens=tokens, smr_latency_us=lats,
        matched=sum(len(c.latencies) for c in clients),
        replicas_identical=all(s == snaps[0] for s in snaps),
        wall_s=wall_s, compile_s=compile_s)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    res = run(args)
    for r, ((sid, _, _), toks, lat) in enumerate(
            zip(res.requests, res.tokens, res.smr_latency_us)):
        print(f"[req {r}] session={sid} tokens={toks} "
              f"smr_latency={lat:.1f}us")
    lats = sorted(res.smr_latency_us)
    print(f"\n{args.requests} requests, {args.batch} sessions | "
          f"SMR-ordering latency p50={lats[len(lats)//2]:.1f}us "
          f"p90={lats[int(len(lats)*0.9)]:.1f}us | wall={res.wall_s:.1f}s "
          f"compile={res.compile_s:.1f}s")
    # all replicas hold identical session state (BFT guarantee)
    assert res.replicas_identical
    print("replica state identical across 2f+1 replicas: OK")


if __name__ == "__main__":
    main()
