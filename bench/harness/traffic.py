"""The one traffic generator: sessions of turns, from a mix's parameters.

A mix (``bench/traffic/<name>.json``) is a closed loop of ``clients``
callers.  Each caller runs one session at a time: the first turn carries a
``prompt_tokens``-token prompt, the later ones continue the session with no
new prompt (the token server's own session model), and every turn asks for
``output_tokens`` tokens.  A finished session is replaced by a new one.
Prompt ids are drawn from ``--seed``; every seed gives the same lengths.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List

import numpy as np

from harness.spec import SpecError, traffic_file


@dataclass(frozen=True)
class Mix:
    name: str
    clients: int
    turns: int
    prompt_tokens: int
    output_tokens: int
    #: sessions whose served tokens the check compares with the reference
    check_sessions: int

    @classmethod
    def from_dict(cls, name: str, d: Dict[str, Any]) -> "Mix":
        if d.get("loop") != "closed":
            raise SpecError(f"traffic {name}: only closed loops are generated")
        return cls(name=name, clients=int(d["clients"]), turns=int(d["turns"]),
                   prompt_tokens=int(d["prompt_tokens"]),
                   output_tokens=int(d["output_tokens"]),
                   check_sessions=int(d["check_sessions"]))


def load(name: str) -> Mix:
    return Mix.from_dict(name, traffic_file(name))


class Sessions:
    """Hands out sessions in a fixed order; the prompts come from the seed."""

    def __init__(self, mix: Mix, seed: int, vocab: int):
        self.mix = mix
        self.vocab = vocab
        self._rng = np.random.default_rng(seed)
        self._made = 0

    def new(self) -> "Session":
        prompt = self._rng.integers(0, self.vocab,
                                    size=self.mix.prompt_tokens).tolist()
        sid = f"s{self._made}"
        self._made += 1
        return Session(sid=sid, prompt=prompt, turns=self.mix.turns,
                       n=self.mix.output_tokens)


@dataclass
class Session:
    sid: str
    prompt: List[int]
    turns: int
    n: int
    done_turns: int = 0

    def next_prompt(self) -> List[int]:
        """The prompt the next turn carries (the first turn's alone)."""
        return self.prompt if self.done_turns == 0 else []


def check_sample(sessions: List[Session], k: int, seed: int) -> List[Session]:
    """``k`` sessions drawn from the seed, the one with the most served
    turns (the longest) always among them."""
    if not sessions:
        return []
    longest = max(sessions, key=lambda s: s.done_turns)
    rest = [s for s in sessions if s is not longest]
    rng = np.random.default_rng([seed, 1])
    pick = rng.permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[i] for i in sorted(pick)]
