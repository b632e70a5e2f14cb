"""What decides ``correct``: the numbers compared, each beside its limit.

* ``logit_gap``: over a sample of finished sessions drawn from the seed
  (the longest among them), the widest gap by which a served token's
  reference logit lies below the reference's best at its position.  It
  covers prefill and every decode step, on the histories as served.
* ``replicas_differ``: replicas whose session snapshot differs from the
  first replica's at the end of the run.
* ``histories_differ``: sessions whose history on the first replica is not
  the prompt followed by the f+1-matched replies the client accepted.
* ``executions_missing``: how far the decode calls fall short of (or
  exceed) one per replica per answered request.
* ``unanswered``: requests that never got an f+1-matched reply.

Each limit comes from ``bench/limits/<cell>.json``.
"""

from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import numpy as np

from harness.spec import SpecError

Numbers = Dict[str, Tuple[float, float]]


def session_turns(requests) -> Dict[str, List[List[int]]]:
    """Served tokens of each session's answered turns, in turn order."""
    out: Dict[str, Dict[int, List[int]]] = {}
    for r in requests:
        if r.tokens is not None:
            out.setdefault(r.session.sid, {})[r.turn] = r.tokens
    return {sid: [t[k] for k in sorted(t)] for sid, t in out.items()}


def consensus_numbers(window, snapshots: Sequence, replicas: int
                      ) -> Dict[str, float]:
    turns = session_turns(window.requests)
    held = dict(snapshots[0])
    differ = 0
    for s in window.sessions:
        want = list(s.prompt) + [t for turn in turns.get(s.sid, [])
                                 for t in turn]
        if list(held.get(s.sid, ())) != want:
            differ += 1
    answered = sum(r.tokens is not None for r in window.requests)
    return {
        "replicas_differ": float(sum(snap != snapshots[0]
                                     for snap in snapshots[1:])),
        "histories_differ": float(differ),
        "executions_missing": float(abs(window.executions
                                        - replicas * answered)),
        "unanswered": float(len(window.requests) - answered),
    }


def gap_numbers(gaps: np.ndarray) -> Dict[str, float]:
    """The numbers read from the served tokens' gaps (NaN with none)."""
    if gaps.size == 0:
        return {"logit_gap": float("nan"), "logit_gap_mean": float("nan")}
    return {"logit_gap": float(gaps.max()),
            "logit_gap_mean": float(gaps.mean())}


def judge(values: Dict[str, float], limits: Dict[str, dict]
          ) -> Tuple[bool, Numbers]:
    """Each number beside its limit; correct when none exceeds its limit."""
    out: Numbers = {}
    for name, v in values.items():
        if name not in limits:
            raise SpecError(f"no limit for {name!r}")
        out[name] = (float(v), float(limits[name]["limit"]))
    return all(v <= lim for v, lim in out.values()), out
