"""The program's own spans and counters (``repro.spans``), read for the
benchmark.

Two readings of the same spans:

* the in-memory record, on the host clock of the window: each replica's
  execution of a request (``replica.execute``) split into the host's work
  and its wait for the device (the ``serve.sync`` spans inside it), and
  each consensus checkpoint (``consensus.checkpoint``) with the session
  table it snapshots (``app.snapshot``, whose ``ids`` is the table's size
  in token ids) and its fingerprint (the rest of its time);
* the profiler trace, on the device's clock: idle gaps of the chip put
  down to the innermost of the program's and the harness's host spans
  open at their middle, and each TPU plane's ``XLA Modules`` line, which
  names the jitted program (``jit_prefill``, ``jit_decode_step``) that
  each device execution belongs to.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

from harness import xtrace

#: host spans, innermost first where they nest: the program's inside the
#: harness's ``replica_exec``, ``harness`` and ``consensus``
HOST_SPANS = ("serve.sync", "serve.step", "serve.prefill", "replica_exec",
              "app.apply", "replica.execute", "app.snapshot",
              "consensus.checkpoint", "harness", "consensus")
MODULES_LINE = "XLA Modules"

#: (name, start_ns, end_ns, parent index or -1, ids), as ``repro.spans``
Span = Tuple[str, int, int, int, dict]


def clip(spans: Sequence[Optional[Span]], t0: float, t1: float
         ) -> List[Span]:
    """The finished spans that start in ``[t0, t1)`` (host seconds), in
    order, each parent index pointing into the list returned (-1 where the
    parent starts before ``t0``)."""
    lo, hi = t0 * 1e9, t1 * 1e9
    keep: Dict[int, int] = {}
    out: List[Span] = []
    for i, s in enumerate(spans):
        if s is None or not lo <= s[1] < hi:
            continue
        keep[i] = len(out)
        out.append((s[0], s[1], s[2], keep.get(s[3], -1), s[4]))
    return out


def _descendants(spans: Sequence[Span]) -> Dict[int, List[int]]:
    """Span index -> the indices of every span below it."""
    below: Dict[int, List[int]] = defaultdict(list)
    for i, s in enumerate(spans):
        p = s[3]
        while p >= 0:
            below[p].append(i)
            p = spans[p][3]
    return below


def _seconds(s: Span) -> float:
    return (s[2] - s[1]) * 1e-9


@dataclass
class Execution:
    """One replica's execution of one request."""
    start_ns: int
    seconds: float
    #: of which the host waited for the device's tokens
    wait_s: float
    ids: dict

    @property
    def host_s(self) -> float:
        return self.seconds - self.wait_s


@dataclass
class Checkpoint:
    """One replica's consensus checkpoint."""
    start_ns: int
    seconds: float
    #: in ``app.snapshot``, and the largest table it snapshot, in token ids
    snapshot_s: float
    table_ids: Optional[int]
    ids: dict

    @property
    def fingerprint_s(self) -> float:
        return self.seconds - self.snapshot_s


def executions(spans: Sequence[Span]) -> List[Execution]:
    below = _descendants(spans)
    return [Execution(s[1], _seconds(s),
                      sum(_seconds(spans[j]) for j in below[i]
                          if spans[j][0] == "serve.sync"), s[4])
            for i, s in enumerate(spans) if s[0] == "replica.execute"]


def checkpoints(spans: Sequence[Span]) -> List[Checkpoint]:
    below = _descendants(spans)
    out = []
    for i, s in enumerate(spans):
        if s[0] != "consensus.checkpoint":
            continue
        snaps = [spans[j] for j in below[i] if spans[j][0] == "app.snapshot"]
        sizes = [x[4]["ids"] for x in snaps if x[4].get("ids") is not None]
        out.append(Checkpoint(s[1], _seconds(s),
                              sum(_seconds(x) for x in snaps),
                              max(sizes, default=None), s[4]))
    return out


def describe(spans: Sequence[Span], counters: Dict[str, int], t0: float
             ) -> List[str]:
    """Log lines: the longest execution and checkpoint of the window, and
    the counters."""
    lines = []
    ex = executions(spans)
    if ex:
        e = max(ex, key=lambda x: x.seconds)
        lines.append(
            f"longest replica.execute: {e.seconds:.4f} s from "
            f"{e.start_ns * 1e-9 - t0:.3f} s ({e.ids.get('replica')}, slot "
            f"{e.ids.get('slot')}): host {e.host_s:.4f} s, serve.sync "
            f"{e.wait_s:.4f} s")
    cp = checkpoints(spans)
    if cp:
        c = max(cp, key=lambda x: x.seconds)
        lines.append(
            f"longest consensus.checkpoint: {c.seconds:.4f} s from "
            f"{c.start_ns * 1e-9 - t0:.3f} s ({c.ids.get('replica')}, slot "
            f"{c.ids.get('slot')}): app.snapshot {c.snapshot_s:.4f} s over "
            f"{c.table_ids} ids, fingerprint {c.fingerprint_s:.4f} s; "
            f"{len(cp)} in the window, {sum(x.seconds for x in cp):.4f} s "
            f"in all")
    lines.append("counters: " + ", ".join(
        f"{k} {v}" for k, v in sorted(counters.items())))
    return lines


# ---------------------------------------------------------------------------
# the profiler trace
# ---------------------------------------------------------------------------
def module_name(event_name: str) -> str:
    """``jit_decode_step(1234)`` -> ``jit_decode_step``."""
    return re.sub(r"\(\d*\)$", "", event_name.strip())


@dataclass
class Trace:
    """What this module reads of a profiler trace, besides ``xtrace``'s
    own reading, in seconds on the trace's clock."""
    events: xtrace.Events
    #: (span name, start, end) of every span of ``HOST_SPANS``
    host_spans: List[Tuple[str, float, float]]
    #: per chip, (module name, start, end)
    modules: Dict[str, List[Tuple[str, float, float]]]


def read(data) -> Trace:
    """Read a trace (a ``jax.profiler.ProfileData``, or the path of an
    ``.xplane.pb``)."""
    if isinstance(data, str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(data)
    spans: List[Tuple[str, float, float]] = []
    mods: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = mods.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    chip.extend((module_name(e.name), e.start_ns * 1e-9,
                                 e.end_ns * 1e-9) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                             for e in line.events if e.name in HOST_SPANS)
    return Trace(events=xtrace.read(data), host_spans=spans, modules=mods)


def _host_at(t: float, spans: List[Tuple[str, float, float]]) -> str:
    open_ = {name for name, s, e in spans if s <= t < e}
    for name in HOST_SPANS:
        if name in open_:
            return name
    return "other"


def idle_by_span(tr: Trace) -> List[Tuple[str, float]]:
    """(host span, idle seconds of the slice while it was the innermost of
    ``HOST_SPANS`` open), most first, summed over the chips."""
    lo, hi = tr.events.slice
    idle: Dict[str, float] = defaultdict(float)
    for chip_ops in tr.events.device_ops.values():
        merged = xtrace.union([(s, e) for _, s, e in chip_ops], lo, hi)
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle[_host_at((a + b) / 2, tr.host_spans)] += b - a
    return sorted(idle.items(), key=lambda kv: -kv[1])


def module_times(tr: Trace) -> Dict[str, Tuple[float, int]]:
    """Module name -> (device seconds, executions) of the module executions
    that lie wholly inside the slice, over every chip."""
    lo, hi = tr.events.slice
    out: Dict[str, Tuple[float, int]] = {}
    for chip in tr.modules.values():
        for name, s, e in chip:
            if lo <= s and e <= hi:
                secs, n = out.get(name, (0.0, 0))
                out[name] = (secs + e - s, n + 1)
    return out


def device_ms(modules: Optional[Dict[str, Tuple[float, int]]], jit: str
              ) -> Optional[float]:
    """Mean device milliseconds of one execution of the program ``jax.jit``
    named ``jit`` (None where it never ran wholly inside the slice)."""
    secs, n = (modules or {}).get(f"jit_{jit}", (0.0, 0))
    return secs / n * 1e3 if n else None
