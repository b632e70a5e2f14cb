"""From a profiler trace to device busy time, idle share and a breakdown.

The traced run records a slice at the end of the window.  The slice is the
host span ``bench_slice``; the device's work is the events of each TPU
plane's ``XLA Ops`` line; the host spans ``replica_exec``, ``harness`` and
``consensus`` say what the host was doing.  Busy time is the union of the
device's op intervals inside the slice, averaged over the chips traced; an
idle gap is a stretch of the slice in which no op ran on a chip, and is
put down to the innermost host span open at its middle.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

#: host spans, innermost first where they nest
HOST_SPANS = ("replica_exec", "harness", "consensus")
SLICE = "bench_slice"
OPS_LINE = "XLA Ops"
#: entries of each list of the breakdown
TOP = 10

Interval = Tuple[float, float]


@dataclass
class Events:
    """What the reduction reads of a trace, in seconds on one clock."""
    slice: Interval
    #: per chip, (op name, start, end)
    device_ops: Dict[str, List[Tuple[str, float, float]]]
    #: (span name, start, end)
    host_spans: List[Tuple[str, float, float]]


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {trace_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def op_name(hlo: str) -> str:
    """``%fusion.80 = bf16[4096]{0:T(1024)} fusion(...)`` ->
    ``fusion.80 bf16[4096]``: the op and its (first) output's shape."""
    name, _, rest = hlo.partition(" = ")
    shape = re.search(r"\w+\[[\d,]*\]", rest)
    name = name.lstrip("%")
    return f"{name} {shape.group(0)}" if shape else name


def is_container(name: str) -> bool:
    """Control-flow ops whose interval holds the ops of their body."""
    return name.split(".", 1)[0].split(" ", 1)[0] in (
        "while", "conditional", "call")


def read(data) -> Events:
    """Pull the slice, the device ops and the host spans out of a trace
    (a ``jax.profiler.ProfileData``, or the path of an ``.xplane.pb``)."""
    if isinstance(data, str):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(data)
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    slices: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            chip = ops.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    chip.extend((op_name(e.name), e.start_ns * 1e-9,
                                 e.end_ns * 1e-9) for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == SLICE:
                        slices.append((e.start_ns * 1e-9, e.end_ns * 1e-9))
                    elif e.name in HOST_SPANS:
                        spans.append((e.name, e.start_ns * 1e-9,
                                      e.end_ns * 1e-9))
    if len(slices) != 1:
        raise RuntimeError(f"{len(slices)} {SLICE} spans in the trace, "
                           "not one")
    return Events(slice=slices[0], device_ops=ops, host_spans=spans)


def union(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    """The intervals clipped to [lo, hi] and merged, in order."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def _host_at(t: float, spans: List[Tuple[str, float, float]]) -> str:
    open_ = {name for name, s, e in spans if s <= t < e}
    for name in HOST_SPANS:
        if name in open_:
            return name
    return "other"


@dataclass
class Reduced:
    busy_s: float          # averaged over the chips traced
    window_s: float
    chips: int
    #: (op name, seconds inside the slice), most time first, all chips;
    #: control-flow ops, which hold others, are left out
    top_ops: List[Tuple[str, float]]
    #: (host span, idle seconds while it was innermost), most first
    idle_by_span: List[Tuple[str, float]]

    @property
    def idle_pct(self) -> Optional[float]:
        if self.window_s <= 0 or self.chips == 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)


def reduce(ev: Events) -> Reduced:
    lo, hi = ev.slice
    busy, per_op = 0.0, defaultdict(float)
    idle = defaultdict(float)
    for chip_ops in ev.device_ops.values():
        merged = union([(s, e) for _, s, e in chip_ops], lo, hi)
        busy += sum(e - s for s, e in merged)
        for name, s, e in chip_ops:
            d = min(e, hi) - max(s, lo)
            if d > 0 and not is_container(name):
                per_op[name] += d
        edges = [lo] + [t for iv in merged for t in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                idle[_host_at((a + b) / 2, ev.host_spans)] += b - a
    chips = len(ev.device_ops)
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:TOP]  # noqa
    return Reduced(busy_s=busy / chips if chips else 0.0, window_s=hi - lo,
                   chips=chips, top_ops=rank(per_op), idle_by_span=rank(idle))
