#!/usr/bin/env python3
"""Traced windows of one cell with the program's own spans recorded, on
the chip, in one process.

    python3 bench/trace_spans.py --workload <cell> --seeds 1,2,3 \\
        --seconds 51 [--out <dir>]

The cell is set up once, as ``bench/run.py`` sets it up.  For each seed one
window is driven exactly as a ``bench/run.py --trace 1`` run drives it,
with ``repro.spans`` recording from the server's build to the end of the
drain.  Each window logs the longest replica execution, split into host
work and wait for the device, the longest consensus checkpoint, with its
snapshot and fingerprint, and the program's counters; then one JSON line:
the cell's per-layer metrics of ``BENCHMARK.json``, the metrics that read
the program's spans (``SPAN_METRICS``), the chip's idle gaps put down to
the program's spans, and the device time of each jitted program.  The
served tokens are not checked against the reference (``bench/run.py``
does that).  With ``--out`` the lines also go to ``<out>/<cell>.jsonl``,
and each window's trace file is copied to ``<out>/``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from typing import Any, Callable, Dict, Optional  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

from harness import program, runner, spec, xtrace  # noqa: E402

#: the metrics that read the program's spans and module times
SPAN_METRICS = ("exec_host_ms", "exec_wait_ms", "checkpoint_ms",
                "prefill_device_ms", "decode_step_device_ms")


@dataclass
class SpanRun(runner.Run):
    """A run with the program's spans of the window and the device time of
    each jitted program in the traced slice."""
    spans: Optional[list] = None
    modules: Optional[Dict[str, Any]] = None


def traced_window(p: runner.Prepared, seed: int, seconds: float,
                  t_start: float, log: Callable[[str], None] = print,
                  keep: Optional[str] = None):
    """One traced window with spans recorded.  Returns the run its metrics
    read, the whole record (server build to drain) and the trace read.
    ``keep`` is a directory to copy the trace file to, as
    ``<cell>.<seed>.xplane.pb``."""
    from repro import spans
    with spans.record() as rec:
        window, _, _ = runner.measure(p, seed, seconds, True, log)
    path = xtrace.find(window.trace_dir)
    if keep:
        shutil.copy(path, os.path.join(
            keep, f"{p.cell['name']}.{seed}.xplane.pb"))
    tr = program.read(path)
    shutil.rmtree(window.trace_dir, ignore_errors=True)
    clipped = program.clip(rec.spans, window.t0, window.t_close)
    run = SpanRun(sizes=p.sizes, family=p.family, replicas=p.replicas,
                  window=window, setup_s=window.t0 - t_start, peaks=p.peaks,
                  trace=xtrace.reduce(tr.events), spans=clipped,
                  modules=program.module_times(tr))
    for line in program.describe(clipped, rec.counters, window.t0):
        log(line)
    return run, rec, tr


def readings(p: runner.Prepared, seed: int, run: SpanRun, rec,
             tr: program.Trace) -> Dict[str, Any]:
    """The JSON line of one window."""
    names = [m["name"] for m in spec.metrics_for(p.bench, p.cell["name"],
                                                 "per_layer")]
    metrics = {}
    for name in names + list(SPAN_METRICS):
        v = spec.metric_reader(name).read(run)
        if v is not None:
            metrics[name] = v
    return {"workload": p.cell["name"], "seed": seed, "metrics": metrics,
            "idle_gaps": program.idle_by_span(tr),
            "modules": {k: list(v) for k, v in sorted(run.modules.items())},
            "counters": rec.counters,
            "device": {"platform": p.used[0].platform,
                       "kind": p.used[0].device_kind}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated, one window each")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    try:
        p = runner.prepare(args.workload)
    except (runner.NoChip, spec.SpecError, KeyError) as e:
        print(f"trace_spans: {e}", file=sys.stderr)
        return 2
    if args.out:
        os.makedirs(args.out, exist_ok=True)
    for seed in (int(s) % (1 << 64) for s in args.seeds.split(",")):
        got = traced_window(p, seed, args.seconds, T_START, keep=args.out)
        line = json.dumps(readings(p, seed, *got))
        print(line, flush=True)
        if args.out:
            with open(os.path.join(args.out, f"{args.workload}.jsonl"),
                      "a") as fh:
                fh.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
