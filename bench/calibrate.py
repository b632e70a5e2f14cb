#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip, in one process.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 20 \\
        [--control-seeds K] [--diag] --out <dir>

The cell is set up once.  For each seed the script drives the timed path
for one window, exactly as ``bench/run.py`` does, then reads on the same
sampled sessions the program's gap numbers (its served tokens against the
float32 reference) and each control's (the reference computed with int8
and with fp8 matrix products in the program's place).  Where the cell has
its limits, each control is judged by them as the program is, and must
come out not correct.  It prints one JSON line per seed and writes them
to ``<out>/<cell>.jsonl``, and every served token's gap, the program's and
each control's, to ``<out>/<cell>.gaps.jsonl``.

Every window also runs a watchdog: while one replica execution has run
for more than ``SLOW_S``, it samples where the main thread is, and the
samples are printed after the window.

``--diag`` first checks that the reference's weights are bit-identical to
the served ones (this reads the program's weights, which the benchmark's
own check never does), and records a short traced window, whose planes and
lines it prints and whose trace file it copies to ``<out>/trace/``.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
import traceback  # noqa: E402
from collections import Counter  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

#: the controls: the reference one precision step and two below bfloat16
QUANTS = ("int8", "fp8")
#: a replica execution longer than this is sampled by the watchdog
SLOW_S = 0.6


class Watchdog(threading.Thread):
    """Samples the main thread's innermost frames while a replica
    execution runs longer than ``SLOW_S``."""

    def __init__(self, decode):
        super().__init__(daemon=True)
        self.decode = decode
        self.begin = None
        self.samples: Counter = Counter()
        self.halt = threading.Event()

    def __call__(self, session, hist, n):
        self.begin = time.perf_counter()
        try:
            return self.decode(session, hist, n)
        finally:
            self.begin = None

    def run(self) -> None:
        main = threading.main_thread().ident
        while not self.halt.wait(0.05):
            begin = self.begin
            if begin is None or time.perf_counter() - begin < SLOW_S:
                continue
            frame = sys._current_frames().get(main)
            if frame is not None:
                where = " < ".join(
                    f"{os.path.basename(f.filename)}:{f.lineno} {f.name}"
                    for f in reversed(traceback.extract_stack(frame)[-3:]))
                self.samples[where] += 1

    def report(self) -> None:
        for where, k in self.samples.most_common(5):
            print(f"watchdog: {k} samples at {where}", flush=True)
        self.samples.clear()


def check_weights(p) -> None:
    import numpy as np
    params = p.serve.params
    w = p.family.Weights(p.sizes)
    pairs = [("embed", params["embed"], w.embed())]
    if "lm_head" in params:
        pairs.append(("lm_head", params["lm_head"], w.head()))
    stacked = params["groups"][0][0]
    for i in (0, p.sizes.layers - 1):
        mine = w.layer(i)
        for k, v in mine.items():
            pairs.append((f"layer{i}.{k}", stacked[k][i], v))
    for name, served, drawn in pairs:
        a = np.asarray(served).view(np.uint16)
        b = np.asarray(drawn).view(np.uint16)
        print(f"weights: {name} shape={a.shape} bit-identical="
              f"{a.shape == b.shape and bool((a == b).all())} "
              f"differing={int((a != b).sum()) if a.shape == b.shape else -1}",
              flush=True)


def dump_trace(p, out: str) -> None:
    from jax.profiler import ProfileData
    from harness import runner, xtrace
    window, _, _ = runner.measure(p, 7, 0.4, trace=True)
    path = xtrace.find(window.trace_dir)
    data = ProfileData.from_file(path)
    for plane in data.planes:
        for line in plane.lines:
            ev = list(line.events)
            if not ev:
                continue
            names = sorted({e.name for e in ev})[:6]
            print(f"trace: plane={plane.name!r} line={line.name!r} "
                  f"events={len(ev)} first={ev[0].start_ns} "
                  f"last_end={max(e.end_ns for e in ev)} names={names}",
                  flush=True)
    red = xtrace.reduce(xtrace.read(path))
    print(f"trace: slice={red.window_s} busy={red.busy_s} chips={red.chips} "
          f"idle_pct={red.idle_pct} top={red.top_ops[:5]} "
          f"idle={red.idle_by_span}", flush=True)
    dst = os.path.join(out, "trace")
    os.makedirs(dst, exist_ok=True)
    shutil.copy(path, os.path.join(dst, f"{p.cell['name']}.xplane.pb"))
    shutil.rmtree(window.trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the controls on this many leading seeds only")
    ap.add_argument("--diag", action="store_true")
    ap.add_argument("--out", required=True,
                    help="directory for the readings (and the trace)")
    args = ap.parse_args(argv)

    from harness import check, runner
    from harness.reference import served_gaps
    p = runner.prepare(args.workload)
    judged = {"logit_gap", "logit_gap_mean"} <= set(p.limits)
    dog = Watchdog(p.serve.decode)
    p.serve.decode = dog
    dog.start()
    print(f"prepared in {time.perf_counter() - T_START:.3f} s", flush=True)
    os.makedirs(args.out, exist_ok=True)
    if args.diag:
        check_weights(p)
        dump_trace(p, args.out)
    rows_path = os.path.join(args.out, f"{args.workload}.jsonl")
    gaps_path = os.path.join(args.out, f"{args.workload}.gaps.jsonl")
    with open(rows_path, "a") as fh, open(gaps_path, "a") as gh:
        for k, seed in enumerate(int(x) for x in args.seeds.split(",")):
            ctl_q = QUANTS if args.control_seeds is None \
                or k < args.control_seeds else []
            window, snaps, peak = runner.measure(p, seed, args.seconds, False)
            dog.report()
            row = check.consensus_numbers(window, snaps, p.replicas)
            row_exact = dict(row)
            hist, start = runner.sample_histories(window, p.mix, seed)
            t = time.perf_counter()
            gaps, ctl = served_gaps(p.family, p.sizes, hist, start, ctl_q,
                                    shape=runner.reference_shape(p.mix))
            row.update({
                "seed": seed, "tokens": int(gaps.size),
                "sessions": len(hist), **check.gap_numbers(gaps),
                "moved": int((gaps > 0).sum()),
                "reference_s": time.perf_counter() - t,
                "replies": len(window.done()), "window_s": window.seconds,
                "compiles": window.compiles, "memory_peak_bytes": peak,
            })
            if judged:
                row["correct"] = check.judge(
                    dict(row_exact, **check.gap_numbers(gaps)), p.limits)[0]
            for q, g in ctl.items():
                numbers = check.gap_numbers(g)
                row.update({f"control_{q}_{name}": v
                            for name, v in numbers.items()})
                row[f"control_{q}_moved"] = int((g > 0).sum())
                if judged:
                    row[f"control_{q}_correct"] = check.judge(
                        dict(row_exact, **numbers), p.limits)[0]
            print(json.dumps(row), flush=True)
            fh.write(json.dumps(row) + "\n")
            gh.write(json.dumps({"seed": seed, "program": gaps.tolist(),
                                 **{q: g.tolist() for q, g in ctl.items()}})
                     + "\n")
    dog.halt.set()
    dog.join()
    return 0


if __name__ == "__main__":
    sys.exit(main())
