"""Benchmark driver: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  The roofline benchmark reads
the dry-run artifacts (artifacts/dryrun/*.json) when present.

``--json`` additionally writes the repo's perf-trajectory artifacts:

* ``BENCH_engine.json``  — host performance (events/sec, wall-clock per
  tier) from ``benchmarks/engine_perf.py``;
* ``BENCH_protocol.json`` — simulated protocol results (p50/p99 µs,
  throughput kops per sweep point) from ``benchmarks/throughput.py``;
* ``BENCH_shared.json`` — multi-application substrate sharing (per-app
  latency + per-app per-pool memory) from ``benchmarks/shared_pools.py``
  (when the ``shared`` figure is run);
* ``BENCH_membership.json`` — reconfiguration-under-load tails (replica
  replacement × pool sync) from ``benchmarks/fig11_reconfig.py`` (when
  the ``membership`` figure is run);
* ``BENCH_sharded.json`` — sharded-service scale-out (K×load×Zipf sweep:
  uniform scaling curve, hot-shard p99 knee, cross-shard 2PC latency)
  from ``benchmarks/sharded.py`` (when the ``sharded`` figure is run);
* ``BENCH_selfheal.json`` — self-healing membership (gray-failure
  detect→replace timeline, rolling full-group rotation tails vs a
  no-fault baseline) from ``benchmarks/selfheal.py`` (when the
  ``selfheal`` figure is run);
* ``BENCH_inference.json`` — replicated inference serving (steady-state
  consensus overhead vs the unreplicated baseline, flash-crowd SLO
  attainment with vs without admission control) from
  ``benchmarks/inference.py`` (when the ``inference`` figure is run).

Usage:  PYTHONPATH=src python -m benchmarks.run [--json] [figure ...]

A module that raises prints a ``<name>.FAILED`` row and the run goes on;
the run then exits non-zero.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def _write_json(path: str, payload: dict) -> None:
    with open(path, "w") as f:
        json.dump(payload, f, indent=2, sort_keys=True)
    print(f"# wrote {path}", flush=True)


def main() -> None:
    from benchmarks import (engine_perf, fig7_app_latency, fig8_request_size,
                            fig9_breakdown, fig10_nonequivocation,
                            fig11_reconfig, fig11_tail_latency, inference,
                            selfheal, sharded, shared_pools, table2_memory,
                            throughput, roofline)
    mods = {
        "fig7": fig7_app_latency,
        "fig8": fig8_request_size,
        "fig9": fig9_breakdown,
        "fig10": fig10_nonequivocation,
        "fig11": fig11_tail_latency,
        "membership": fig11_reconfig,
        "table2": table2_memory,
        "throughput": throughput,
        "shared": shared_pools,
        "sharded": sharded,
        "selfheal": selfheal,
        "inference": inference,
        "engine": engine_perf,
        "roofline": roofline,
    }
    args = sys.argv[1:]
    want_json = "--json" in args
    explicit = [a for a in args if a != "--json"]
    wanted = explicit or list(mods)
    results: dict = {}
    failed: list = []
    print("name,us_per_call,derived")
    for name in wanted:
        t0 = time.time()
        try:
            results[name] = mods[name].run()
            print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
        except Exception as e:  # keep going — report the failure as a row
            import traceback
            traceback.print_exc()
            print(f"{name}.FAILED,0,{type(e).__name__}:{str(e)[:120]}")
            failed.append(name)

    if want_json:
        # a module that already failed above must not crash the JSON pass;
        # with an explicit figure list, only the requested artifacts are
        # (re)computed — `--json shared` must not drag in the full sweeps
        backfill = () if explicit else ("engine", "throughput")
        for name in backfill:
            if name not in results:
                try:
                    results[name] = mods[name].run()
                except Exception:
                    import traceback
                    traceback.print_exc()
                    print(f"# {name} failed — skipping its JSON artifact")
                    failed.append(name)
        if "engine" in results:
            _write_json("BENCH_engine.json", results["engine"])
        if "shared" in results:
            shared = {str(k): v for k, v in results["shared"].items()}
            _write_json("BENCH_shared.json", shared)
        if "membership" in results:
            _write_json("BENCH_membership.json", results["membership"])
        if "sharded" in results:
            _write_json("BENCH_sharded.json", results["sharded"])
        if "selfheal" in results:
            _write_json("BENCH_selfheal.json", results["selfheal"])
        if "inference" in results:
            _write_json("BENCH_inference.json", results["inference"])
        if "throughput" in results:
            tp = results["throughput"]
            protocol = {
                label: {k: v for k, v in metrics.items()}
                for label, metrics in tp.items()
                if isinstance(metrics, dict)
            }
            protocol["speedup_b8_p4"] = tp.get("speedup_b8_p4")
            _write_json("BENCH_protocol.json", protocol)
    if failed:
        sys.exit(f"benchmarks failed: {', '.join(failed)}")


if __name__ == "__main__":
    main()
