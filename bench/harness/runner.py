"""One run of one cell: set-up, window, check, metrics, result line."""

from __future__ import annotations

import gc
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass
from types import ModuleType
from typing import Any, Callable, Dict, List, Optional, Tuple

import numpy as np

from harness import check, driver, models, spec, traffic, xtrace
from harness.peaks import peaks as peak_table
from harness.reference import histories_of, served_gaps

#: seconds the traced run records, at the end of its window
TRACE_SLICE_S = 3.0


class NoChip(RuntimeError):
    """JAX finds no accelerator of the kind, or too few of them."""


@dataclass
class Run:
    """What a metric's reader may read."""
    #: the configuration's sizes, as its family describes them
    sizes: Any
    #: the configuration's family (``bench/families/<family>.py``)
    family: ModuleType
    replicas: int
    window: driver.Window
    setup_s: float
    #: the chip's peaks (None off the chip: no device metric there)
    peaks: Optional[Dict[str, float]]
    #: the traced slice, reduced (traced runs only)
    trace: Optional[xtrace.Reduced] = None


def devices_for(chips: int, platform: str):
    import jax
    devs = jax.devices()
    if devs[0].platform != platform:
        raise NoChip(f"first device is {devs[0].platform!r}, not "
                     f"{platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs


def _most_bytes(devs, stat: str) -> Optional[int]:
    """``stat`` of the device memory, on the fullest chip."""
    vals = [(d.memory_stats() or {}).get(stat) for d in devs]
    vals = [v for v in vals if v is not None]
    return max(vals) if vals else None


@dataclass
class Prepared:
    """A cell set up: its description and the program's served model."""
    bench: Dict[str, Any]
    cell: Dict[str, Any]
    devices: List[Any]
    used: List[Any]
    peaks: Optional[Dict[str, float]]
    sizes: Any
    family: ModuleType
    mix: traffic.Mix
    limits: Dict[str, Any]
    replicas: int
    serve: Any                  # the program's ServeResult
    counter: driver.CompileCounter


def prepare(workload: str, platform: str = "tpu", smoke: bool = False,
            log: Callable[[str], None] = print) -> Prepared:
    """Find the cell's files, check the chip, and set the program up at the
    cell's sizes through its own entry.

    ``smoke`` runs the configuration at the repo's CPU smoke sizes (for the
    harness's tests)."""
    import jax

    bench = spec.load_benchmark()
    c = spec.cell(bench, workload)
    devs = devices_for(int(c["chips"]), platform)
    used = devs[:int(c["chips"])]
    pk = peak_table(used[0].device_kind) if platform == "tpu" else None

    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    cfg_file = spec.config_file(bench, c["config"])
    family = spec.config_family(bench, c["config"])
    models.register(c["config"], cfg_file, family)
    sizes = family.sizes(cfg_file, smoke)
    mix = traffic.load(c["traffic"])
    counter = driver.CompileCounter()
    t = time.perf_counter()
    res = driver.serve_setup(c["config"], mix, smoke)
    log(f"setup: serve.run {time.perf_counter() - t:.3f} s "
        f"(compile {res.compile_s:.3f} s, its own session "
        f"{res.wall_s:.3f} s); compilations so far {counter.count}")
    return Prepared(bench=bench, cell=c, devices=devs, used=used, peaks=pk,
                    sizes=sizes, family=family, mix=mix,
                    limits=spec.limits_file(workload),
                    replicas=int(cfg_file["guarantees"]["replicas"]),
                    serve=res, counter=counter)


def measure(p: Prepared, seed: int, seconds: float, trace: bool,
            log: Callable[[str], None] = print,
            fault: Optional[Callable[[Any], None]] = None):
    """A fresh replicated server on the program's decode, driven for one
    window.  Returns the window, the replicas' snapshots and the peak
    device memory.  ``fault`` breaks the server (the harness's tests)."""
    from repro.runtime.server import ReplicatedServer
    timed = driver.Timed(p.serve.decode, annotate=trace)
    server = ReplicatedServer.build(timed)
    if len(server.cluster.replicas) != p.replicas:
        raise spec.SpecError(f"{len(server.cluster.replicas)} replicas, the "
                             f"configuration states {p.replicas}")
    if fault is not None:
        fault(server)
    sessions = traffic.Sessions(p.mix, seed, vocab=p.serve.cfg.vocab)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    window = driver.run_window(
        server, timed, p.mix, sessions, seconds, p.counter,
        trace_slice=min(TRACE_SLICE_S, seconds / 2) if trace else None,
        trace_dir=trace_dir)
    memory_peak = _most_bytes(p.used, "peak_bytes_in_use")
    snapshots = [r.app.snapshot() for r in server.cluster.replicas]
    sim_lat = sorted(x for cl in server.cluster.clients
                     for x in cl.latencies)
    log(f"window: {window.seconds:.3f} s, {len(window.done())} replies in it, "
        f"{len(window.requests)} requests in all; compilations in the "
        f"window: {window.compiles}")
    done = sorted(r.t_done - window.t0 for r in window.done())
    gaps = [b - a for a, b in zip([0.0] + done[:-1], done)]
    if gaps:
        k = max(range(len(gaps)), key=gaps.__getitem__)
        log(f"replies: first at {done[0]:.3f} s, median gap "
            f"{sorted(gaps)[len(gaps) // 2]:.3f} s, longest gap {gaps[k]:.3f} s "
            f"ending at {done[k]:.3f} s")
    if window.exec_s:
        at = [t - window.t0 for t in window.exec_at]
        k = max(range(len(at)), key=window.exec_s.__getitem__)
        between = [(b - a - d, a + d) for a, b, d
                   in zip(at, at[1:], window.exec_s)]
        idle, after = max(between, default=(0.0, 0.0))
        log(f"replica executions: {len(at)}, median "
            f"{sorted(window.exec_s)[len(at) // 2]:.3f} s, longest "
            f"{window.exec_s[k]:.3f} s from {at[k]:.3f} s; longest time "
            f"between two {idle:.3f} s from {after:.3f} s")
    log(f"memory: {_most_bytes(p.used, 'bytes_in_use')} bytes in use after "
        f"the window, peak {memory_peak}")
    log(f"collector: {len(window.gc_pauses)} pauses in the window, "
        f"{sum(window.gc_pauses):.4f} s in all, longest "
        f"{max(window.gc_pauses, default=0.0):.4f} s")
    if sim_lat:
        log(f"simulated SMR latency (virtual time, not a metric): median "
            f"{sim_lat[len(sim_lat) // 2]:.2f} us over {len(sim_lat)}")
    return window, snapshots, memory_peak


def execute(workload: str, seed: int, seconds: float, trace: bool,
            t_start: float, platform: str = "tpu", smoke: bool = False,
            log: Callable[[str], None] = print,
            fault: Optional[Callable[[Any], None]] = None):
    """Run ``workload`` once.  Returns ``(result, numbers)``: the result
    line's object and the numbers compared, each beside its limit."""
    p = prepare(workload, platform, smoke, log)
    window, snapshots, memory_peak = measure(p, seed, seconds, trace, log,
                                             fault)
    setup_s = window.t0 - t_start
    reduced = None
    if trace:
        reduced = xtrace.reduce(xtrace.read(xtrace.find(window.trace_dir)))
        shutil.rmtree(window.trace_dir, ignore_errors=True)

    # the program's state goes before the reference runs
    values = check.consensus_numbers(window, snapshots, p.replicas)
    p.serve = None
    del snapshots
    gc.collect()
    t = time.perf_counter()
    values.update(check.gap_numbers(served_token_gaps(
        window, p.mix, p.family, p.sizes, seed)))
    log(f"reference: {time.perf_counter() - t:.3f} s")
    correct, numbers = check.judge(values, p.limits)

    run = Run(sizes=p.sizes, family=p.family, replicas=p.replicas,
              window=window, setup_s=setup_s, peaks=p.peaks, trace=reduced)
    metrics = {}
    for m in spec.metrics_for(p.bench, workload,
                              "per_layer" if trace else "end_to_end"):
        v = spec.metric_reader(m["name"]).read(run)
        if v is not None and math.isfinite(v):
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}

    device: Dict[str, Any] = {"platform": p.used[0].platform,
                              "kind": p.used[0].device_kind,
                              "count": len(p.devices),
                              "memory_peak_bytes": memory_peak}
    result: Dict[str, Any] = {
        "correct": correct,
        "attempted": len(window.requests),
        "failed": sum(r.tokens is None for r in window.requests),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = reduced.busy_s
        device["window_s"] = reduced.window_s
        result["breakdown"] = {
            "device_ops": [list(x) for x in reduced.top_ops],
            "idle_gaps": [list(x) for x in reduced.idle_by_span],
        }
    result["check"] = {k: {"value": v if math.isfinite(v) else None,
                           "limit": lim}
                       for k, (v, lim) in numbers.items()}
    return result, numbers


def sample_histories(window: driver.Window, mix: traffic.Mix, seed: int):
    """The sessions the check compares: (histories, where serving starts)."""
    turns = check.session_turns(window.requests)
    answered = [s for s in window.sessions if turns.get(s.sid)]
    chosen = traffic.check_sample(answered, mix.check_sessions, seed)
    return histories_of([(s.prompt, turns[s.sid]) for s in chosen])


def served_token_gaps(window: driver.Window, mix: traffic.Mix,
                      family: ModuleType, sizes: Any, seed: int
                      ) -> np.ndarray:
    """The gap of every served token of the sampled sessions."""
    hist, start = sample_histories(window, mix, seed)
    if not hist:
        return np.zeros(0)
    gaps, _ = served_gaps(family, sizes, hist, start,
                          shape=reference_shape(mix))
    return gaps


def reference_shape(mix: traffic.Mix) -> Tuple[int, int, int]:
    """The most sessions, the longest history and the most served tokens
    a check hands over."""
    served = mix.turns * mix.output_tokens
    return (mix.check_sessions, mix.prompt_tokens + served,
            mix.check_sessions * served)


def report(result: Dict[str, Any], numbers: check.Numbers) -> None:
    """The result line last on stdout; the numbers compared last on stderr."""
    import json
    print(json.dumps(result), flush=True)
    for name, (v, lim) in numbers.items():
        print(f"check: {name} {v!r} limit {lim!r}", file=sys.stderr)
    sys.stderr.flush()
