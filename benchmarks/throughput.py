"""Throughput of the batched + pipelined consensus hot path (§9 "What
about uBFT's throughput?" — and beyond it).

The paper's evaluation is latency-centric: one client request per CTBcast
slot bounds throughput by the protocol round (~91 kops at 32 B).  This
benchmark drives a closed-loop multi-client load generator and sweeps the
leader's ``max_batch`` × ``pipeline_depth``: the leader coalesces pending
requests into one slot and keeps several slots in flight, so protocol cost
amortizes over the batch.  Reported per configuration: requests/s, p50/p99
latency, and wire bytes per request — against the seed's
one-request-per-slot configuration and the unreplicated / Mu / MinBFT
baselines at equal replica count.

Execution model: every sweep point is an independent, seeded simulation, so
the sweep fans out across worker processes (``--serial`` forces one
process).  Parallelism changes *wall-clock only* — each simulation is
deterministic in its own process and its results are bit-identical either
way (the golden-trace test enforces this for the engine itself).
"""

from __future__ import annotations

import multiprocessing as mp
import os
import sys

from benchmarks.common import emit, tune_runtime

WINDOW_US = 20_000.0
N_CLIENTS = 32
PAYLOAD = b"x" * 32

#: (label, max_batch, pipeline_depth); (1, 1) is the seed's configuration.
SWEEP = [
    ("b1_p1", 1, 1),
    ("b4_p4", 4, 4),
    ("b8_p4", 8, 4),
    ("b16_p8", 16, 8),
]


def _closed_loop(sim, clients, window_us: float):
    """Drive every client closed-loop for ``window_us``; return
    (completed, sorted latencies)."""
    done = {"n": 0}
    lats = []

    def refire(cl):
        def cb(_res, lat):
            done["n"] += 1
            lats.append(lat)
            cl.request(PAYLOAD, cb)
        return cb

    for cl in clients:
        cl.request(PAYLOAD, refire(cl))
    sim.run(until=sim.now + window_us)
    lats.sort()
    return done["n"], lats


def _pcts(lats):
    if not lats:
        return 0.0, 0.0
    return (lats[len(lats) // 2], lats[min(len(lats) - 1,
                                           int(len(lats) * 0.99))])


# ---------------------------------------------------------------- jobs
# One function per sweep point — module-level so they cross the process
# boundary; each builds its own seeded simulator (deterministic in
# isolation, so the fan-out cannot change any simulated number).

def _job_ubft(args):
    label, max_batch, depth = args
    tune_runtime()
    from repro.apps.flip import FlipApp
    from repro.core.consensus import ConsensusConfig
    from repro.scenario import AppSpec, ScenarioSpec, Workload, run_scenario
    cfg = ConsensusConfig(max_batch=max_batch, pipeline_depth=depth)
    res = run_scenario(ScenarioSpec(apps=[AppSpec(
        name="", app=FlipApp, cfg=cfg,
        workload=Workload(kind="closed", duration_us=WINDOW_US,
                          n_clients=N_CLIENTS, payload=PAYLOAD))]))
    lats = sorted(res.latencies())
    n = len(lats)
    p50, p99 = _pcts(lats)
    return (label, {"kops": n / (WINDOW_US / 1e6) / 1e3,
                    "p50_us": p50, "p99_us": p99,
                    "bytes_per_req": res.bytes_sent / max(1, n),
                    "events": res.events_processed})


def _job_unreplicated(_):
    tune_runtime()
    from repro.apps.flip import FlipApp
    from repro.baselines.unreplicated import (UnreplicatedClient,
                                              build_unreplicated)
    sim, _server, client = build_unreplicated(FlipApp)
    clients = [client] + [
        UnreplicatedClient(sim, client.net, client.registry, f"c{i}", "s0")
        for i in range(1, N_CLIENTS)]
    n, _lats = _closed_loop(sim, clients, WINDOW_US)
    return ("unreplicated", {"kops": n / (WINDOW_US / 1e6) / 1e3,
                             "events": sim.events_processed})


def _job_mu(_):
    tune_runtime()
    from repro.apps.flip import FlipApp
    from repro.baselines.mu import build_mu
    sim, client = build_mu(FlipApp)
    n, _lats = _closed_loop(sim, [client], WINDOW_US)
    return ("mu", {"kops": n / (WINDOW_US / 1e6) / 1e3,
                   "events": sim.events_processed})


def _job_minbft(_):
    tune_runtime()
    from repro.apps.flip import FlipApp
    from repro.baselines.minbft import build_minbft
    sim, client = build_minbft(FlipApp)
    n, _lats = _closed_loop(sim, [client], WINDOW_US)
    return ("minbft", {"kops": n / (WINDOW_US / 1e6) / 1e3,
                       "events": sim.events_processed})


_JOBS = ([(_job_ubft, cfg) for cfg in SWEEP] +
         [(_job_unreplicated, None), (_job_mu, None), (_job_minbft, None)])


def _run_jobs(serial: bool = False):
    if serial or os.environ.get("UBFT_BENCH_SERIAL"):
        return [fn(arg) for fn, arg in _JOBS]
    workers = min(len(_JOBS), os.cpu_count() or 1)
    if workers <= 1:
        return [fn(arg) for fn, arg in _JOBS]
    # the jobs never import JAX: a forked child must not need the chip
    # that a parent which touched JAX holds
    ctx = mp.get_context("fork" if "fork" in mp.get_all_start_methods()
                         else "spawn")
    with ctx.Pool(workers) as pool:
        handles = [pool.apply_async(fn, (arg,)) for fn, arg in _JOBS]
        return [h.get() for h in handles]


def run(serial: bool = False) -> dict:
    tune_runtime()
    out = dict(_run_jobs(serial))

    for label, _b, _p in SWEEP:
        r = out[label]
        emit(f"throughput.ubft.{label}.kops", r["kops"],
             "paper~91kops_one_req_per_slot" if label == "b1_p1" else "")
        emit(f"throughput.ubft.{label}.p50_us", r["p50_us"])
        emit(f"throughput.ubft.{label}.p99_us", r["p99_us"])
        emit(f"throughput.ubft.{label}.bytes_per_req", r["bytes_per_req"])

    speedup = out["b8_p4"]["kops"] / max(1e-9, out["b1_p1"]["kops"])
    out["speedup_b8_p4"] = speedup
    emit("throughput.ubft.speedup_b8_p4_vs_seed", speedup,
         "acceptance>=5x")

    emit("throughput.unreplicated.kops", out["unreplicated"]["kops"])
    emit("throughput.mu.kops", out["mu"]["kops"], "single_client")
    emit("throughput.minbft.kops", out["minbft"]["kops"], "single_client")
    return out


if __name__ == "__main__":
    run(serial="--serial" in sys.argv)
