"""Tests of the readings of the program's own spans (``bench/harness/
program.py``, ``bench/trace_spans.py`` and the readers that need spans),
on the CPU.

Whole traced windows at the repo's smoke sizes, recording on: each
replica execution holds the app's apply and one prefill; the counters
agree with what the harness's requests imply; the split of an execution
into host work and device wait adds up to the harness's own timer; and
with a consensus window of 8 slots the checkpoints are counted, sized and
timed.  On traces recorded on a TPU v5e: idle gaps go to the program's
spans and device time to the named jits, and the benchmark's recorded
trace reduces as ``xtrace`` reduces it.
"""

from __future__ import annotations

import os
import sys
import time
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

import trace_spans  # noqa: E402
from harness import program, runner, spec, xtrace  # noqa: E402

CELLS = ["chatglm3-6b-d14.chat", "qwen3-8b-d12.score"]
SEED = 2**33 + 17


def _window(workload, log, build=None):
    """A traced smoke-size window with spans recorded: (prepared, run,
    record).  ``build`` replaces ``ReplicatedServer.build``."""
    import repro.launch.compile_cache as cc
    from repro.runtime.server import ReplicatedServer
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(cc, "enable_compile_cache", lambda: "off in tests")
        if build is not None:
            mp.setattr(ReplicatedServer, "build", build)
        p = runner.prepare(workload, platform="cpu", smoke=True, log=log)
        run, rec, _ = trace_spans.traced_window(
            p, SEED, 1.5, time.perf_counter(), log=log)
    return p, run, rec


@pytest.fixture(scope="module", params=CELLS)
def traced(request):
    lines = []
    p, run, rec = _window(request.param, lines.append)
    return SimpleNamespace(p=p, run=run, rec=rec, lines=lines)


def _children(spans, i):
    return [j for j, s in enumerate(spans) if s[3] == i]


def test_every_execution_holds_one_apply_and_one_prefill(traced):
    spans = [s for s in traced.rec.spans if s is not None]
    assert len(spans) == len(traced.rec.spans)
    n = traced.p.mix.output_tokens
    execs = [i for i, s in enumerate(spans) if s[0] == "replica.execute"]
    assert execs
    for i in execs:
        kids = _children(spans, i)
        assert [spans[j][0] for j in kids] == ["app.apply"]
        inner = [spans[j][0] for j in _children(spans, kids[0])]
        # the prefill, one decode loop for the n - 1 tokens after its own,
        # and one read of all n
        loop = ["serve.step"] if n > 1 else []
        assert inner == ["serve.prefill"] + loop + ["serve.sync"]
        ids = spans[i][4]
        assert ids["replica"] in {"r0", "r1", "r2"} and ids["slot"] >= 0
    # the spans of one request share its rid, once per replica
    rids = {}
    for i in execs:
        rids.setdefault(spans[i][4]["rid"], []).append(spans[i][4]["replica"])
    assert all(sorted(r) == ["r0", "r1", "r2"] for r in rids.values())


def test_counters_match_the_harness_requests(traced):
    w, c = traced.run.window, traced.rec.counters
    answered = [r for r in w.requests if r.tokens is not None]
    assert len(answered) == len(w.requests)
    reps = traced.p.replicas
    assert c["serve.prefills"] == w.executions == reps * len(answered)
    steps = reps * sum(len(r.tokens) - 1 for r in answered)
    loops = reps * sum(len(r.tokens) > 1 for r in answered)
    assert c.get("serve.decode_steps", 0) == steps
    assert c.get("serve.decode_loops", 0) == loops
    # a counter never counted is absent, not 0
    assert ("serve.decode_steps" in c) == ("serve.decode_loops" in c) \
        == (steps > 0) == (traced.p.mix.output_tokens > 1)
    assert c["serve.prefill_tokens"] == reps * sum(r.history
                                                   for r in answered)
    (line,) = [ln for ln in traced.lines if ln.startswith("counters: ")]
    names = [x.split()[0] for x in line[len("counters: "):].split(", ")]
    assert names == sorted(c)
    assert ("serve.decode_steps" in names) == (steps > 0)


def test_host_and_wait_add_up_to_the_harness_timer(traced):
    read = lambda m: spec.metric_reader(m).read(traced.run)  # noqa: E731
    host, wait, exec_ms = (read("exec_host_ms"), read("exec_wait_ms"),
                           read("replica_exec_ms"))
    assert host > 0 and wait > 0
    assert len(program.executions(traced.run.spans)) == \
        len(traced.run.window.exec_s)
    assert exec_ms <= host + wait <= 1.10 * exec_ms
    # no checkpoint boundary in a short window of 256-slot checkpoints
    assert read("checkpoint_ms") == 0.0
    # the CPU's trace has no TPU plane: no device time is read
    assert read("prefill_device_ms") is None
    assert read("decode_step_device_ms") is None


def test_checkpoints_are_counted_sized_and_timed():
    from repro.core.consensus import ConsensusConfig
    from repro.runtime.server import ReplicatedServer
    window = 8
    built = []
    real = ReplicatedServer.build.__func__

    def build(cls, decode):
        server = real(cls, decode, cfg=ConsensusConfig(
            f=1, f_m=1, max_request_bytes=4096, window=window))
        built.append(server)
        return server

    lines = []
    _, run, rec = _window("qwen3-8b-d12.score", lines.append,
                          build=classmethod(build))
    replicas = built[-1].cluster.replicas
    passed = sum((r.exec_upto + 1) // window for r in replicas)
    assert passed >= 3
    assert rec.counters["consensus.checkpoints"] == passed
    cps = program.checkpoints(program.clip(rec.spans, 0.0, float("inf")))
    assert len(cps) == passed
    sized = 0
    for r in replicas:
        # the boundaries a replica still holds the snapshot of (slot 0 is
        # the state it started from, taken at no checkpoint)
        for slot, snap in r._boundary_snaps.items():
            if slot == 0:
                continue
            mine = [c.table_ids for c in cps
                    if c.ids == {"replica": r.pid, "slot": slot}]
            assert mine == [sum(len(h) for _, h in snap)]
            sized += 1
    assert sized >= len(replicas)
    assert all(c.table_ids > 0 for c in cps)
    assert all(c.snapshot_s > 0 and c.fingerprint_s > 0 for c in cps)
    assert spec.metric_reader("checkpoint_ms").read(run) > 0
    assert any(line.startswith("longest consensus.checkpoint")
               for line in lines)


@pytest.mark.parametrize("metric", trace_spans.SPAN_METRICS)
def test_readers_read_nothing_from_a_run_without_spans(metric):
    w = SimpleNamespace(done=lambda: [SimpleNamespace(tokens=[1])])
    plain = runner.Run(sizes=None, family=None, replicas=3, window=w,
                       setup_s=1.0, peaks=None)
    assert spec.metric_reader(metric).read(plain) is None


def test_clip_keeps_the_window_and_points_parents_into_it():
    s = [("a", 0, 50, -1, {}), ("b", 10, 20, 0, {}), None,
         ("c", 30, 40, 0, {}), ("d", 31, 39, 3, {}), ("e", 60, 70, -1, {})]
    got = program.clip(s, 25e-9, 60e-9)
    assert got == [("c", 30, 40, -1, {}), ("d", 31, 39, 0, {})]


# ---------------------------------------------------------------------------
# traces recorded on the chip
# ---------------------------------------------------------------------------
def _profile(name):
    from jax.profiler import ProfileData
    with open(os.path.join(HERE, "data", name)) as fh:
        return ProfileData.from_text_proto(fh.read())


def test_recorded_benchmark_trace_reduces_as_before():
    """The benchmark's own recorded trace, which holds no program span:
    read here, it reduces to what ``xtrace`` gives, to the nanosecond."""
    data = _profile("chatglm3-6b-d14.chat.trace.txtpb")
    tr = program.read(data)
    red = xtrace.reduce(tr.events)
    want = xtrace.reduce(xtrace.read(data))
    assert red == want
    assert red.busy_s == pytest.approx(0.017757583, abs=1e-8)
    assert red.idle_pct == pytest.approx(100 * (1 - 0.017757583 / 0.020),
                                         abs=1e-6)
    assert red.top_ops[0][0] == "fusion.80"
    assert program.idle_by_span(tr) == want.idle_by_span
    assert program.module_times(tr) == {}


def test_recorded_program_trace_names_idle_time_and_jits():
    """A 34.6 ms slice of a traced ``chat`` window on a TPU v5e, recorded
    with the program's spans on: one replica's prefill between two decode
    steps.  Idle time goes to the program's spans, where the benchmark's
    own spans put all of it under ``replica_exec``; device time goes to
    the named jits."""
    data = _profile("chatglm3-6b-d14.chat.spans.txtpb")
    tr = program.read(data)
    red = xtrace.reduce(tr.events)
    assert red.window_s == pytest.approx(0.034591, abs=1e-9)
    idle = dict(program.idle_by_span(tr))
    assert sum(idle.values()) == pytest.approx(red.window_s - red.busy_s,
                                               abs=1e-9)
    assert set(idle) == {"serve.step", "serve.sync"}
    assert idle["serve.step"] == pytest.approx(0.003645725, abs=1e-9)
    assert [n for n, _ in red.idle_by_span] == ["replica_exec"]
    # module time again, by hand from the module line
    lo, hi = tr.events.slice
    (plane,) = [p for p in data.planes if p.name == "/device:TPU:0"]
    (line,) = [ln for ln in plane.lines if ln.name == "XLA Modules"]
    hand = {}
    for e in line.events:
        s, t = e.start_ns * 1e-9, e.end_ns * 1e-9
        if lo <= s and t <= hi:
            name = e.name.split("(")[0]
            hand.setdefault(name, []).append(t - s)
    mods = program.module_times(tr)
    assert {k: n for k, (_, n) in mods.items()} == \
        {k: len(v) for k, v in hand.items()}
    assert mods["jit_prefill"][1] == 1 and mods["jit_decode_step"][1] == 2
    run = SimpleNamespace(modules=mods)
    assert spec.metric_reader("prefill_device_ms").read(run) == \
        pytest.approx(sum(hand["jit_prefill"]) * 1e3, abs=1e-9)
    assert spec.metric_reader("decode_step_device_ms").read(run) == \
        pytest.approx(sum(hand["jit_decode_step"]) / 2 * 1e3, abs=1e-9)
    assert spec.metric_reader("decode_step_device_ms").read(run) == \
        pytest.approx(8.7736, abs=1e-3)
    assert program.module_name("jit_decode_step(8890307536913268963)") == \
        "jit_decode_step"
