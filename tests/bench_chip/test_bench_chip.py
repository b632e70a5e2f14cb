"""Tests of the chip benchmark's harness (``bench/``), on the CPU.

They check the pieces the benchmark's numbers rest on: the reduction of a
profiler trace (on a trace recorded on a TPU v5e and trimmed to 20 ms), the
FLOP count behind ``step_mfu`` (against a hand count), the percentiles
(over every request of the window), the discovery of cells, files and
metric readers by name, and whole runs of a ``chat`` and a ``score`` cell
at the repo's smoke sizes.  The faults a token-server cell can have are
planted under a run, and each must turn ``correct`` false; so must the
controls, the reference computed in int8 and in fp8: at smoke size, and,
by the cells' own limits, on gaps recorded on the chip at the cells' size.
"""

from __future__ import annotations

import os
import re
import subprocess
import sys
import time
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(ROOT, "bench")
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from harness import check, driver, runner, spec, traffic, xtrace  # noqa: E402
from harness.reference import served_gaps  # noqa: E402

TRACE = os.path.join(HERE, "data", "chatglm3-6b-d14.chat.trace.txtpb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


# ---------------------------------------------------------------------------
# trace -> device_idle_pct and breakdown
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData
    with open(TRACE) as fh:
        return xtrace.read(ProfileData.from_text_proto(fh.read()))


def test_trace_reduction_on_recorded_trace(recorded):
    red = xtrace.reduce(recorded)
    lo, hi = recorded.slice
    ops = recorded.device_ops["/device:TPU:0"]
    # busy time again, by marking every nanosecond of the slice some op ran
    mark = np.zeros(int(round((hi - lo) * 1e9)), bool)
    for _, s, e in ops:
        a = max(0, int(round((s - lo) * 1e9)))
        b = min(mark.size, int(round((e - lo) * 1e9)))
        mark[a:b] = True
    assert red.chips == 1
    assert red.window_s == pytest.approx(0.020, abs=1e-9)
    assert red.busy_s == pytest.approx(mark.sum() * 1e-9, abs=2e-8)
    assert red.busy_s == pytest.approx(0.017757583, abs=1e-8)
    assert red.idle_pct == pytest.approx(100 * (1 - red.busy_s / 0.020))
    # every idle nanosecond is put down to one host span
    assert sum(s for _, s in red.idle_by_span) == pytest.approx(
        red.window_s - red.busy_s, abs=1e-9)
    assert red.idle_by_span[0][0] == "replica_exec"
    # the decode step's loop holds the others: ranked are the ops inside it
    names = [n for n, _ in red.top_ops]
    assert len(names) == 10 and not any(xtrace.is_container(n) for n in names)
    assert names[0] == "fusion.80"
    assert [s for _, s in red.top_ops] == sorted(
        [s for _, s in red.top_ops], reverse=True)


def test_trace_union_clips_and_merges():
    got = xtrace.union([(5, 7), (0, 2), (1, 3), (6, 9), (12, 13)], 1, 8)
    assert got == [(1, 3), (5, 8)]
    assert xtrace.op_name("%fusion.80 = bf16[4096]{0:T(1024)} fusion(%a), "
                          "kind=kLoop") == "fusion.80 bf16[4096]"
    assert xtrace.op_name("%fusion.5 = (bf16[13696]{0}, bf16[13696]{0}) "
                          "fusion(%a)") == "fusion.5 bf16[13696]"
    assert xtrace.op_name("%while.6") == "while.6"
    assert xtrace.is_container("while.6 s32[]")


def test_trace_with_no_device_reads_no_idle_share():
    ev = xtrace.Events(slice=(0.0, 1.0), device_ops={}, host_spans=[])
    assert xtrace.reduce(ev).idle_pct is None


# ---------------------------------------------------------------------------
# step_mfu's FLOP count
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def dense():
    return spec.family("dense_gqa")


@pytest.fixture(scope="module")
def glm_smoke(bench, dense):
    return dense.sizes(spec.config_file(bench, "chatglm3-6b-d14"), smoke=True)


def test_flop_count_matches_hand_count(dense, glm_smoke):
    s = glm_smoke
    assert (s.layers, s.d_model, s.heads, s.kv_heads, s.head_dim, s.d_ff,
            s.vocab) == (2, 64, 4, 2, 16, 128, 256)
    # q 64x64, k and v 64x32 each, o 64x64, gate/up/down 64x128 each
    weights = 64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 128
    assert weights == 36864

    def by_hand(processed, history, n):
        total = 0
        for p in range(processed, history + n - 1):
            total += 2 * (2 * weights + 4 * 4 * 16 * (p + 1))
        return total + n * 2 * 64 * 256

    for args in [(0, 128, 32), (159, 160, 32), (0, 448, 1), (3, 4, 1)]:
        assert dense.request_flops(s, *args) == by_hand(*args)


def test_step_mfu_counts_every_replica_and_stays_under_peak(dense, glm_smoke):
    reader = spec.metric_reader("step_mfu")
    req = lambda p, h, n: SimpleNamespace(  # noqa: E731
        processed=p, history=h, tokens=[0] * n, in_window=True)
    w = SimpleNamespace(done=lambda: [req(0, 128, 32), req(159, 160, 32)],
                        seconds=2.0, profiler_s=0.0)
    run = SimpleNamespace(window=w, sizes=glm_smoke, family=dense, replicas=3,
                          peaks={"bf16_flops_per_s": 1e9})
    want = 100 * 3 * (dense.request_flops(glm_smoke, 0, 128, 32)
                      + dense.request_flops(glm_smoke, 159, 160, 32)) / 2e9
    assert reader.read(run) == pytest.approx(want)
    run.peaks = None
    assert reader.read(run) is None


# ---------------------------------------------------------------------------
# percentiles over every request of the window
# ---------------------------------------------------------------------------
def test_percentiles_taken_over_all_requests_of_the_window():
    lat = [0.9, 0.1, 0.5, 0.3, 0.7, 0.2, 0.8, 0.4, 0.6, 1.0, 0.05]
    reqs = [SimpleNamespace(t_submit=10.0, t_done=10.0 + x, in_window=True,
                            tokens=[1, 2]) for x in lat]
    late = SimpleNamespace(t_submit=10.0, t_done=99.0, in_window=False,
                           tokens=[1, 2])
    w = driver.Window(requests=reqs + [late], sessions=[], t0=0.0,
                      t_close=4.0, exec_s=[], exec_at=[], executions=0,
                      compiles=0)
    run = SimpleNamespace(window=w)
    p50 = spec.metric_reader("latency_p50_ms").read(run)
    p90 = spec.metric_reader("latency_p90_ms").read(run)
    assert p50 == pytest.approx(np.percentile(lat, 50) * 1e3)
    assert p90 == pytest.approx(np.percentile(lat, 90) * 1e3)
    assert spec.metric_reader("tokens_per_s").read(run) == pytest.approx(
        2 * len(lat) / 4.0)


def test_traffic_is_drawn_from_the_seed_with_fixed_lengths():
    mix = traffic.load("chat")
    assert (mix.clients, mix.turns, mix.prompt_tokens, mix.output_tokens) == \
        (4, 4, 128, 32)
    a = [traffic.Sessions(mix, 2**31 + 11, 1000).new() for _ in range(2)]
    b = traffic.Sessions(mix, 2**31 + 11, 1000).new()
    c = traffic.Sessions(mix, 5, 1000).new()
    assert a[0].prompt == b.prompt != c.prompt
    assert len(b.prompt) == len(c.prompt) == 128
    s = traffic.load("score")
    assert (s.clients, s.turns, s.prompt_tokens, s.output_tokens) == \
        (1, 1, 448, 1)


# ---------------------------------------------------------------------------
# cells, configurations, mixes, limits and readers, by name
# ---------------------------------------------------------------------------
def test_benchmark_names_and_files_are_found(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for cfg in bench["configs"]:
        assert NAME.match(cfg["name"])
        f = spec.config_file(bench, cfg["name"])
        assert f["name"] == cfg["name"] and f["reduced"] == cfg["reduced"]
        assert set(f["reduced"]) <= set(f["config"]) and \
            set(f["reduced"]) == set(f["published"])
        spec.config_family(bench, cfg["name"]).program_config(
            cfg["name"], f).validate()
    for c in bench["workloads"]:
        assert NAME.match(c["name"]) and c["chips"] in (1, 4)
        spec.config_entry(bench, c["config"])
        traffic.load(c["traffic"])
        limits = spec.limits_file(c["name"])
        assert {"logit_gap", "logit_gap_mean", "replicas_differ",
                "histories_differ", "executions_missing",
                "unanswered"} <= set(limits)
        kinds = {k: spec.metrics_for(bench, c["name"], k)
                 for k in ("end_to_end", "per_layer")}
        assert kinds["per_layer"] and len(kinds["end_to_end"]) >= 2
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"])
        assert callable(spec.metric_reader(m["name"]).read)
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
    with pytest.raises(spec.SpecError):
        spec.cell(bench, "no-such-cell")
    with pytest.raises(KeyError):
        from harness.peaks import peaks
        peaks("TPU v0 imaginary")


# ---------------------------------------------------------------------------
# whole runs at smoke size on the CPU
# ---------------------------------------------------------------------------
@pytest.fixture
def cpu_run(monkeypatch):
    """A run of a cell at smoke size, past the look for a chip; returns
    (result, numbers, log lines)."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off in tests")

    def go(workload, seed=2**31 + 7, seconds=1.5, fault=None):
        lines = []
        result, numbers = runner.execute(
            workload, seed, seconds, False, time.perf_counter(),
            platform="cpu", smoke=True, log=lines.append, fault=fault)
        return result, numbers, lines
    return go


CELLS = ["chatglm3-6b-d14.chat", "qwen3-8b-d12.score"]


@pytest.mark.parametrize("workload", CELLS)
def test_cpu_rehearsal_runs_the_cell_correctly(cpu_run, bench, workload):
    result, numbers, lines = cpu_run(workload)
    assert result["correct"] is True, numbers
    assert result["attempted"] >= 1 and result["failed"] == 0
    assert set(result["metrics"]) == {
        m["name"] for m in spec.metrics_for(bench, workload, "end_to_end")}
    assert list(result)[-1] == "check"
    assert result["check"]["logit_gap"]["value"] <= \
        result["check"]["logit_gap"]["limit"]
    assert any(l.endswith("compilations in the window: 0") for l in lines)


@pytest.mark.parametrize("workload", CELLS)
def test_unsteered_run_on_cpu_exits_without_a_result(workload):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         workload, "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "not 'tpu'" in out.stderr
    assert not any(l.startswith("{") for l in out.stdout.splitlines())


def _bump_lowest(logits):
    import jax.numpy as jnp
    low = jnp.argmin(logits, -1)
    top = jnp.max(logits, -1) + 1.0
    return logits.at[jnp.arange(logits.shape[0]), low].set(top)


def test_fault_token_altered_where_produced(cpu_run, monkeypatch):
    """Every replica's prefill puts its least likely token first."""
    import repro.launch.serve as serve
    real = serve.prefill

    def prefill(cfg, params, inputs, **kw):
        logits, caches = real(cfg, params, inputs, **kw)
        return _bump_lowest(logits), caches
    monkeypatch.setattr(serve, "prefill", prefill)
    for workload in CELLS:
        result, numbers, _ = cpu_run(workload)
        assert result["correct"] is False
        gap, limit = numbers["logit_gap"]
        assert gap > limit
        assert numbers["replicas_differ"][0] == 0   # consensus still held


def test_fault_one_replica_answers_otherwise(cpu_run):
    """A Byzantine replica alters the tokens it produces: its reply loses
    the f+1 match, and its state no longer matches the others'."""
    def fault(server):
        app = server.cluster.replicas[0].app
        honest = app.decode_fn
        app.decode_fn = lambda sid, hist, n: [
            (t + 1) % 256 for t in honest(sid, hist, n)]
    for workload in CELLS:
        result, numbers, _ = cpu_run(workload, fault=fault)
        assert result["correct"] is False
        assert numbers["replicas_differ"][0] >= 1
        assert numbers["logit_gap"][0] <= numbers["logit_gap"][1]


def test_control_in_fp8_is_not_correct(monkeypatch):
    """The reference computed in fp8, put in the program's place on the
    served histories, reads a gap the program's bf16 never does: at smoke
    size on two seeds, more than three times the program's widest."""
    import repro.launch.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "off in tests")
    p = runner.prepare("chatglm3-6b-d14.chat", platform="cpu", smoke=True,
                       log=lambda _: None)
    program, control = [], []
    for seed in (2**31 + 3, 2**33 + 5):
        window, _, _ = runner.measure(p, seed, 3.0, False, log=lambda _: None)
        hist, start = runner.sample_histories(window, p.mix, seed)
        gaps, ctl = served_gaps(p.family, p.sizes, hist, start, ["fp8"],
                                shape=runner.reference_shape(p.mix))
        program.append(gaps.max())
        control.append(ctl["fp8"].max())
    assert min(control) > 3 * max(program), (program, control)


@pytest.mark.parametrize("workload", CELLS)
def test_recorded_controls_are_not_correct_by_the_cells_limits(workload):
    """Every served token's gap, recorded on a TPU v5e at the cell's size by
    ``bench/calibrate.py`` (the program's, and on the same histories the
    int8 and fp8 controls'), judged by ``bench/limits/<cell>.json``: the
    program is correct on every seed, each control on none."""
    rec = spec.load_json(os.path.join(HERE, "data", f"{workload}.gaps.json"))
    limits = spec.limits_file(workload)
    exact = {k: 0.0 for k in ("replicas_differ", "histories_differ",
                              "executions_missing", "unanswered")}

    def correct(gaps):
        values = dict(exact, **check.gap_numbers(np.asarray(gaps)))
        return check.judge(values, limits)[0]
    assert len(rec) >= 3
    for row in rec:
        assert correct(row["program"]), row["seed"]
        assert not correct(row["int8"]), row["seed"]
        assert not correct(row["fp8"]), row["seed"]
