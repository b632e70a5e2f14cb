"""Set-up and the measured window of a token-server cell.

Set-up goes through the program's own entry, ``repro.launch.serve.run``,
with the cell's sizes: it builds the model on the device, compiles every
prefill length the mix uses and the decode step, and serves one session
end to end.  The window then drives a fresh ``ReplicatedServer`` built on
the decode that ``run`` returns, with the default consensus configuration
(f = 1, f_m = 1, one request per slot, no cost model), as a closed loop:
each caller submits its next turn when its f+1-matched reply arrives.

A request's latency is the host clock from ``Client.request`` to the
quorum callback.  Replies come in clumps: a replica's execution takes no
simulated time, so several requests often finish on one simulated instant
and their replies reach the clients together, with no device work between
them.  The window therefore opens and closes on whole clumps.  The loop
starts in set-up, and the window opens as the first clump of replies has
been delivered (the replies of the clump are set-up's); it closes at the
first reply at or after its nominal end, with the rest of that reply's
clump: the window holds whole clumps at both ends, so its rate does not
swing with where the deadline falls in a clump.  Requests still in flight
then are drained afterwards, for the check, and are not in the window's
statistics.
"""

from __future__ import annotations

import contextlib
import gc
import json
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax

from harness.traffic import Mix, Session, Sessions

#: simulated microseconds without the awaited event after which the system
#: counts as stuck
IDLE_TIMEOUT_US = 1e7

@dataclass
class Request:
    session: Session
    turn: int
    history: int              # context length the replicas decode after
    processed: int            # of which earlier turns already forwarded
    t_submit: float
    t_done: Optional[float] = None
    tokens: Optional[List[int]] = None
    in_window: bool = False


@dataclass
class Window:
    requests: List[Request]
    sessions: List[Session]
    t0: float
    t_close: float
    #: host seconds of every replica execution (decode call) in the window
    exec_s: List[float]
    #: host clock at the start of each of those executions
    exec_at: List[float]
    #: replica executions of requests of this run, window and drain
    executions: int
    #: compilations JAX reported between the window's start and its close
    compiles: int
    #: host seconds spent starting the profiler inside the window
    profiler_s: float = 0.0
    #: pauses of the Python collector between the window's start and close
    gc_pauses: Tuple[float, ...] = ()
    trace_dir: Optional[str] = None

    @property
    def seconds(self) -> float:
        return self.t_close - self.t0

    def done(self) -> List[Request]:
        return [r for r in self.requests if r.in_window]


class GcClock:
    """Host seconds the Python collector paused the window for."""

    def __init__(self):
        self.pauses: List[float] = []
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append(time.perf_counter() - self._t)


class CompileCounter:
    """Counts the compilations JAX reports (its own monitoring events)."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",)

    def __init__(self):
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event in self.EVENTS:
            self.count += 1


def request_bytes(sid: str, prompt: List[int], n: int, vocab: int) -> bytes:
    """A request as JSON, every id padded with spaces to the width of the
    largest, so that a request's size, and with it its simulated network
    delay, is the same for every seed."""
    width = len(str(vocab - 1))
    ids = ",".join(f"{t:{width}d}" for t in prompt)
    return (f'{{"session": {json.dumps(sid)}, "prompt": [{ids}], '
            f'"n": {n}}}').encode()


def serve_setup(arch: str, mix: Mix, smoke: bool):
    """The program's entry at the cell's sizes: returns its ServeResult."""
    from repro.launch import serve
    argv = ["--arch", arch, "--batch", "1", "--requests", str(mix.turns),
            "--prompt-len", str(mix.prompt_tokens),
            "--gen", str(mix.output_tokens)]
    return serve.run(serve.parse_args(argv + (["--smoke"] if smoke else [])))


def span(name: str, on: bool):
    """A host span in the profiler's trace, where the run is traced."""
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


class Timed:
    """The decode handed to the replicas, with a host timer (and, in a
    traced run, a span) around each call."""

    def __init__(self, decode: Callable, annotate: bool):
        self.decode = decode
        self.annotate = annotate
        self.exec_s: List[float] = []
        self.exec_at: List[float] = []
        #: decode calls begun and ended
        self.started = 0
        self.calls = 0
        self.record = False

    def __call__(self, session: str, hist, n: int):
        self.started += 1
        t = time.perf_counter()
        with span("replica_exec", self.annotate):
            out = self.decode(session, hist, n)
        self.calls += 1
        if self.record:
            self.exec_at.append(t)
            self.exec_s.append(time.perf_counter() - t)
        return out


def profile_options():
    """The device's ops and the host's annotations; no Python call tracing,
    which would slow the host it measures."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def run_window(server, timed: Timed, mix: Mix, sessions: Sessions,
               seconds: float, counter: CompileCounter,
               trace_slice: Optional[float] = None,
               trace_dir: Optional[str] = None) -> Window:
    """The closed loop for ``seconds``, then the drain of what is in flight,
    until every replica has executed every answered request.

    With ``trace_slice`` the profiler records that many seconds at the end
    of the window into ``trace_dir``.  ``IDLE_TIMEOUT_US`` of simulated time
    without the awaited event ends the loop or the drain: the system is
    stuck (replies take microseconds of simulated time)."""
    from repro.runtime.server import ReplicatedServer

    sim = server.cluster.sim
    clients = [server.cluster.new_client() for _ in range(mix.clients)]
    reqs: List[Request] = []
    all_sessions: List[Session] = []
    # phase: "warm" until the first reply, "opening" while that reply's
    # clump is delivered, "open", then "closed"
    state: Dict[str, Any] = {"phase": "warm", "outstanding": 0,
                             "started": -1}
    traced = bool(trace_slice)
    win: Dict[str, Any] = {}
    gc_clock = GcClock()

    def submit(ci: int, s: Session) -> None:
        prompt = s.next_prompt()
        history = len(s.prompt) + s.done_turns * s.n
        # a later turn starts after the previous one forwarded all of its
        # context but its last generated token
        r = Request(session=s, turn=s.done_turns, history=history,
                    processed=0 if s.done_turns == 0 else history - 1,
                    t_submit=time.perf_counter())
        reqs.append(r)
        payload = request_bytes(s.sid, prompt, s.n, sessions.vocab)
        state["outstanding"] += 1
        clients[ci].request(payload, lambda raw, _lat: done(ci, s, r, raw))

    def start(ci: int) -> None:
        s = sessions.new()
        all_sessions.append(s)
        submit(ci, s)

    def done(ci: int, s: Session, r: Request, raw: bytes) -> None:
        t = time.perf_counter()
        with span("harness", traced):
            answered(ci, s, r, raw, t)

    def open_at(t: float) -> None:
        """(Re)open the window at ``t``: its clocks and counts start."""
        win.update(t0=t, deadline=t + seconds, c0=counter.count,
                   trace_at=t + seconds - trace_slice if traced else None)
        gc_clock.pauses.clear()
        timed.exec_s.clear()
        timed.exec_at.clear()
        timed.record = True

    def answered(ci: int, s: Session, r: Request, raw: bytes, t: float
                 ) -> None:
        r.t_done = t
        r.tokens = ReplicatedServer._parse(raw)
        state["outstanding"] -= 1
        if r.tokens is not None:
            s.done_turns += 1
        # no replica began an execution since the last reply: same clump
        clump = timed.started == state["started"]
        state["started"] = timed.started
        phase = state["phase"]
        if phase == "closed":
            if clump and not state["drained"]:
                r.in_window = True
                state["t_close"] = t
            else:
                state["drained"] = True
            return
        if phase == "warm" or (phase == "opening" and clump):
            state["phase"] = "opening"
            open_at(t)
        else:
            state["phase"] = "open"
            r.in_window = True
            if t >= win["deadline"]:
                state.update(phase="closed", t_close=t, drained=False)
                return
        if s.done_turns < s.turns and r.tokens is not None:
            submit(ci, s)
        else:
            start(ci)

    profiler_s = 0.0
    tracing = None
    gc.callbacks.append(gc_clock)
    open_at(time.perf_counter())

    def closed() -> bool:
        return state["phase"] == "closed"

    def trace_due() -> bool:
        return (win["trace_at"] is not None and tracing is None
                and state["phase"] == "open"
                and time.perf_counter() >= win["trace_at"])

    for ci in range(mix.clients):
        start(ci)
    while not closed():
        with span("consensus", traced):
            sim.run_until(lambda: closed() or trace_due(),
                          timeout=IDLE_TIMEOUT_US)
        if closed():
            break
        if not trace_due():      # the simulation ran dry: nothing answers
            state["t_close"] = time.perf_counter()
            break
        tp = time.perf_counter()
        jax.profiler.start_trace(trace_dir, profiler_options=profile_options())
        tracing = jax.profiler.TraceAnnotation("bench_slice")
        tracing.__enter__()
        profiler_s += time.perf_counter() - tp
    # the rest of the closing reply's clump, delivered before any replica
    # executes again (the execution that ends this runs past the window)
    sim.run_until(lambda: not closed() or timed.started != state["started"]
                  or state["outstanding"] == 0, timeout=IDLE_TIMEOUT_US)
    compiles = counter.count - win["c0"]
    gc.callbacks.remove(gc_clock)
    timed.record = False
    exec_s = [d for d, a in zip(timed.exec_s, timed.exec_at)
              if a < state["t_close"]]
    if tracing is not None:
        tracing.__exit__(None, None, None)
        jax.profiler.stop_trace()
    sim.run_until(lambda: state["outstanding"] == 0, timeout=IDLE_TIMEOUT_US)
    answered = sum(r.tokens is not None for r in reqs)
    n_rep = len(server.cluster.replicas)
    sim.run_until(lambda: timed.calls >= n_rep * answered,
                  timeout=IDLE_TIMEOUT_US)
    return Window(requests=reqs, sessions=all_sessions, t0=win["t0"],
                  t_close=state["t_close"], exec_s=exec_s,
                  exec_at=timed.exec_at[:len(exec_s)],
                  executions=timed.calls, compiles=compiles,
                  profiler_s=profiler_s, trace_dir=trace_dir,
                  gc_pauses=tuple(gc_clock.pauses))
