"""The program's spans and counters (``repro.spans``).

The recorder: off, it makes no profiler annotation and keeps nothing; on,
it keeps each span with its parent and ids, adds up counters and counts
the spans past its cap.  The program under it: the replicated token
server gives bit-identical replies, snapshots and simulated event counts
with recording on and off, and the two jits' names change nothing of
their HLO but the module's name.
"""

import jax
import jax.numpy as jnp
import pytest

from repro import spans
from repro.core.consensus import ConsensusConfig
from repro.launch import serve
from repro.runtime.server import ReplicatedServer


class Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``, counting."""

    made = []

    def __init__(self, name, **ids):
        self.made.append((name, dict(ids)))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def set_metadata(self, **ids):
        self.made.append(("set", dict(ids)))


@pytest.fixture
def annotations(monkeypatch):
    Annotations.made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotations)
    return Annotations.made


def test_off_makes_no_annotation_and_keeps_nothing(annotations):
    assert not spans.recording()
    with spans.span("a", x=1) as sp:
        sp.note(y=2)
        spans.count("n", 3)
    assert spans.span("b") is spans.span("c")      # one shared no-op
    assert annotations == []
    with spans.record() as rec:
        assert spans.recording()
    assert rec.spans == [] and rec.counters == {}
    assert not spans.recording()


def test_nesting_gives_parents_and_keeps_ids(annotations):
    with spans.record() as rec:
        with spans.span("outer", replica="r0", slot=7):
            with spans.span("mid") as sp:
                sp.note(session="s1")
                with spans.span("inner"):
                    pass
            with spans.span("second"):
                pass
        with spans.span("top"):
            pass
    names = [s[0] for s in rec.spans]
    assert names == ["outer", "mid", "inner", "second", "top"]
    assert [s[3] for s in rec.spans] == [-1, 0, 1, 0, -1]
    assert rec.spans[0][4] == {"replica": "r0", "slot": 7}
    assert rec.spans[1][4] == {"session": "s1"}
    for name, start, end, parent, _ in rec.spans:
        assert start <= end
        if parent >= 0:
            p = rec.spans[parent]
            assert p[1] <= start and end <= p[2]
    # every span is also a profiler annotation, with its ids
    assert annotations[0] == ("outer", {"replica": "r0", "slot": 7})
    assert ("set", {"session": "s1"}) in annotations
    assert len([a for a in annotations if a[0] != "set"]) == 5


def test_counters_add_up():
    with spans.record() as rec:
        for i in range(5):
            spans.count("steps")
            spans.count("tokens", i)
    assert rec.counters == {"steps": 5, "tokens": 10}


def test_cap_counts_the_spans_it_drops(monkeypatch):
    monkeypatch.setattr(spans, "CAP", 3)
    with spans.record() as rec:
        with spans.span("kept"):
            for _ in range(4):
                with spans.span("child"):
                    pass
    assert [s[0] for s in rec.spans] == ["kept", "child", "child"]
    assert rec.counters == {spans.DROPPED: 2}
    assert rec.spans[2][3] == 0


def test_one_record_at_a_time():
    with spans.record():
        with pytest.raises(RuntimeError):
            with spans.record():
                pass
    with spans.record() as rec:      # the failed attempt left it usable
        spans.count("x")
    assert rec.counters == {"x": 1}


# ---------------------------------------------------------------------------
# the program under the recorder
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def smoke_decode():
    """The program's own decode at the smoke size."""
    res = serve.run(serve.parse_args(
        ["--arch", "gemma3-1b", "--smoke", "--requests", "1", "--batch", "1",
         "--prompt-len", "8", "--gen", "2"]))
    return res.decode


def _serve(decode):
    """A seeded run of the replicated token server that passes checkpoint
    boundaries: replies, snapshots, simulated events."""
    server = ReplicatedServer.build(decode, cfg=ConsensusConfig(
        f=1, f_m=1, max_request_bytes=4096, window=4))
    client = server.cluster.new_client()
    replies = [server.generate(client, f"s{r % 3}", [r, r + 5, 7], 2)
               for r in range(10)]
    return (replies, [x.app.snapshot() for x in server.cluster.replicas],
            server.cluster.sim.events_processed)


def test_recording_leaves_the_served_run_bit_identical(smoke_decode):
    off = _serve(smoke_decode)
    with spans.record() as rec:
        on = _serve(smoke_decode)
    assert on == off
    names = {s[0] for s in rec.spans}
    assert {"replica.execute", "app.apply", "serve.prefill", "serve.step",
            "serve.sync", "consensus.checkpoint", "app.snapshot"} <= names
    assert rec.counters["serve.prefills"] == 30      # 10 requests, 3 replicas
    # 2 tokens a request: the prefill's and one step of the decode loop
    assert rec.counters["serve.decode_steps"] == 30
    assert rec.counters["serve.decode_loops"] == 30
    assert rec.counters["consensus.checkpoints"] == 6   # slots 4 and 8
    assert spans.DROPPED not in rec.counters


def test_named_jits_change_only_the_module_name():
    f = lambda a, b: jnp.tanh(a @ b) + 1    # noqa: E731
    x = jnp.ones((8, 8))
    plain = jax.jit(f).lower(x, x).as_text()
    named = serve.jit_as("decode_step", f).lower(x, x).as_text()
    assert "@jit_decode_step" in named and "@jit_decode_step" not in plain
    assert named.replace("jit_decode_step", "jit_f") == \
        plain.replace("jit__lambda", "jit_f")
