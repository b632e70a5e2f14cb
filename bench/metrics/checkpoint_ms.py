"""Host time of the consensus checkpoints in the window, every replica's,
per committed request: the program's ``consensus.checkpoint`` spans, which
snapshot the session table twice and fingerprint it once (host clock);
None where the run recorded no spans."""

from harness import program


def read(run):
    spans = getattr(run, "spans", None)
    committed = sum(r.tokens is not None for r in run.window.done())
    if spans is None or not committed:
        return None
    return sum(c.seconds for c in program.checkpoints(spans)) \
        / committed * 1e3
