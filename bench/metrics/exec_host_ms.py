"""Mean host time of one replica's execution of one request in the window,
outside its waits for the device: request framing, history copies, the
dispatch of prefill and of each decode step, the eager argmax.  Read from
the program's ``replica.execute`` spans less the ``serve.sync`` spans
inside them (host clock); None where the run recorded no spans."""

from harness import program


def read(run):
    ex = program.executions(getattr(run, "spans", None) or [])
    return sum(e.host_s for e in ex) / len(ex) * 1e3 if ex else None
