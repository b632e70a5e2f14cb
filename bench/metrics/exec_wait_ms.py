"""Mean time one replica's execution of one request in the window waits
for the device: the program's ``serve.sync`` spans (each ``int(tok[0])``
of the decode) inside its ``replica.execute`` span (host clock); None
where the run recorded no spans."""

from harness import program


def read(run):
    ex = program.executions(getattr(run, "spans", None) or [])
    return sum(e.wait_s for e in ex) / len(ex) * 1e3 if ex else None
