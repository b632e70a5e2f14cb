"""Mean host time of one replica's execution of one request in the window:
the decode the token server's app calls (prefill, decode steps, syncs)."""


def read(run):
    e = run.window.exec_s
    return sum(e) / len(e) * 1e3 if e else None
