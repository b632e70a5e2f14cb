"""Host time of the window outside replica execution, per committed
request: consensus, the simulated network and the harness's callbacks.
The profiler's start inside a traced window is left out."""


def read(run):
    w = run.window
    committed = sum(r.tokens is not None for r in w.done())
    if not committed:
        return None
    return (w.seconds - w.profiler_s - sum(w.exec_s)) / committed * 1e3
