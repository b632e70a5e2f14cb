"""Share of the traced slice of the window in which no operation ran on the
chip: 1 - (union of the XLA op intervals) / slice, from the profiler trace."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct
