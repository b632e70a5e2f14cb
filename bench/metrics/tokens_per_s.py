"""Generated tokens of the f+1-matched replies the window completed, over
the window's wall seconds."""


def read(run):
    w = run.window
    return sum(len(r.tokens) for r in w.done() if r.tokens) / w.seconds
