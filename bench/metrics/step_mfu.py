"""Model FLOPs of the window's committed work, over the window's seconds
times the chip's peak bf16 FLOP/s.

Every replica executes every request, so each committed request counts once
per replica.  A request needs the forwards of the tokens that entered its
session's context since the last forward, one per generated token after the
first, and the output head once per generated token; re-prefill of the
history and the trailing decode step are recomputation and do not count.
The profiler's start inside a traced window is left out of the seconds.
The FLOPs of a request are its family's ``request_flops``."""


def read(run):
    if run.peaks is None:
        return None
    w = run.window
    flops = sum(run.family.request_flops(run.sizes, r.processed, r.history,
                                         len(r.tokens))
                for r in w.done() if r.tokens)
    secs = w.seconds - w.profiler_s
    return 100.0 * run.replicas * flops / (secs * run.peaks["bf16_flops_per_s"])
