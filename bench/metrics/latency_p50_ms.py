"""Median latency, request to f+1-matched reply, of every request the
window completed (host clock)."""

import numpy as np


def read(run):
    lat = [r.t_done - r.t_submit for r in run.window.done()]
    return float(np.percentile(lat, 50)) * 1e3 if lat else None
