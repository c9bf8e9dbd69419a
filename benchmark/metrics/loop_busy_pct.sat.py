"""Daemon serving loop: share of the window inside the daemon loop's
calls of process_ring_once (the harness's spans around them)."""
import numpy as np

from benchmark import spans


def read(run):
    p = np.asarray([(a, b) for a, b, _n in run.rec.passes]).reshape(-1, 2)
    return spans.busy_pct(p, run.t0, run.t1)
