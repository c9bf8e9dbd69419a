"""Ingest ring: share of the window the producer spent blocked on a full
ring (its attach's ring_blocked_us_total across the window)."""
import numpy as np


def read(run):
    r = run.recs
    order = np.argsort(r.commit)
    t, b = r.commit[order], r.blocked_us[order]
    ok = ~np.isnan(t)
    t, b = t[ok], b[ok]
    before = b[t < run.t0]
    inside = b[(t >= run.t0) & (t < run.t1)]
    if not len(inside):
        return None
    start = before[-1] if len(before) else 0.0
    return 100.0 * (inside[-1] - start) * 1e-6 / run.seconds
