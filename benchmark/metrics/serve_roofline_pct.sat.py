"""Kernels: the bytes the served packets need read or written, by the
configuration's floor model (wire words, a 4-byte verdict, and the
matched entry's rule rows at 10 bytes a rule), over the chip's peak
memory bandwidth, as a share of the device's busy time in the profiled
stretch."""
import numpy as np


def read(run):
    if run.trace is None or run.peak is None:
        return None
    r = run.recs
    inside = (r.deliver >= run.prof[0]) & (r.deliver < run.prof[1])
    pool = r.pool[inside]
    nbytes = float(np.sum(run.check.rec_bytes[pool[pool >= 0]]))
    if nbytes <= 0 or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / run.trace["busy_s"]
