"""Kernels: device busy milliseconds (the union of every operation's
interval in the profiled stretch) per million packets whose verdicts
were delivered in that stretch."""
import numpy as np


def read(run):
    if run.trace is None:
        return None
    d = run.recs.deliver
    inside = (d >= run.prof[0]) & (d < run.prof[1])
    packets = float(np.sum(run.recs.packets[inside]))
    if packets <= 0:
        return None
    return run.trace["busy_s"] * 1e3 / (packets / 1e6)
