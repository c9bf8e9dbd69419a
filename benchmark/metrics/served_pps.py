"""Packets whose verdicts were delivered inside the window, per second
of the window."""
import numpy as np


def read(run):
    d = run.recs.deliver
    inside = (d >= run.t0) & (d < run.t1)
    return float(np.sum(run.recs.packets[inside])) / run.seconds
