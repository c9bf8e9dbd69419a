"""Daemon serving loop: share of the whole loop turns in the trace that
the loop spent in its poll wait (the union of the program's
infw.loop.wait spans, from the first recorded wait's start to the
last's)."""
import numpy as np

from benchmark import spans, stages


def read(run):
    red = stages.reduce_run(run)
    if red is None:
        return None
    turns = stages.whole_turns(red["spans"])
    if turns is None:
        return None
    w = np.array([p[1:3] for p in red["spans"] if p[0] == stages.WAIT_SPAN])
    return spans.busy_pct(w, *turns)
