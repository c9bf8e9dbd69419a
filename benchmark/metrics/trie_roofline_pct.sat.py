"""Kernels: the trie walk's floor bytes (``benchmark/trie_floor.py``: the
root slot, a node row per level down to the plain reference's longest
match, the matched entry's joined row) for the packets delivered in the
profiled stretch, over the chip's peak memory bandwidth, as a share of
the device seconds of the walk's stages."""
import numpy as np

from benchmark import stages, trie_floor


def read(run):
    if run.peak is None:
        return None
    red = stages.reduce_run(run)
    if red is None or red["stages"] is None:
        return None
    s = red["stages"]["stage_s"]
    walk_s = sum(s.get(k, 0.0) for k in trie_floor.WALK_STAGES)
    if walk_s <= 0:
        return None
    r, inp = run.recs, run.inputs
    inside = (r.deliver >= run.prof[0]) & (r.deliver < run.prof[1])
    pool = r.pool[inside]
    per_record = trie_floor.record_bytes(inp.table, inp.pool, inp.record_packets,
                                         inp.pool_records)
    nbytes = float(np.sum(per_record[pool[pool >= 0]]))
    if nbytes <= 0:
        return None
    return 100.0 * nbytes / run.peak["hbm_bytes_per_s"] / walk_s
