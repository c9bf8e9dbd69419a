"""The checks that decide ``correct`` fail when the timed path is broken
underneath: once per fault the cells can have, and for the control
(verdicts delivered as their action byte alone, the rule id dropped).
The harness's look for a chip is skipped; the rest of a run is driven
at a tiny size on the CPU."""
import os
import time

import numpy as np
import pytest

from benchmark import run
from conftest import tiny_cell


def wrap_prepared(change):
    """patch(daemon): the classifier's materialized output passes
    through ``change(out) -> out``; with ``change=None`` the launch is
    made without applying statistics."""
    def patch(daemon):
        from infw.backend.base import ClassifyOutput, PendingClassify

        clf = daemon.syncer.classifier
        inner = clf.classify_prepared

        def classify_prepared(plan, apply_stats=True):
            if change is None:
                return inner(plan, apply_stats=False)
            pend = inner(plan, apply_stats=apply_stats)

            def materialize():
                out = pend.result()
                res = change(np.array(out.results, np.uint32))
                return ClassifyOutput(results=res, xdp=out.xdp,
                                      stats_delta=out.stats_delta)
            return PendingClassify(materialize)

        clf.classify_prepared = classify_prepared
    return patch


def flip_one(res):
    res[len(res) // 3] ^= 0x100
    return res


def leave_half(res):
    res[len(res) // 2:] = 0
    return res


FAULTS = {
    "answer altered where produced": (
        {"patch": wrap_prepared(flip_one)}, "verdict_mismatches"),
    "half of each record left out": (
        {"patch": wrap_prepared(leave_half)}, "verdict_mismatches"),
    "statistics state left unchanged": (
        {"patch": wrap_prepared(None)}, "stats_mismatches"),
    "a record's verdicts never delivered": (
        {"alter": lambda seq, res: None if seq == 6 else res}, "undelivered_records"),
    "control: action byte only": (
        {"alter": lambda seq, res: np.asarray(res, np.uint32) & 0xFF},
        "verdict_mismatches"),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_makes_run_incorrect(fault, tmp_path, monkeypatch):
    monkeypatch.setattr(run, "GRACE_S", 3.0)
    kw, number = FAULTS[fault]
    cell = tiny_cell("refcap-1k", "hit-sat", entries=200, record_packets=256,
                     pool_records=4)
    work = str(tmp_path / "work")
    os.makedirs(work)
    r = run.run_cell(cell, 77, 1.0, False, work, t_begin=time.monotonic(),
                     warm_timeout=300.0, **kw)
    out, info = run.result_line(cell, r, 77)
    assert out["correct"] is False, info
    assert out["checks"][number]["value"] > out["checks"][number]["limit"]
