"""The trace reduction: interval arithmetic, and the reduction of a small
trace recorded on a v5e by ``record_trace.py``."""
import os

import numpy as np
import pytest

from benchmark import run, spans, trace

DATA = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")


def test_union_and_cover():
    u = spans.union([5, 0, 1, 10, 12], [6, 2, 3, 11, 14])
    np.testing.assert_array_equal(u, [[0, 3], [5, 6], [10, 11], [12, 14]])
    assert spans.covered(u, 1, 13) == 2 + 1 + 1 + 1
    np.testing.assert_array_equal(spans.gaps(u), [[3, 5], [6, 10], [11, 12]])
    assert spans.busy_pct(np.array([[0.0, 1.0], [0.5, 2.0]]), 0.0, 4.0) == 50.0


def test_reduce_names_gaps_by_open_span():
    device = {"/device:TPU:0": [("fusion", 0.0, 10.0), ("fusion", 5.0, 10.0),
                                ("copy", 40.0, 5.0), ("fusion", 100.0, 20.0)]}
    host = {"sink": [(20.0, 35.0)], "pass": [(0.0, 16.0), (38.0, 50.0)]}
    red = trace.reduce(device, host, (("verdict sink", "sink"), ("loop pass", "pass")),
                       "poll sleep")
    assert red["busy_s"] == pytest.approx(40e-9)
    assert red["device_ops"][0] == ["fusion", pytest.approx(40e-9)]
    # gaps: [15, 40] middle 27.5 inside the sink; [45, 100] middle 72.5
    assert red["idle_gaps"] == [["poll sleep", pytest.approx(55e-9)],
                                ["verdict sink", pytest.approx(25e-9)]]
    assert trace.reduce({"/device:TPU:0": []}, host, (), "x") is None


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_trace():
    device, host = trace.read_events(DATA, [run.SINK_SPAN, run.PASS_SPAN])
    assert list(device) == ["/device:TPU:0"]
    ev = device["/device:TPU:0"]
    # brute force: busy time is the measure of the union of op intervals
    st = np.array([e[1] for e in ev])
    en = st + np.array([e[2] for e in ev])
    grid = np.arange(st.min(), en.max(), 1000.0)
    inside = np.zeros(len(grid), bool)
    for a, b in zip(st, en):
        inside |= (grid >= a) & (grid < b)
    red = trace.reduce(device, host, run.IDLE_LABELS, run.IDLE_OTHER)
    assert red["busy_s"] == pytest.approx(inside.sum() * 1e-6, rel=0.02)
    # record_trace.py: two 35 ms idle stretches opened by a sink span, two
    # 40 ms stretches outside any span, all far longer than other gaps
    longest = red["idle_gaps"][:4]
    assert sorted(n for n, _s in longest) == ["poll sleep", "poll sleep",
                                              "verdict sink", "verdict sink"]
    for name, s in longest:
        assert s == pytest.approx(0.035 if name == "verdict sink" else 0.04, abs=0.006)
    assert len(host[run.PASS_SPAN]) == 5 and len(host[run.SINK_SPAN]) == 2
