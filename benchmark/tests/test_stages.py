"""The program-span and stage reductions (``stages.py``): on made-up
events, on the small trace of ``record_trace.py`` (no program spans, no
resident step: no reading), and on a trace of the daemon's own loop
recorded on a v5e by ``record_resident.py``."""
import os
import shutil
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import run, spans, stages

DATA = os.path.join(os.path.dirname(__file__), "data")
SMALL = os.path.join(DATA, "small.xplane.pb")
RESIDENT = os.path.join(DATA, "resident.xplane.pb")
READERS = ("flow_ms_per_mpkt.sat", "lpm_ms_per_mpkt.sat",
           "rule_scan_ms_per_mpkt.sat", "poll_wait_pct.sat",
           "readback_us_per_record.sat", "finalize_us_per_record.sat")


def test_stage_seconds_joins_ops_by_time_then_name():
    ops = [("a", 0.0, 10.0), ("b", 10.0, 5.0), ("x", 15.0, 1.0),
           ("a", 30.0, 10.0), ("c", 42.0, 2.0), ("a", 60.0, 7.0)]
    modules = [("jit_resident_step(1)", 0.0, 20.0),
               ("jit_resident_step(1)", 30.0, 45.0),
               ("jit_other(2)", 60.0, 70.0)]
    maps = {"jit_resident_step(1)": {"a": "lpm", "b": "flow_probe", "c": "lpm"}}
    red = stages.stage_seconds(ops, modules, maps)
    assert red["stage_s"] == pytest.approx(
        {"lpm": 22e-9, "flow_probe": 5e-9, stages.UNMAPPED: 1e-9})
    assert red["resident_op_s"] == pytest.approx(28e-9)
    assert red["resident_busy_s"] == pytest.approx(28e-9)
    assert red["device_busy_s"] == pytest.approx(35e-9)
    assert red["top_ops"][0] == ["a", "lpm", pytest.approx(20e-9)]
    assert stages.stage_seconds(ops, modules, {}) is None


def test_idle_split_by_innermost_open_span():
    ops = [("a", 0.0, 10.0), ("a", 20.0, 10.0), ("a", 100.0, 10.0),
           ("a", 200.0, 10.0)]
    prog = [("infw.loop.ring", 0.0, 60.0, None),
            ("infw.ring.readback", 12.0, 18.0, 3),
            ("infw.loop.wait", 60.0, 150.0, None)]
    red = stages.idle_by_program_span(ops, prog)
    # gaps [10, 20] mid 15: readback inside ring; [30, 100] mid 65: wait;
    # [110, 200] mid 155: none
    assert red == pytest.approx({"infw.loop.wait": 70e-9,
                                 stages.UNSPANNED: 90e-9,
                                 "infw.ring.readback": 10e-9})
    assert stages.idle_by_program_span(ops, []) is None
    assert stages.whole_turns(prog) is None
    assert stages.whole_turns(prog + [("infw.loop.wait", 300.0, 400.0, None)]) == (60.0, 300.0)


def test_hlo_from_a_trace():
    mods = stages.hlo_modules(SMALL, wanted=("jit__lambda",))
    assert list(mods) == ["jit__lambda(18081562644795022830)"]
    text = next(iter(mods.values()))
    assert 'op_name="jit(<lambda>)/dot_general"' in text
    assert stages.hlo_modules(SMALL) == {}


def fake_run(tmp_path, path):
    """What the readers see of a traced run whose trace is ``path``: every
    record delivered inside the profiled stretch."""
    trace_dir = tmp_path / "trace"
    trace_dir.mkdir()
    shutil.copy(path, trace_dir / "t.xplane.pb")
    n = 100
    return SimpleNamespace(
        traced=True, trace_dir=str(trace_dir), prof=[0.0, 1.0],
        recs=SimpleNamespace(deliver=np.full(n, 0.5),
                             packets=np.full(n, 4096, np.int64)))


def test_no_program_no_reading(tmp_path):
    """A trace without the program's spans or a resident step, as the
    program before them gives: no reduction, no reading."""
    assert stages.reduce_file(SMALL) is None
    r = fake_run(tmp_path, SMALL)
    assert [run.read_metric(m, r) for m in READERS] == [None] * len(READERS)


@pytest.fixture(scope="module")
def resident():
    ops, modules, prog, length = stages.read_events(RESIDENT)
    return ops, modules, prog, stages.reduce_file(RESIDENT)


def test_recorded_stages_add_up_to_resident_modules(resident):
    ops, modules, _prog, red = resident
    # brute force: every op that starts inside a resident module
    res = [m for m in modules if stages.base_name(m[0]) in stages.RESIDENT_MODULES]
    assert res
    inside = sum(d for _n, s, d in ops if any(a <= s < b for _m, a, b in res))
    st = red["stages"]
    assert sum(st["stage_s"].values()) == pytest.approx(st["resident_op_s"], rel=1e-12)
    assert st["resident_op_s"] == pytest.approx(inside * 1e-9, rel=1e-12)
    # the step's ops all have a stage, and the named stages hold them
    assert stages.UNMAPPED not in st["stage_s"]
    assert {"lpm", "rule_scan", "flow_probe", "flow_insert"} <= set(st["stage_s"])
    assert st["stage_s"].get("other", 0.0) <= 0.05 * st["resident_op_s"]


def test_recorded_wait_gaps_are_found(resident):
    ops, _modules, prog, red = resident
    waits = [p for p in prog if p[0] == stages.WAIT_SPAN]
    assert len(waits) >= 2
    st = np.array([o[1] for o in ops])
    gaps = spans.gaps(spans.union(st, st + np.array([o[2] for o in ops])))
    mids = gaps.mean(axis=1)
    found = 0.0
    for _n, a, b, _s in waits:
        hit = (mids >= a) & (mids < b)
        # the records' device work stops before each wait: one long gap
        # holds each wait span whole
        assert hit.sum() == 1, (a, b)
        lo, hi = gaps[hit][0]
        assert lo <= a and b <= hi
        found += (hi - lo) * 1e-9
    idle = red["idle_by_program_span"]
    assert idle[stages.WAIT_SPAN] == pytest.approx(found, rel=1e-9)
    assert idle.get(stages.UNSPANNED, 0.0) <= 0.01 * sum(idle.values())


def test_recorded_readers_read(tmp_path):
    r = fake_run(tmp_path, RESIDENT)
    got = {m: run.read_metric(m, r) for m in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert got["poll_wait_pct.sat"] < 100.0
