"""The harness's phases, run as functions at tiny sizes on the CPU: a
rehearsal of the control flow, not a measurement.  ``main()`` itself
refuses the CPU."""
import json
import os
import shutil
import subprocess
import sys
import time

from benchmark import run
from conftest import ROOT, tiny_cell


def rehearse(cell, tmp_path, seconds=1.0, traced=False, **kw):
    work = str(tmp_path / "work")
    os.makedirs(work)
    r = run.run_cell(cell, 2**31 + 12345, seconds, traced, work,
                     t_begin=time.monotonic(), warm_timeout=300.0, **kw)
    return run.result_line(cell, r, 2**31 + 12345)


def test_saturating_cell(tmp_path):
    cell = tiny_cell("refcap-1k", "hit-sat", entries=200, record_packets=256,
                     pool_records=4)
    out, info = rehearse(cell, tmp_path)
    assert out["correct"] is True, info
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"served_pps", "setup_s"}
    assert out["metrics"]["served_pps"]["value"] > 0
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def test_trie_cell_traced(tmp_path):
    """The trie path (entries past the dense limit), and the traced run's
    per-layer metrics that need no device plane."""
    cell = tiny_cell("refcap-1k", "hit-sat", entries=5000, record_packets=256,
                     pool_records=8,
                     per_layer=("loop_busy_pct.sat", "ring_blocked_pct.sat",
                                "dispatch_us_per_record.sat",
                                "device_idle_pct.sat", "device_ms_per_mpkt.sat"))
    out, info = rehearse(cell, tmp_path, seconds=1.5, traced=True)
    assert out["correct"] is True, info
    assert out["failed"] == 0
    assert set(out["metrics"]) == {"loop_busy_pct.sat", "ring_blocked_pct.sat",
                                   "dispatch_us_per_record.sat"}
    # the CPU has no device plane: no busy time, no breakdown
    assert "breakdown" not in out and "busy_s" not in out["device"]


def test_main_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
                        "--workload", "refcap-1k.hit-sat", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_refuses_without_program(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    holds no system to measure: no result."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmark"), tmp_path / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload",
                        "refcap-1k.hit-sat", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=120)
    assert p.returncode != 0 and p.stdout == ""


def test_benchmark_json_is_data():
    """Every cell, configuration, traffic mix and metric named in
    BENCHMARK.json has its file; the harness keeps no list of them."""
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for c in bench["configs"]:
        assert os.path.exists(os.path.join(ROOT, c["file"]))
    for w in bench["workloads"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "traffic",
                                           w["traffic"] + ".json"))
        for traced in (False, True):
            assert run.cell_metrics(bench, w["name"], traced)
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert os.path.exists(os.path.join(ROOT, "benchmark", "metrics",
                                           m["name"] + ".py")), m["name"]
