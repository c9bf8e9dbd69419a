"""The benchmark's own tests: its phases at tiny sizes on the CPU (a
rehearsal, not a measurement), the reference against the program's
oracle, the trace reduction, and the checks' controls.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def tiny_cell(config: str, traffic: str, entries: int, record_packets: int,
              pool_records: int, end_to_end=("served_pps", "setup_s"),
              per_layer=()):
    """A cell of a configuration and a traffic mix under ``benchmark/``,
    cut to a size the CPU runs in seconds, reporting the named metrics
    (whether or not BENCHMARK.json lists the cell)."""
    from types import SimpleNamespace

    from benchmark import run

    bench_dir = os.path.join(ROOT, "benchmark")
    cfg = run.load_json(os.path.join(bench_dir, "configs", config + ".json"))
    tfc = run.load_json(os.path.join(bench_dir, "traffic", traffic + ".json"))
    cfg["table"]["entries"] = entries
    tfc.update(record_packets=record_packets, pool_records=pool_records,
               lead_s=0.3)
    name = f"{config}.{traffic}"
    bench = {
        "end_to_end": [{"name": m, "unit": "", "workloads": [name]}
                       for m in end_to_end],
        "per_layer": [{"name": m, "unit": "", "moves": end_to_end[0],
                       "workloads": [name]} for m in per_layer],
    }
    return SimpleNamespace(name=name, chips=1, bench=bench, config=cfg,
                           traffic=tfc)
