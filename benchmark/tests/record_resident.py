"""Record the profiler trace of the daemon's own loop that
``test_stages.py`` reduces.

    python benchmark/tests/record_resident.py OUT_DIR

Run on the chip.  A short traced run of the ``refcap-1k.hit-sat`` cell
with records of 4,096 packets and at most two records a loop pass, so
that the trace holds a few whole loop turns (pass, then the 0.2 s poll
wait) in a small file: the daemon's program spans (``--trace``), the
resident step's device ops, and the step's optimized HLO (about 270 KB
of the file).  The profiler's Python function tracer is off and its host
tracer keeps annotations only: their events would take most of the
file, and nothing here reads them.  Copies the
trace to ``OUT_DIR/resident.xplane.pb`` and prints its planes and the
program's stage reduction; copy the file to
``benchmark/tests/data/resident.xplane.pb``.
"""
import json
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run, stages, trace  # noqa: E402

CELL = "refcap-1k.hit-sat"
RECORD_PACKETS = 4096
PASS_PACKETS = 2 * RECORD_PACKETS
SECONDS = 1.5
TRACE_S = 0.6


def _annotations_only() -> None:
    import jax

    start = jax.profiler.start_trace

    def start_trace(log_dir, **_kw):
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        return start(log_dir, profiler_options=opts)

    jax.profiler.start_trace = start_trace


def record(out: str, seed: int = 2**31 + 24) -> str:
    _annotations_only()
    cell = run.load_cell(CELL)
    cell.traffic.update(record_packets=RECORD_PACKETS, pool_records=4, lead_s=0.5)
    cell.config["daemon_args"] = cell.config["daemon_args"] + [
        "--max-tick-packets", str(PASS_PACKETS)]
    run.TRACE_S = TRACE_S
    work = tempfile.mkdtemp(prefix="infw-record-")
    try:
        r = run.run_cell(cell, seed, SECONDS, True, work, t_begin=time.monotonic())
        os.makedirs(out, exist_ok=True)
        dst = os.path.join(out, "resident.xplane.pb")
        shutil.copy(trace.find_xplane(r.trace_dir), dst)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return dst


def main(out: str) -> int:
    import jax
    from jax.profiler import ProfileData

    if jax.default_backend() != "tpu":
        print("record_resident: not on a TPU", file=sys.stderr)
        return 2
    path = record(out)
    print("xplane", path, os.path.getsize(path))
    for plane in ProfileData.from_file(path).planes:
        lines = [(ln.name, len(list(ln.events))) for ln in plane.lines]
        print("plane", plane.name, lines)
    red = stages.reduce_file(path)
    print("stages", json.dumps({k: v for k, v in (red or {}).items() if k != "spans"}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
