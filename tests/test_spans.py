"""Program spans of a daemon run with --trace, read back from an
in-process jax.profiler trace: one span per phase of each loop turn
(``infw.loop.*``), one per step of each ring record (``infw.ring.*``)
with the record's seq, none without --trace; and the ``inflight``
histogram stage of the ring path."""
import glob
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from infw import testing
from infw.backend.base import ClassifyOutput, PendingClassify
from infw.daemon import Daemon
from infw.flow import FlowConfig
from infw.ring import IngestRing

LOOP_SPANS = ("infw.loop.wait", "infw.loop.control", "infw.loop.ring",
              "infw.loop.ingest", "infw.loop.maintenance")
RECORD_SPANS = ("infw.ring.pop", "infw.ring.h2d", "infw.ring.launch",
                "infw.ring.readback", "infw.ring.finalize", "infw.ring.sink")
RECORDS = 4


def _program_spans(trace_dir):
    """[(name, start_ns, end_ns, seq or None)] of the infw.* host events."""
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for ln in plane.lines:
            for ev in ln.events:
                if ev.name.startswith("infw."):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats).get("seq")))
    return out


def _serve_traced(tmp_path, trace: bool):
    """A resident daemon serving RECORDS ring records from its own loop,
    under a profiler trace; returns (program spans, pushed seqs)."""
    ringp = str(tmp_path / "ingest.ring")
    daemon = Daemon(
        state_dir=str(tmp_path), node_name="n1", backend="tpu",
        resident=True, ring=ringp, metrics_port=0, health_port=0,
        file_poll_interval_s=0.05, flow_table=FlowConfig.make(entries=512),
        trace=trace,
    )
    try:
        tabs = testing.random_tables_fast(
            np.random.default_rng(5), n_entries=64, width=4,
            v6_fraction=0.3, ifindexes=(2, 3),
        )
        clf = daemon.syncer._factory()
        clf.load_tables(tabs)
        daemon.syncer._classifier = clf
        batch = testing.random_batch_fast(np.random.default_rng(6), tabs, 64)
        wire, v4 = batch.pack_wire_subset(np.arange(64, dtype=np.int64))
        prod = IngestRing.attach(ringp)
        # compile outside the trace
        prod.push(wire, v4_only=v4)
        daemon.process_ring_once()
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            seqs = [prod.push(wire, v4_only=v4) for _ in range(RECORDS)]
            daemon.start()
            deadline = time.monotonic() + 60
            while (daemon.ingest_ring.tail < daemon.ingest_ring.head
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            time.sleep(0.3)  # whole loop turns after the records
            daemon.stop()
            for t in daemon._threads:
                t.join(timeout=10)
        assert daemon.ingest_ring.tail == daemon.ingest_ring.head
        prod.close()
        return _program_spans(trace_dir), seqs
    finally:
        daemon.stop()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    return _serve_traced(tmp_path_factory.mktemp("traced"), trace=True)


def test_traced_daemon_writes_every_program_span(traced):
    spans, _seqs = traced
    names = {s[0] for s in spans}
    for name in LOOP_SPANS + RECORD_SPANS:
        assert name in names, name


def test_record_spans_carry_seq_inside_loop_ring(traced):
    spans, seqs = traced
    rings = [s for s in spans if s[0] == "infw.loop.ring"]
    for name in RECORD_SPANS:
        got = [s for s in spans if s[0] == name]
        # a pop that finds the ring empty has no seq
        carried = sorted(s[3] for s in got if s[3] is not None)
        assert carried == sorted(seqs), name
        for s in got:
            assert any(r[1] <= s[1] and s[2] <= r[2] for r in rings), s


def test_untraced_daemon_writes_no_program_span(tmp_path):
    spans, _seqs = _serve_traced(tmp_path, trace=False)
    assert spans == []


class _FakeClassifier:
    """Ring records through classify_async; the first record's result
    takes ``slow_s``."""

    def __init__(self, slow_s: float) -> None:
        self.slow_s = slow_s
        self.calls = 0

    def classify_async(self, batch, apply_stats=True):
        delay = self.slow_s if self.calls == 0 else 0.0
        self.calls += 1
        n = len(batch.kind)

        def materialize():
            time.sleep(delay)
            return ClassifyOutput(results=np.zeros(n, np.uint32),
                                  xdp=np.zeros(n, np.int32),
                                  stats_delta=np.zeros((1024, 4), np.int64))

        return PendingClassify(materialize)

    def close(self) -> None:
        pass


def test_queued_record_is_charged_to_inflight(tmp_path):
    """A ring record launched behind a slow one waits for that one's
    drain: the wait is charged to ``inflight``, and ``materialize`` keeps
    only each record's own result()."""
    ringp = str(tmp_path / "ingest.ring")
    daemon = Daemon(state_dir=str(tmp_path), node_name="n1", backend="cpu",
                    ring=ringp, metrics_port=0, health_port=0,
                    pipeline_depth=2, trace=True)
    try:
        daemon.syncer._classifier = _FakeClassifier(slow_s=0.3)
        prod = IngestRing.attach(ringp)
        wire = np.zeros((8, 7), np.uint32)
        for _ in range(2):
            prod.push(wire)
        assert daemon.process_ring_once() == 16
        h = daemon.tracer.histograms.values()
        assert h["materialize"]["count"] == h["inflight"]["count"] == 2
        # charged from launch to result, the slow result() would count
        # for both records
        assert 0.3e6 <= h["materialize"]["sum_us"] < 0.45e6
        assert h["inflight"]["sum_us"] >= 0.25e6
        prod.close()
    finally:
        daemon.stop()
