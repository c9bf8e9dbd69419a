"""The per-node daemon: watch desired state, program the dataplane,
classify ingest traffic, serve metrics, stream deny events.

Equivalent of the reference daemon binary
(/root/reference/cmd/daemon/daemon.go): env contract NODE_NAME /
NAMESPACE / POLL_PERIOD_SECONDS / ENABLE_LPM_LOOKUP_DBG (:69-84),
loopback-bound metrics + health endpoints (:57-58, ports 39301/39300),
namespace-scoped state watching (:91-95), wiring of the NodeState
controller and the statistics poller (:96-130).

TPU-native deltas:
- ``--backend tpu|cpu`` selects the classifier (Pallas/XLA device path vs
  the native C++ reference) behind the same syncer boundary.
- Desired state arrives either through an in-process Store watch or a
  **state directory** (``<state-dir>/nodestates/<node>.json``) so external
  controllers/tools can drive a running daemon exactly like applying a CR;
  file deletion = CR deletion.
- Packet ingest is file-based replay: drop a frames file (see
  ``write_frames_file``) into ``<state-dir>/ingest/``; verdict summaries
  land in ``<state-dir>/out/``; deny events stream to the event log (the
  role of the syslog sidecar, cmd/syslog/syslog.go).
- ``ENABLE_LPM_LOOKUP_DBG`` fills a bounded in-memory key buffer served at
  ``/debug/lookup-keys`` — the analogue of the 16384-entry debug hash map
  (bpf/ingress_node_firewall_kernel.c:59-64,214-216) inspectable with
  bpftool.
"""
from __future__ import annotations

import argparse
import json
import logging
import mmap
import os
import signal
import struct
import sys
import threading
import time
from collections import deque
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ._threads import spawn
from . import platform as platform_mod
from .compiler import CompileError
from .constants import DENY, KIND_IPV6, KIND_OTHER, MAX_TARGETS
from .interfaces import InterfaceError, InterfaceRegistry, default_registry
from .nodestate_controller import NodeStateReconciler
from .obs.events import EventRing, EventsLogger, emit_deny_events
from .obs.pcap import FramesBuf, parse_frames_buf
from .obs.statistics import Registry as MetricsRegistry, Statistics
from . import packets as packets_mod
from .packets import PacketBatch, expand_wire_v4
from .schema import validate_nodestate_schema
from .spec import IngressNodeFirewallNodeState
from .store import InMemoryStore
from .syncer import DataplaneSyncer, SyncError

log = logging.getLogger("infw.daemon")

DEFAULT_METRICS_PORT = 39301   # cmd/daemon/daemon.go:57
DEFAULT_HEALTH_PORT = 39300    # cmd/daemon/daemon.go:58
DEBUG_MAP_ENTRIES = 16384      # kernel.c:63 debug map max_entries
DEFAULT_INGEST_CHUNK = 1 << 16     # packets per in-flight sub-batch
# In-flight async classify jobs.  Deeper pipelining lets more jobs
# enqueue before the first drain blocks, overlapping device transfers
# with the link's round-trip latency: measured 1.9x sustained ingest vs
# depth 4 on a ~100ms-RTT link (bench config: 1M-row jobs, where one
# job's wire buffer is ~16-28MB; at this default chunk of 64K rows a job
# is <=1.8MB, so memory is trivial either way).
DEFAULT_PIPELINE_DEPTH = 16
DEFAULT_MAX_TICK_PACKETS = 4 << 20   # parse-ahead bound for one ingest tick
# Double-buffered ingestion: how many UPCOMING jobs are host-packed +
# codec-encoded with their H2D copy already started while earlier jobs'
# classifies run (prepare_packed).  2 keeps one transfer in flight ahead
# of the compute at all times (classic double buffering) without holding
# more than ~2 chunks of extra pinned wire memory.
DEFAULT_H2D_STAGE_DEPTH = 2

_FRAMES_MAGIC = b"INFW1\n"
_FRAMES_MAGIC2 = b"INFW2\n"


# --- frames-file replay format ----------------------------------------------

def write_frames_file(path: str, frames: Sequence[bytes], ifindex) -> None:
    """v1 length-prefixed raw-frame container for ingest replay: per
    record a u32 ingress ifindex + u32 length + frame bytes."""
    if np.isscalar(ifindex):
        ifindex = [int(ifindex)] * len(frames)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(_FRAMES_MAGIC)
        f.write(struct.pack("<I", len(frames)))
        for idx, frame in zip(ifindex, frames):
            f.write(struct.pack("<II", int(idx), len(frame)))
            f.write(frame)
    os.replace(tmp, path)


def write_frames_file_v2(path: str, fb: FramesBuf) -> None:
    """v2 columnar container: u32 count, then the ifindex and length
    arrays, then all frame bytes concatenated.  Written and read with
    three bulk I/O calls — the replay-scale format (10M frames = two
    40MB arrays + one buffer, no per-record Python)."""
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(_FRAMES_MAGIC2)
        f.write(struct.pack("<I", len(fb)))
        f.write(np.ascontiguousarray(fb.ifindex, "<u4").tobytes())
        f.write(np.ascontiguousarray(fb.lengths, "<u4").tobytes())
        f.write(np.ascontiguousarray(fb.buf).tobytes())
    os.replace(tmp, path)


def read_frames_file(path: str) -> Tuple[List[bytes], List[int]]:
    with open(path, "rb") as f:
        magic = f.read(len(_FRAMES_MAGIC))
        if magic != _FRAMES_MAGIC:
            raise ValueError(f"{path}: not an infw frames file")
        (count,) = struct.unpack("<I", f.read(4))
        frames, ifindexes = [], []
        for _ in range(count):
            idx, length = struct.unpack("<II", f.read(8))
            frames.append(f.read(length))
            ifindexes.append(idx)
    return frames, ifindexes


def read_frames_any(path: str) -> FramesBuf:
    """Read either frames-file version into a FramesBuf.

    The v2 frame buffer is memory-mapped, not read: the parser (native,
    one linear pass) faults pages straight from the page cache with no
    intermediate copy of the (potentially multi-GB) payload, and the map
    lives only as long as the FramesBuf referencing it."""
    with open(path, "rb") as f:
        magic = f.read(len(_FRAMES_MAGIC2))
        if magic == _FRAMES_MAGIC2:
            (count,) = struct.unpack("<I", f.read(4))
            # Bound the declared count against the file size BEFORE
            # allocating: a corrupt header with count near 2^32 would
            # otherwise attempt multi-GB reads ahead of the truncation
            # check below.
            st_size = os.fstat(f.fileno()).st_size
            if 8 * count + f.tell() > st_size:
                raise ValueError(
                    f"{path}: v2 header count {count} exceeds file size"
                )
            ifindex = np.frombuffer(f.read(4 * count), "<u4")
            lengths = np.frombuffer(f.read(4 * count), "<u4")
            payload_off = f.tell()
            total = os.fstat(f.fileno()).st_size - payload_off
            if len(lengths) != count or total != int(
                lengths.astype(np.int64).sum()
            ):
                raise ValueError(f"{path}: truncated v2 frames file")
            if total:
                mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
                buf = np.frombuffer(mm, np.uint8, count=total, offset=payload_off)
            else:
                buf = np.zeros(0, np.uint8)
            return FramesBuf.from_lengths(buf, lengths, ifindex)
    if magic != _FRAMES_MAGIC:
        raise ValueError(f"{path}: not an infw frames file")
    frames, ifindexes = read_frames_file(path)
    return FramesBuf.from_frames(frames, ifindexes)


# --- debug lookup buffer (ENABLE_LPM_LOOKUP_DBG) -----------------------------

class DebugLookupBuffer:
    """Bounded record of the LPM lookup keys the dataplane constructed —
    the debug hash map (kernel.c:59-64) re-expressed host-side.  Keys are
    (ifindex, ip_words) per classified packet; capacity-bounded with
    overwrite of the oldest (the kernel map just stops inserting; a ring
    is strictly more useful and still bounded)."""

    def __init__(self, capacity: int = DEBUG_MAP_ENTRIES) -> None:
        self._lock = threading.Lock()
        self._buf: deque = deque(maxlen=capacity)

    def record_batch(self, batch: PacketBatch) -> None:
        ifx = np.asarray(batch.ifindex)
        if len(ifx) == 0:
            return
        words = np.asarray(batch.ip_words)
        # Build all row tuples in C (one column_stack + tolist) rather than
        # 5 int() conversions per packet in a Python loop.
        rows = np.column_stack([ifx.reshape(-1, 1), words.reshape(len(ifx), -1)])
        items = [(r[0], tuple(r[1:])) for r in rows.tolist()]
        with self._lock:
            self._buf.extend(items)

    def snapshot(self) -> List[Tuple[int, Tuple[int, int, int, int]]]:
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()


# --- classifier factories ----------------------------------------------------

def stats_from_results(results: np.ndarray, pkt_len: np.ndarray) -> np.ndarray:
    """Per-file statistics from host-resident verdicts — (MAX_TARGETS, 4)
    int64 [allow_pkts, allow_bytes, deny_pkts, deny_bytes], mirroring the
    device accumulation semantics (kernel.c:361-399: allow/deny only,
    ruleId < MAX_TARGETS).  Computed host-side so a device job that spans
    files never entangles one file's counters with another's exactly-once
    lifecycle."""
    action = results & 0xFF
    rid = (results >> 8).astype(np.int64)
    pl = np.asarray(pkt_len, np.int64)
    out = np.zeros((MAX_TARGETS, 4), np.int64)
    for col, act in ((0, 2), (2, 1)):  # ALLOW=2, DENY=1
        m = (action == act) & (rid < MAX_TARGETS)
        if m.any():
            r = rid[m]
            out[:, col] = np.bincount(r, minlength=MAX_TARGETS)[:MAX_TARGETS]
            out[:, col + 1] = np.bincount(
                r, weights=pl[m], minlength=MAX_TARGETS
            )[:MAX_TARGETS].astype(np.int64)
    return out


def make_classifier_factory(backend: str, fused_deep: Optional[bool] = None,
                            wire_codec: Optional[str] = None,
                            mesh: Optional[str] = None,
                            compressed: Optional[bool] = None,
                            flow_table=None,
                            resident: Optional[bool] = None,
                            telemetry=None,
                            mlscore=None,
                            mlscore_mode: Optional[str] = None,
                            payload=None,
                            payload_mode: Optional[str] = None,
                            payload_plen: Optional[int] = None):
    """``fused_deep`` steers the TPU backend's fused Pallas deep-walk
    dispatch (kernels.pallas_walk) for full-depth v6 chunks; None keeps
    the backend default (on for real TPU hardware, off in interpret
    mode).  ``wire_codec`` selects the H2D wire format (auto | wire8 |
    delta — the --wire-codec knob); None keeps the backend default (the
    INFW_WIRE_CODEC env, else auto).  ``mesh`` is the multi-chip serving
    spec ("DATAxRULES", the --mesh / INFW_MESH knob): when it resolves
    against the visible device pool the factory produces the
    MeshTpuClassifier; when it does not (single-chip node, 1x1 spec) the
    daemon falls back to the single-chip classifier and keeps serving.
    The CPU reference backend ignores all three."""
    from .backend import classifier_class

    if backend == "cpu":
        if flow_table is not None:
            log.warning(
                "--flow-table is a device-backend feature; the cpu "
                "reference classifier serves stateless"
            )
        if resident:
            log.warning(
                "--resident is a device-backend feature; the cpu "
                "reference classifier serves the multi-dispatch path"
            )
        if telemetry is not None:
            log.warning(
                "--telemetry is a device-backend feature; the cpu "
                "reference classifier exports no sketch plane"
            )
        if mlscore is not None:
            log.warning(
                "--mlscore is a device-backend feature; the cpu "
                "reference classifier serves unscored"
            )
        if payload is not None:
            log.warning(
                "--payload is a device-backend feature; the cpu "
                "reference classifier serves headers-only"
            )
        return classifier_class("cpu")
    if backend == "tpu":
        import functools

        kw = {}
        if fused_deep is not None:
            kw["fused_deep"] = fused_deep
        if wire_codec is not None:
            kw["wire_codec"] = wire_codec
        if compressed is not None:
            kw["compressed"] = compressed
        if resident:
            # zero-copy resident serving loop (ISSUE-12): one fused
            # donated-buffer device program per admission; implies a
            # flow tier (the classifier synthesizes a default table
            # when none was configured)
            kw["resident"] = True
        if flow_table is not None:
            # the stateful flow tier (infw.flow): a FlowConfig built at
            # launch (validated there) rides into every classifier
            # generation the syncer constructs
            kw["flow_table"] = flow_table
        if telemetry is not None:
            # device-resident telemetry plane (infw.obs.telemetry): a
            # SketchSpec built at launch rides into every classifier
            # generation; the daemon attaches its obs ring + drain
            # cadence on the idle loop (_telemetry_maintenance)
            kw["telemetry"] = telemetry
        if mlscore is not None:
            # MXU anomaly scoring (infw.mlscore): the launch-validated
            # (ScoreSpec, ScoreModel) bundle rides into every classifier
            # generation; the daemon attaches the obs ring, the drain
            # cadence and the <state-dir>/models/ hot-swap scan on the
            # idle loop (_mlscore_maintenance)
            spec, model = mlscore
            kw["mlscore"] = spec
            kw["mlscore_model"] = model
            kw["mlscore_mode"] = mlscore_mode or "shadow"
        if payload is not None:
            # payload matching tier (infw.payload): the launch-validated
            # pattern set (an AcModel / PayloadTier) rides into every
            # classifier generation; the daemon runs the
            # <state-dir>/patterns/ hot-swap scan on the idle loop
            # (_payload_maintenance).  The automaton tensors replicate
            # onto the mesh via the classifier's device sharding, so the
            # tier serves on multi-chip nodes too.
            kw["payload"] = payload
            kw["payload_mode"] = payload_mode or "shadow"
            if payload_plen is not None:
                kw["payload_plen"] = payload_plen
        if mesh:
            from .backend.mesh import resolve_mesh_spec

            m = resolve_mesh_spec(mesh)  # None -> single-chip fallback
            if m is not None:
                log.info(
                    "serving on a %dx%d (data x rules) device mesh",
                    m.shape["data"], m.shape["rules"],
                )
                if kw.pop("compressed", None):
                    # the compressed layout is single-chip for now: the
                    # mesh shard programs walk the per-level form
                    log.warning(
                        "--compressed is single-chip only; the mesh "
                        "backend serves the per-level trie layout"
                    )
                if kw.pop("mlscore", None) is not None:
                    # the scoring tensors are not mesh-placed yet (the
                    # telemetry-plane posture, ISSUE-13/14)
                    kw.pop("mlscore_model", None)
                    kw.pop("mlscore_mode", None)
                    log.warning(
                        "--mlscore is single-chip only; the mesh "
                        "backend serves unscored"
                    )
                return functools.partial(
                    classifier_class("mesh"), mesh=m, **kw
                )
        if not kw:
            return classifier_class("tpu")
        return functools.partial(classifier_class("tpu"), **kw)
    raise ValueError(f"unknown backend {backend!r} (expected tpu|cpu)")


class _FlowCounters:
    """flow_* counters + occupancy gauge as a /metrics provider: the
    getter indirection survives classifier reloads (the WireStatsCounters
    pattern); a classifier without a flow tier renders nothing.
    ``prefix`` disambiguates independent tiers — the registry SUMS
    same-named counters, so the tenant arena's flow tier must not share
    the single-tenant tier's series."""

    def __init__(self, clf_getter, prefix: str = "") -> None:
        self._get = clf_getter
        self._prefix = prefix

    def counter_values(self):
        clf = self._get()
        fc = getattr(clf, "flow_counters", None)
        if clf is None or fc is None:
            return {}
        try:
            vals = fc()
        except Exception:
            return {}
        if not self._prefix:
            return vals
        return {f"{self._prefix}{k}": v for k, v in vals.items()}


def _batch_from_wire(wire: np.ndarray, tcp_flags=None) -> PacketBatch:
    """Rebuild a PacketBatch from a packed 4/7-word wire record (the
    ring ingest's fallback for backends without the packed-wire
    contract) — the host twin of kernels' unpack_wire."""
    from .flow import host_unpack_wire

    f = host_unpack_wire(np.asarray(wire, np.uint32))
    return PacketBatch(
        kind=f["kind"], l4_ok=f["l4_ok"], ifindex=f["ifindex"],
        ip_words=f["ip_words"], proto=f["proto"],
        dst_port=f["dst_port"], icmp_type=f["icmp_type"],
        icmp_code=f["icmp_code"], pkt_len=f["pkt_len"],
        tcp_flags=(
            None if tcp_flags is None
            else np.asarray(tcp_flags, np.int32).copy()
        ),
    )


class _ResidentCounters:
    """resident_* pool gauges as a /metrics provider (the
    _FlowCounters getter-indirection pattern: survives classifier
    reloads; a classifier without a resident pool renders nothing)."""

    def __init__(self, clf_getter) -> None:
        self._get = clf_getter

    def counter_values(self):
        clf = self._get()
        rc = getattr(clf, "resident_counters", None)
        if clf is None or rc is None:
            return {}
        try:
            return rc()
        except Exception:
            return {}


class _TelemetryCounters:
    """telemetry_* counters as a /metrics provider (same getter
    indirection: survives classifier reloads; no telemetry tier renders
    nothing)."""

    def __init__(self, clf_getter) -> None:
        self._get = clf_getter

    def counter_values(self):
        clf = self._get()
        tc = getattr(clf, "telemetry_counters", None)
        if clf is None or tc is None:
            return {}
        try:
            return tc()
        except Exception:
            return {}


class _MlScoreCounters:
    """mlscore_* counters as a /metrics provider (same getter
    indirection: survives classifier reloads; no scoring tier renders
    nothing)."""

    def __init__(self, clf_getter) -> None:
        self._get = clf_getter

    def counter_values(self):
        clf = self._get()
        mc = getattr(clf, "mlscore_counters", None)
        if clf is None or mc is None:
            return {}
        try:
            return mc()
        except Exception:
            return {}


class _PayloadCounters:
    """payload_* counters + pattern-set version gauge as a /metrics
    provider (same getter indirection: survives classifier reloads; no
    payload tier renders nothing)."""

    def __init__(self, clf_getter) -> None:
        self._get = clf_getter

    def counter_values(self):
        clf = self._get()
        pc = getattr(clf, "payload_counters", None)
        if clf is None or pc is None:
            return {}
        try:
            return pc()
        except Exception:
            return {}


# --- daemon ------------------------------------------------------------------

class Daemon:
    def __init__(
        self,
        state_dir: str,
        node_name: str,
        namespace: str = "ingress-node-firewall-system",
        backend: str = "cpu",
        poll_period_s: float = 30.0,
        debug_lookup: bool = False,
        registry: Optional[InterfaceRegistry] = None,
        store: Optional[InMemoryStore] = None,
        metrics_port: int = DEFAULT_METRICS_PORT,
        health_port: int = DEFAULT_HEALTH_PORT,
        file_poll_interval_s: float = 0.2,
        event_sink=None,
        events_socket: Optional[str] = None,
        ingest_chunk: int = DEFAULT_INGEST_CHUNK,
        pipeline_depth: int = DEFAULT_PIPELINE_DEPTH,
        max_tick_packets: int = DEFAULT_MAX_TICK_PACKETS,
        event_ring_size: int = 1 << 21,
        fused_deep: Optional[bool] = None,
        wire_codec: Optional[str] = None,
        compressed: Optional[bool] = None,
        h2d_overlap: bool = True,
        h2d_stage_depth: int = DEFAULT_H2D_STAGE_DEPTH,
        mesh: Optional[str] = None,
        deadline_us: Optional[float] = None,
        max_batch: Optional[int] = None,
        patch_staleness_us: Optional[float] = None,
        patch_max_ops: Optional[int] = None,
        tenants: Optional[int] = None,
        flow_table=None,
        resident: bool = False,
        ring: Optional[str] = None,
        telemetry=None,
        telemetry_drain: int = 256,
        trace: bool = False,
        trace_slow_us: float = 50_000.0,
        mlscore=None,
        mlscore_mode: Optional[str] = None,
        payload=None,
        payload_mode: Optional[str] = None,
        payload_plen: Optional[int] = None,
        superbatch_k: Optional[int] = None,
    ) -> None:
        self.state_dir = state_dir
        self.node_name = node_name
        self.namespace = namespace
        self.backend = backend
        self.debug_lookup = debug_lookup
        self.file_poll_interval_s = file_poll_interval_s
        self.ingest_chunk = max(1, int(ingest_chunk))
        self.pipeline_depth = max(1, int(pipeline_depth))
        self.max_tick_packets = max(1, int(max_tick_packets))
        self.h2d_overlap = bool(h2d_overlap)
        self.h2d_stage_depth = max(1, int(h2d_stage_depth))
        # Stateful flow tier (--flow-table / INFW_FLOW_TABLE): an exact-
        # match verdict cache in front of the LPM+scan (infw.flow); the
        # daemon owns its observability (flow_* counters on /metrics,
        # FlowEvictRecords on the event ring) and the idle-loop age
        # sweep.  flow_table is a validated FlowConfig or None.
        self.flow_table = flow_table
        self._flow_attached: set = set()
        self._flow_age_last = 0.0
        # CoW arena upkeep (ISSUE-15): the idle-loop dedup sweep
        # re-hashes patched/cloned tenant slabs and re-merges pages
        # whose content re-converged (flips only — compile-free)
        self._tenant_dedup_last = 0.0
        # Zero-copy resident serving (--resident / INFW_RESIDENT,
        # ISSUE-12): the syncer's classifiers run the donated-buffer
        # fused serving loop; resident_* pool gauges export on /metrics.
        self.resident = bool(resident)
        # Device-resident telemetry plane (--telemetry / INFW_TELEMETRY,
        # ISSUE-13): count-min + top-K heavy-hitter tensors updated
        # inside the serving dispatch; the daemon owns the decimated
        # summarizer cadence (one small D2H per --telemetry-drain
        # admissions), the summary records on the obs event ring, the
        # telemetry_* counters on /metrics and the per-tenant
        # token-bucket sampling of raw deny-event export.
        self.telemetry = telemetry  # validated SketchSpec or None
        self.telemetry_drain = max(1, int(telemetry_drain))
        self._telemetry_attached: set = set()
        self._telemetry_drain_last = 0.0
        # MXU anomaly scoring (--mlscore [MODEL] / INFW_MLSCORE,
        # ISSUE-14): per-flow quantized inference fused into the
        # serving dispatch with shadow/enforce mitigation; the daemon
        # owns the anomaly-verdict records on the obs event ring, the
        # mlscore_* counters on /metrics and the <state-dir>/models/
        # hot-swap dir (versioned npz+manifest artifacts, consumed on
        # the idle loop — a swap behaves like a rule patch).
        self.mlscore = mlscore  # validated (ScoreSpec, ScoreModel) or None
        self.mlscore_mode = mlscore_mode or "shadow"
        self._mlscore_attached: set = set()
        self._mlscore_drain_last = 0.0
        # last models-dir hot-swapped artifact (consumed from disk) —
        # re-applied to rebuilt classifier generations so an escalation
        # rebuild can't silently revert to the launch-time model
        self._mlscore_swapped_model = None
        self.models_dir = os.path.join(state_dir, "models")
        # Payload matching tier (--payload [PATTERNS] / INFW_PAYLOAD,
        # ISSUE-19): batched Aho-Corasick multi-pattern matching over
        # the ring-sliced payload-prefix column, fused into the serving
        # dispatch with shadow/enforce mitigation; the daemon owns the
        # payload_* counters on /metrics and the <state-dir>/patterns/
        # hot-swap dir (versioned npz+manifest artifacts, consumed on
        # the idle loop — an in-bucket swap recompiles nothing).
        self.payload = payload  # patterns / AcModel / PayloadTier or None
        self.payload_mode = payload_mode or "shadow"
        self.payload_plen = payload_plen
        self._payload_attached: set = set()
        # last patterns-dir hot-swapped set (consumed from disk) —
        # re-applied to rebuilt classifier generations so an escalation
        # rebuild can't silently revert to the launch-time pattern set
        self._payload_swapped = None
        self.patterns_dir = os.path.join(state_dir, "patterns")
        # Serving-path tracing (--trace): per-stage span clocks through
        # the ingest/serving pipeline, exported as Prometheus histograms
        # on /metrics + sampled TraceSpanRecords for slow admissions.
        self.tracer = None
        if trace:
            from .obs.telemetry import SpanTracer

            self.tracer = SpanTracer(slow_us=float(trace_slow_us))
        # Persistent pinned host ingest ring (--ring / INFW_RING): a
        # preallocated shared-memory SPSC ring producers write packed
        # wire records into IN PLACE — the ingest loop admits by ring
        # cursor (no per-chunk file syscalls, no per-chunk numpy
        # reallocation on the hot path); the popped slot views double
        # as the H2D staging buffers and are released only after the
        # dispatch that read them materialized.
        # Device-side epoch loop (ISSUE-16): when the ring holds >= K
        # committed chunks of one shape class, the ingest loop stacks
        # them into ONE superbatch dispatch
        # (jaxpath.jitted_resident_superbatch) — K admissions chew
        # entirely on-device, one stacked fused readback.  K=1 disables
        # gathering (every chunk rides the single fused path).
        if superbatch_k is None:
            superbatch_k = int(os.environ.get("INFW_SUPERBATCH_K", "1") or 1)
        self.superbatch_k = max(1, int(superbatch_k))
        self.ingest_ring = None
        self._ring_inflight: deque = deque()
        #: optional callable(seq, results) receiving each ring record's
        #: packed per-packet verdicts once they materialize — the ring
        #: path's counterpart of the file path's out/*.verdicts.bin
        self.ring_verdict_sink = None
        if ring:
            from .ring import IngestRing

            # a payload tier grows each slot by the prefix column
            # (n * (L + 4) bytes) so producers can ship payload bytes
            # through the same zero-copy cursor discipline
            ring_pw = 0
            if payload is not None and backend != "cpu":
                from .kernels.wire_decode import PAYLOAD_PREFIX_WIDTHS

                ring_pw = int(payload_plen or PAYLOAD_PREFIX_WIDTHS[0])
            self.ingest_ring = IngestRing.create(
                ring, slots=max(8, 2 * self.pipeline_depth + 4),
                slot_packets=max(self.max_tick_packets, 4096),
                payload_width=ring_pw,
            )
        # Deadline-aware continuous microbatching (infw.scheduler): with
        # --deadline-us set, ingest jobs are sized by the LARGEST ladder
        # batch whose measured service time still fits the per-packet
        # deadline budget (admit-by-deadline) instead of the fixed
        # ingest_chunk; the batch-size ladder is pre-warmed at table
        # load so shape-driven jit recompiles never land on the serving
        # path, and scheduler observability (queue depth, batch-size
        # histogram, deadline misses, per-format wire bytes) exports on
        # /metrics with misses also emitted on the obs event ring.
        self.max_batch = max(1, int(max_batch)) if max_batch else self.ingest_chunk
        self.sched_stats = None
        self._sched_policy = None
        self._prewarmed_gen = None
        if deadline_us is not None:
            from .scheduler import DeadlinePolicy, SchedulerStats

            self.sched_stats = SchedulerStats()
            self._sched_policy = DeadlinePolicy(
                deadline_s=float(deadline_us) * 1e-6,
                max_admit=self.max_batch,
            )
            # ping-pong staging is the point of the serving loop: keep at
            # least one prepared batch ahead of the in-flight one
            self.h2d_stage_depth = max(2, self.h2d_stage_depth)
        self.registry = registry if registry is not None else default_registry
        # Update-storm edit batching (infw.txn): rule edits dropped into
        # <state-dir>/edits/ queue in a TxnBatcher and flush as ONE
        # folded patch transaction when (a) the oldest edit exceeds the
        # staleness deadline (--patch-staleness-us/INFW_PATCH_STALENESS_US)
        # or (b) the batch threshold (--patch-max-ops) trips — checked
        # between classify admissions inside the ingest tick AND on the
        # idle file loop, so edits never stall classification and
        # verdict staleness stays bounded.  Counters + the staleness
        # histogram export on /metrics; each flush emits a
        # PatchTxnRecord on the obs event ring.
        from .txn import (
            DEFAULT_MAX_OPS,
            DEFAULT_STALENESS_US,
            TxnBatcher,
            TxnStats,
        )

        self.patch_staleness_us = float(
            patch_staleness_us if patch_staleness_us is not None
            else DEFAULT_STALENESS_US
        )
        self.patch_max_ops = int(patch_max_ops or DEFAULT_MAX_OPS)
        self.txn_stats = TxnStats()
        self.txn_batcher = TxnBatcher(
            staleness_s=self.patch_staleness_us * 1e-6,
            max_ops=self.patch_max_ops,
        )
        # at most one flush in flight, on its own thread (see
        # _maybe_flush_edits); only the file loop mutates this
        self._edit_flush_thread = None

        self.nodestates_dir = os.path.join(state_dir, "nodestates")
        self.ingest_dir = os.path.join(state_dir, "ingest")
        self.edits_dir = os.path.join(state_dir, "edits")
        self.out_dir = os.path.join(state_dir, "out")
        self.events_path = os.path.join(state_dir, "events.log")
        # Multi-tenant paged arena mode (--tenants/INFW_TENANTS): each
        # tenant is a slab in one preallocated pool, created lazily when
        # <state-dir>/tenants/<name>/edits/ first appears; per-tenant
        # edit files apply through the SAME folded-transaction codec as
        # the single-tenant edits dir, landing as per-slab row scatters.
        self.tenants_max = max(0, int(tenants or 0))
        self.tenants_dir = os.path.join(state_dir, "tenants")
        self.tenant_registry = None
        dirs = [self.nodestates_dir, self.ingest_dir, self.edits_dir,
                self.out_dir]
        if self.tenants_max:
            dirs.append(self.tenants_dir)
        if self.mlscore is not None:
            dirs.append(self.models_dir)
        if self.payload is not None:
            dirs.append(self.patterns_dir)
        for d in dirs:
            os.makedirs(d, exist_ok=True)

        if backend == "tpu":
            platform_mod.enable_compile_cache()

        # Per-daemon metrics registry (controller-runtime gives each
        # manager its own, statistics.go:79-86): /metrics serves whatever
        # collectors are registered here — the daemon's own Statistics
        # plus any additional pollers a composition adds.
        self.metrics_registry = MetricsRegistry()
        self.stats = Statistics(poll_period_s=poll_period_s)
        self.stats.register(self.metrics_registry)
        self.syncer = DataplaneSyncer(
            classifier_factory=make_classifier_factory(
                backend, fused_deep=fused_deep, wire_codec=wire_codec,
                mesh=mesh, compressed=compressed,
                flow_table=flow_table if backend != "cpu" else None,
                resident=self.resident if backend != "cpu" else None,
                telemetry=self.telemetry if backend != "cpu" else None,
                mlscore=self.mlscore if backend != "cpu" else None,
                mlscore_mode=self.mlscore_mode,
                payload=self.payload if backend != "cpu" else None,
                payload_mode=self.payload_mode,
                payload_plen=self.payload_plen,
            ),
            registry=self.registry,
            stats_poller=self.stats,
            checkpoint_dir=os.path.join(state_dir, "checkpoint"),
        )
        self.store = store if store is not None else InMemoryStore()
        self.reconciler = NodeStateReconciler(
            self.store, self.syncer, node_name=node_name, namespace=namespace
        )
        self.store.watch(IngressNodeFirewallNodeState.KIND, self._on_store_event)

        # perf-ring analogue (kernel.c perf event array): once full,
        # incoming records are dropped and counted as LostSamples (the
        # oldest events survive a deny storm), so capacity trades event
        # completeness for memory
        self.ring = EventRing(capacity=max(64, int(event_ring_size)))
        self._event_file = open(self.events_path, "a", buffering=1)
        # Sidecar composition (daemonset.yaml:54-67): events always land in
        # events.log (the in-process record) and, when --events-socket is
        # given, are ALSO shipped as unixgram datagrams to the follower
        # process (cmd/syslog/syslog.go:16) — fire-and-forget, a dead
        # sidecar never blocks the dataplane.
        self._events_socket_sink = None
        if events_socket:
            from .obs.sidecar import UnixDatagramSink

            self._events_socket_sink = UnixDatagramSink(events_socket)
        base_sink = event_sink if event_sink is not None else self._write_event_line
        if self._events_socket_sink is not None:
            def sink(line, _base=base_sink, _sock=self._events_socket_sink):
                _base(line)
                _sock(line)
        else:
            sink = base_sink
        self.events_logger = EventsLogger(
            self.ring,
            sink,
            iface_names={i.index: i.name for i in self.registry.list()},
            # replay-scale batches drain as vectorized binary rows next
            # to events.log; the line sink gets one summary line each
            spill_path=os.path.join(
                os.path.dirname(self.events_path), "deny-events.bin"
            ),
        )
        # deny-event loss/queue totals on /metrics (events.go:79-82's
        # LostSamples, exported instead of only logged)
        self.metrics_registry.register_counters(self.ring)
        # background-thread crash accounting (infw._threads.spawn): zero
        # in a healthy control plane, so any nonzero reading is a page
        from ._threads import CRASH_COUNTERS

        self.metrics_registry.register_counters(CRASH_COUNTERS)
        # per-format H2D wire accounting (TpuClassifier.wire_stats) as
        # counters; the getter indirection survives table reloads and the
        # CPU backend (no wire_stats) renders nothing.  Registry holds
        # providers weakly, so keep the strong ref here.
        from .scheduler import WireStatsCounters

        self._wire_counters = WireStatsCounters(
            lambda: self.syncer.classifier
        )
        self.metrics_registry.register_counters(self._wire_counters)
        if self.sched_stats is not None:
            self.metrics_registry.register_counters(self.sched_stats)
        # patch-transaction counters + staleness histogram
        # (ingressnodefirewall_node_patch_txn_*)
        self.metrics_registry.register_counters(self.txn_stats)
        if (self.flow_table is not None or self.resident) and backend != "cpu":
            # flow_* counters + occupancy gauge; the getter indirection
            # survives table reloads exactly like the wire counters
            # (resident mode implies a flow tier, so its counters export
            # here too)
            self._flow_counters = _FlowCounters(
                lambda: self.syncer.classifier
            )
            self.metrics_registry.register_counters(self._flow_counters)
        if self.resident and backend != "cpu":
            # resident_* pool gauges (dispatches, context reuses,
            # fallbacks, steady-state allocation counter) — the
            # observability half of the zero-alloc contract
            self._resident_counters = _ResidentCounters(
                lambda: self.syncer.classifier
            )
            self.metrics_registry.register_counters(self._resident_counters)
        if self.telemetry is not None and backend != "cpu":
            # telemetry_* counters (updates, drains, summaries, sampled/
            # suppressed raw events, drain seq) — the decimation's
            # accounting half
            self._telemetry_counters = _TelemetryCounters(
                lambda: self.syncer.classifier
            )
            self.metrics_registry.register_counters(self._telemetry_counters)
        if self.mlscore is not None and backend != "cpu":
            # mlscore_* counters (updates, anomalies, enforced denies,
            # model swaps, drain seq) — the policy tier's accounting
            self._mlscore_counters = _MlScoreCounters(
                lambda: self.syncer.classifier
            )
            self.metrics_registry.register_counters(self._mlscore_counters)
        if self.payload is not None and backend != "cpu":
            # payload_* counters (admissions, scanned lanes, matches,
            # enforced rewrites, pattern swaps) + the pattern-set
            # version gauge — the matching tier's accounting
            self._payload_counters = _PayloadCounters(
                lambda: self.syncer.classifier
            )
            self.metrics_registry.register_counters(self._payload_counters)
        if self.tracer is not None:
            # span histograms (ingressnodefirewall_node_span_us) +
            # trace_* sample counters; slow-admission TraceSpanRecords
            # land on the obs event ring next to deny events
            self.tracer.attach_ring(self.ring)
            self.metrics_registry.register_histograms(
                self.tracer.histograms
            )
            self.metrics_registry.register_counters(self.tracer)
        if self.ingest_ring is not None:
            # ring_* cursor/backpressure gauges
            self.metrics_registry.register_counters(self.ingest_ring)
        if self.tenants_max:
            self.tenant_registry = self._build_tenant_registry()
            # tenant_* counters (active/free slabs, swaps, flips,
            # compactions, per-tenant packets/verdicts) on /metrics
            self.metrics_registry.register_counters(self.tenant_registry)
        # tenant names whose create failed deterministically (e.g. the
        # pool is smaller than the dirs an operator made): logged once,
        # then skipped — not retried every idle-loop pass forever
        self._tenant_create_failed: set = set()
        self.debug_buffer = DebugLookupBuffer()

        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._servers: List[ThreadingHTTPServer] = []
        self._known_state_files: Dict[str, float] = {}
        # Files rejected deterministically (schema/compile): remembered by
        # mtime so they are logged once, not every tick — but kept separate
        # from _known_state_files so deleting a rejected file never counts
        # as a CR deletion (which would reset the dataplane).
        self._rejected_state_files: Dict[str, float] = {}
        self.metrics_port = metrics_port
        self.health_port = health_port

    # -- event sink ----------------------------------------------------------

    def _write_event_line(self, line: str) -> None:
        self._event_file.write(line + "\n")

    # -- store-driven reconcile ----------------------------------------------

    def _on_store_event(self, event: str, obj) -> None:
        try:
            if event == "DELETED":
                if (
                    obj.metadata.name == self.node_name
                    and obj.metadata.namespace == self.namespace
                ):
                    # finalizer path already synced the delete; nothing to do
                    return
            self.reconciler.reconcile(obj.metadata.name, obj.metadata.namespace)
        except (SyncError, CompileError, InterfaceError) as e:
            log.error("reconcile failed: %s", e)

    # -- file-driven desired state -------------------------------------------

    def scan_nodestates_once(self) -> None:
        """State-dir protocol: <nodestates>/<node-name>.json holds the
        NodeState CR dict; file deletion is CR deletion."""
        seen = {}
        for fn in os.listdir(self.nodestates_dir):
            if not fn.endswith(".json"):
                continue
            path = os.path.join(self.nodestates_dir, fn)
            try:
                mtime = os.path.getmtime(path)
            except FileNotFoundError:
                continue
            seen[fn] = mtime
            if self._known_state_files.get(fn) == mtime:
                continue
            if self._rejected_state_files.get(fn) == mtime:
                continue
            try:
                with open(path) as f:
                    doc = json.load(f)
                ns_obj = IngressNodeFirewallNodeState.from_dict(doc)
            except OSError as e:
                # I/O errors can be transient; retry next tick.
                log.error("bad nodestate file %s: %s", fn, e)
                continue
            except (json.JSONDecodeError, TypeError, AttributeError, ValueError, KeyError) as e:
                # Deterministically unparseable bytes: reject once by mtime
                # like the schema tier, not every tick.
                log.error("bad nodestate file %s: %s", fn, e)
                self._rejected_state_files[fn] = mtime
                continue
            if not ns_obj.metadata.name:
                ns_obj.metadata.name = fn[: -len(".json")]
            if not ns_obj.metadata.namespace:
                ns_obj.metadata.namespace = self.namespace
            if ns_obj.metadata.name != self.node_name:
                continue
            schema_errs = validate_nodestate_schema(ns_obj)
            if schema_errs:
                # The file protocol has no API server in front of it; apply
                # the schema tier here so a misspelled protocol or order=0
                # is rejected with CRD-style messages, not a compile error.
                log.error("schema-invalid nodestate %s: %s", fn, "; ".join(schema_errs))
                self._rejected_state_files[fn] = mtime
                continue
            try:
                self.syncer.sync_interface_ingress_rules(
                    ns_obj.spec.interface_ingress_rules, False
                )
                self._known_state_files[fn] = mtime
            except CompileError as e:
                # Deterministic input error: re-reading the same bytes can
                # never succeed, so record the mtime and reject once.
                log.error("sync failed for %s: %s", fn, e)
                self._rejected_state_files[fn] = mtime
            except (SyncError, InterfaceError) as e:
                # Possibly transient (interface not up yet, attach EBUSY):
                # leave unrecorded so the next tick retries, but never
                # abort the rest of the scan.
                log.error("sync failed for %s: %s", fn, e)
        for fn in list(self._rejected_state_files):
            if fn not in seen:
                del self._rejected_state_files[fn]
        for fn in list(self._known_state_files):
            if fn not in seen:
                del self._known_state_files[fn]
                try:
                    self.syncer.sync_interface_ingress_rules({}, True)
                except (SyncError, CompileError, InterfaceError) as e:
                    log.error("delete sync failed for %s: %s", fn, e)

    # -- rule-edit files (the update-storm control plane) --------------------

    def scan_edits_once(self) -> int:
        """Queue every edit file in <state-dir>/edits/ into the
        transaction batcher (infw.txn edit-file protocol: one JSON doc
        of single-key ops per file, written tmp+rename by
        tools/churngen.py or any control plane).  Files are consumed in
        sorted order; a deterministically bad file is removed and
        logged, never wedging the scan.  Returns ops queued."""
        from .txn import read_edit_file

        n = 0
        for fn in sorted(os.listdir(self.edits_dir)):
            path = os.path.join(self.edits_dir, fn)
            if fn.endswith(".tmp") or not os.path.isfile(path):
                continue
            if fn.endswith("-manifest.json"):
                continue  # churngen's schedule sidecar, not an edit file
            try:
                ops = read_edit_file(path)
            except (OSError, ValueError, KeyError, TypeError) as e:
                log.error("bad edit file %s: %s", fn, e)
                try:
                    os.remove(path)
                except OSError as re:
                    log.error("could not remove bad edit file %s: %s",
                              fn, re)
                continue
            self.txn_batcher.queue_many(ops)
            n += len(ops)
            try:
                os.remove(path)
            except OSError as e:
                log.error("could not remove edit file %s: %s", fn, e)
        return n

    def _build_tenant_registry(self):
        """The multi-tenant arena control plane: one preallocated pool
        sized from --tenants and the INFW_TENANT_SLAB_ENTRIES /
        INFW_TENANT_RULE_SLOTS geometry knobs, with a staging page so
        every full-ruleset tenant update is a hot swap (page-table row
        flip), never a serving-path re-upload."""
        from .backend.tpu import ArenaClassifier
        from .kernels import jaxpath
        from .syncer import TenantRegistry

        entries = int(os.environ.get("INFW_TENANT_SLAB_ENTRIES") or 1024)
        slots = int(os.environ.get("INFW_TENANT_RULE_SLOTS") or 16)
        spec = jaxpath.make_arena_spec(
            "ctrie",
            pages=max(self.tenants_max + 2, 4),
            max_tenants=self.tenants_max,
            entries=entries,
            rule_slots=slots,
            lut_rows=64,
            root_nodes=4,
            node_rows=4 * entries,
            target_rows=8 * entries,
            d_max=18,
        )
        clf = ArenaClassifier(spec, flow_table=self.flow_table)
        if self.flow_table is not None:
            self._attach_flow_events(clf)
            # registry holds providers weakly — keep the strong ref;
            # prefixed so the arena tier's series never sums into the
            # single-tenant flow_* series
            self._tenant_flow_counters = _FlowCounters(
                lambda: clf, prefix="tenant_"
            )
            self.metrics_registry.register_counters(
                self._tenant_flow_counters
            )
        return TenantRegistry(clf, rule_width=slots, event_ring=self.ring)

    def scan_tenant_edits_once(self) -> int:
        """Apply every per-tenant edit file under
        <state-dir>/tenants/<name>/edits/ (same JSON edit-file codec as
        the single-tenant dir) as ONE folded transaction per file
        through the tenant registry.  A tenant is created (empty) the
        first time its directory appears; bad files are consumed and
        logged like the single-tenant scan.  Returns ops applied."""
        if self.tenant_registry is None:
            return 0
        from .txn import read_edit_file

        n = 0
        try:
            names = sorted(os.listdir(self.tenants_dir))
        except OSError:
            return 0
        for name in names:
            edits = os.path.join(self.tenants_dir, name, "edits")
            if not os.path.isdir(edits):
                continue
            if name not in self.tenant_registry.tenant_ids_by_name():
                if name in self._tenant_create_failed:
                    continue
                try:
                    self.tenant_registry.create_tenant(name, {})
                except Exception as e:
                    log.error(
                        "could not create tenant %r (will not retry; "
                        "its edit files are left in place): %s", name, e,
                    )
                    self._tenant_create_failed.add(name)
                    continue
            for fn in sorted(os.listdir(edits)):
                path = os.path.join(edits, fn)
                if fn.endswith(".tmp") or not os.path.isfile(path):
                    continue
                try:
                    ops = read_edit_file(path)
                    self.tenant_registry.apply_edit_transaction(name, ops)
                    n += len(ops)
                except Exception as e:
                    log.error("bad tenant edit file %s/%s: %s", name, fn, e)
                try:
                    os.remove(path)
                except OSError as e:
                    log.error("could not remove tenant edit file %s: %s",
                              fn, e)
        return n

    def _maybe_flush_edits(self, force: bool = False) -> bool:
        """Start a flush of the queued edit transaction when the
        bounded-staleness policy trips (or ``force``): ONE folded patch
        generation through the syncer, counters + staleness histogram
        into TxnStats, a PatchTxnRecord on the obs ring.  The flush runs
        on its OWN thread (at most one in flight — later edits keep
        coalescing toward the next transaction), so neither the ingest
        tick's admissions nor the idle loop ever wait on it; in
        particular an escalated columnar rebuild, which can take
        seconds at the 1M tier, overlaps classification instead of
        starving it (the scheduler-path slot contract, daemon half).
        Returns True when a flush was started."""
        batcher = getattr(self, "txn_batcher", None)
        if batcher is None or len(batcher) == 0:
            return False
        t = getattr(self, "_edit_flush_thread", None)
        if t is not None and t.is_alive():
            return False  # one flush in flight; edits keep coalescing
        reason = "manual" if force else batcher.should_flush()
        if reason is None:
            return False
        if self.syncer.classifier is None or self.syncer.classifier.tables is None:
            # no dataplane yet: keep queuing — the staleness clock keeps
            # running, so the first sync is followed by a flush
            return False
        items = batcher.drain()
        if not items:
            return False

        def work() -> None:
            try:
                self.syncer.apply_edit_transaction(
                    [op for op, _ts in items], reason=reason,
                    enqueue_ts=[ts for _op, ts in items],
                    stats=self.txn_stats, ring=self.ring,
                )
            except Exception as e:
                # a deterministically bad transaction must not re-queue
                # forever; drop it with a loud log (the model checker
                # and edit-file validation make this the rare path)
                log.error(
                    "edit transaction flush failed (%d ops dropped): %s",
                    len(items), e,
                )

        th = spawn(work, name="infw-edit-flush")
        self._edit_flush_thread = th
        return True

    # -- ingest --------------------------------------------------------------
    #
    # (helpers below are module-level: _expand_wire, _concat_batches,
    # stats_from_results)

    def process_ingest_once(self) -> int:
        """Classify every frames file in the ingest dir; write verdict
        summaries to out/; emit deny events; consume the file.

        Cross-file batching: all pending files (bounded by
        ``max_tick_packets``) are parsed up front, their packets regrouped
        into family-homogeneous device jobs of ``ingest_chunk`` rows that
        SPAN file boundaries — on a high-latency link each dispatch/
        readback round trip is the dominant cost, so ten small files share
        a handful of round trips instead of paying two each.  Jobs are
        kept ``pipeline_depth`` deep in flight so H2D, kernel and D2H of
        consecutive jobs overlap (the inline per-packet role of
        bpf/ingress_node_firewall_kernel.c:412-457).

        Failure isolation: a failed MERGED job is re-dispatched as
        per-file jobs, so a fault attributable to one file's packets
        poisons only that file (left on disk for retry) while its
        job-mates complete; statistics are computed host-side per file
        from the verdicts and applied only after the file is consumed —
        exactly once across any retry."""
        clf = self.syncer.classifier
        if clf is None or clf.tables is None:
            return 0
        processed = 0
        # getattr: tolerate the bare ingest-only harness (bench.py and
        # the ingest tests build Daemon.__new__ without __init__, the
        # h2d_stage_depth pattern below)
        tracer = getattr(self, "tracer", None)

        # Deadline scheduling (infw.scheduler, --deadline-us): job sizes
        # come from the policy's service-time model — the largest ladder
        # batch that still meets the per-packet deadline budget — scaled
        # by the classifier's data-parallel width (a mesh spreads one
        # admission over its "data" shards, so the per-chip budget
        # multiplies; a single-chip pool serves it unchanged).  Without
        # the knob the historical fixed ingest_chunk applies.
        # (getattr: the bench's Daemon.__new__ ingest harness constructs
        # no scheduler state.)
        policy = getattr(self, "_sched_policy", None)
        sched_stats = getattr(self, "sched_stats", None)
        if policy is not None:
            from .scheduler import data_parallel_width, ladder_floor

            self._maybe_prewarm_ladder(clf)
            width = data_parallel_width(clf)
            # quantize the cap to a pre-warmed ladder step: with a
            # non-pow2 mesh width, service_cap * width can land between
            # steps, and a chunk-capped pad would otherwise emit a shape
            # the prewarm never compiled
            chunk = ladder_floor(
                policy.service_cap() * width, self.max_batch * width
            )
            min_bucket_exp = 5  # the ladder starts at 32 (pre-warmed)
        else:
            chunk = self.ingest_chunk
            min_bucket_exp = 6

        def finalize(fctx) -> None:
            """Write verdicts, consume the file, then apply stats and
            emit events — strictly AFTER the source file is removed: a
            failure anywhere earlier leaves the file for a clean retry
            with zero double-counted statistics and no duplicate deny
            events."""
            nonlocal processed
            batch, fb, fn = fctx["batch"], fctx["frames"], fctx["fn"]
            results, xdp = fctx["results"], fctx["xdp"]
            if self.debug_lookup:
                self.debug_buffer.record_batch(batch)
            # Per-packet verdicts go to a binary sidecar (little-endian u32
            # per packet, file order), NOT into the JSON — a replay-scale
            # (10M-packet) file would otherwise produce a ~100MB JSON doc
            # built in memory.  The JSON stays a bounded summary.
            results.astype("<u4").tofile(
                os.path.join(self.out_dir, fn + ".verdicts.bin")
            )
            summary = {
                "file": fn,
                "packets": len(batch),
                "pass": int((xdp == 2).sum()),
                "drop": int((xdp == 1).sum()),
                "results_file": fn + ".verdicts.bin",
            }
            # tmp + rename like every other file in the protocol: readers
            # poll for the path and must never see a half-written doc
            jpath = os.path.join(self.out_dir, fn + ".verdicts.json")
            with open(jpath + ".tmp", "w") as f:
                json.dump(summary, f)
            os.replace(jpath + ".tmp", jpath)
            os.remove(fctx["path"])
            clf.stats.add(stats_from_results(results, np.asarray(batch.pkt_len)))
            self._emit_deny_sampled(clf, results, batch.ifindex,
                                    batch.pkt_len, fb, batch)
            processed += 1

        def seg_done(fctx) -> None:
            fctx["remaining"] -= 1
            if fctx["remaining"] == 0 and not fctx["failed"]:
                try:
                    finalize(fctx)
                except Exception as e:
                    log.error("ingest finalize failed for %s: %s", fctx["fn"], e)

        # ---- phase 1: read + parse pending files (bounded per tick) ----
        files = []
        total = 0
        for fn in sorted(os.listdir(self.ingest_dir)):
            path = os.path.join(self.ingest_dir, fn)
            if fn.endswith(".tmp") or not os.path.isfile(path):
                continue
            if files and total >= self.max_tick_packets:
                break  # the rest belongs to the next tick
            try:
                t_read0 = time.perf_counter()
                fb = read_frames_any(path)
                t_read1 = time.perf_counter()
                batch = parse_frames_buf(fb)
                if tracer is not None:
                    # file-drop taxonomy: ingest = file read, pack =
                    # frame parse (the wire pack itself is charged per
                    # job in prepare below)
                    h = tracer.histograms
                    h.observe("ingest", (t_read1 - t_read0) * 1e6)
                    h.observe("pack",
                              (time.perf_counter() - t_read1) * 1e6)
            except (OSError, ValueError, struct.error, IndexError) as e:
                # A parse crash must consume the file like a bad header
                # does — leaving it would wedge the tick at this file
                # every poll and starve later-sorted files.
                log.error("bad ingest file %s: %s", fn, e)
                try:
                    os.remove(path)
                except OSError as re:
                    # An unremovable file (EACCES/EROFS, racing unlink)
                    # must not abort the tick — that would starve every
                    # later-sorted file behind it.
                    log.error("could not remove bad ingest file %s: %s",
                              fn, re)
                continue
            # Arrival timestamp for the deadline accounting: the file's
            # DROP time (mtime — write_frames_file's os.replace stamps
            # it), mapped into the monotonic domain by age, so time the
            # file spent queued in the ingest dir behind a busy tick (or
            # the ladder prewarm) counts against the deadline — never
            # the parse or dispatch time (the coordinated-omission rule).
            try:
                age = max(0.0, time.time() - os.path.getmtime(path))
            except OSError:
                age = 0.0
            n = len(batch)
            fctx = {
                "fn": fn, "path": path, "frames": fb, "batch": batch,
                "results": np.zeros(n, np.uint32),
                "xdp": np.full(n, 2, np.int32),
                "remaining": 0, "failed": False,
                "t_arrival": time.monotonic() - age,
            }
            if n == 0:
                try:
                    finalize(fctx)  # no device dispatch for an empty file
                except Exception as e:
                    log.error("ingest finalize failed for %s: %s", fn, e)
                continue
            files.append(fctx)
            total += n
        if not files:
            return processed

        # ---- phase 2: family- and depth-homogeneous jobs spanning files --
        # v4-only jobs take the truncated trie walk (3 gathers, not 15);
        # v6 jobs additionally split by the classifier's depth classes
        # (v6_depth_groups): most v6 packets' root slots need only a few
        # deep levels, and walk cost is linear in levels.
        jobs: deque = deque()
        depth_groups_of = getattr(clf, "v6_depth_groups", None)
        group_keys = [(False, None)]
        if depth_groups_of is None:
            group_keys.append((True, None))
        else:
            # discover this generation's classes from the first v6 split
            seen_depths = set()
            per_file_v6 = {}
            for fctx in files:
                kinds = np.asarray(fctx["batch"].kind)
                g = np.nonzero(kinds == KIND_IPV6)[0]
                b = fctx["batch"]
                groups = depth_groups_of(b.ifindex, b.ip_words, g)
                per_file_v6[id(fctx)] = dict(
                    (d, idx) for d, idx in groups
                )
                seen_depths.update(d for d, _ in groups)
            # d is the (class, generation) pair from v6_depth_groups;
            # shallow classes first, full depth (class None) last
            group_keys += [(True, d) for d in sorted(
                seen_depths,
                key=lambda d: (d[0] is None, -1 if d[0] is None else d[0]),
            )]
        for want_v6, depth in group_keys:
            cur = []
            cur_n = 0
            for fctx in files:
                if want_v6 and depth_groups_of is not None:
                    g = per_file_v6[id(fctx)].get(depth)
                    if g is None:
                        continue
                else:
                    kinds = np.asarray(fctx["batch"].kind)
                    g = np.nonzero((kinds == KIND_IPV6) == want_v6)[0]
                pos = 0
                while pos < len(g):
                    take = g[pos : pos + (chunk - cur_n)]
                    cur.append((fctx, take))
                    fctx["remaining"] += 1
                    cur_n += len(take)
                    pos += len(take)
                    if cur_n >= chunk:
                        jobs.append({"segments": cur, "retry": False,
                                     "depth": depth})
                        cur, cur_n = [], 0
            if cur:
                jobs.append({"segments": cur, "retry": False,
                             "depth": depth})

        packed_ok = (
            getattr(clf, "supports_packed", None) is not None
            and clf.supports_packed()
        )

        def _bucket(n: int) -> int:
            """Pad jobs to power-of-two row counts (capped at the chunk
            size) so tail jobs reuse compiled executables instead of
            jit-compiling a fresh shape mid-tick.  Padding rows are
            KIND_OTHER (always PASS, no stats — and per-file statistics
            come from the host-side verdicts anyway, so inert padding is
            free).  Scheduler mode starts the ladder at 32 (every step
            pre-warmed); the legacy floor stays 64."""
            if n >= chunk:
                return n
            return min(1 << max(min_bucket_exp, (n - 1).bit_length()), chunk)

        # Double-buffered ingestion: ``prepare`` does the HOST half of a
        # dispatch (segment gather + wire pack + codec encode) and — on
        # backends exposing prepare_packed — STARTS the H2D copy of the
        # payload; ``launch`` invokes the classify on the staged plan.
        # The drain loop below keeps up to ``h2d_stage_depth`` prepared
        # jobs ahead of the in-flight window, so while one chunk's
        # classify runs on device, the next chunk's transfer is already
        # in flight and the one after that is being packed/encoded on
        # the host — the pipeline never stalls on a cold H2D copy.
        # (getattr defaults keep the bench/tests' Daemon.__new__ ingest
        # harnesses working without listing every knob.)
        h2d_overlap = bool(getattr(self, "h2d_overlap", True))
        can_stage = packed_ok and hasattr(clf, "prepare_packed")

        def prepare(job):
            """Host pack (+ staged H2D start).  Returns the launch
            payload, or None when every segment already failed; raises
            like the old dispatch did (the caller maps it to
            job_failed)."""
            nonlocal packed_ok, can_stage
            t_prep0 = time.perf_counter()
            segs = [(f, idx) for f, idx in job["segments"] if not f["failed"]]
            job["segments"] = segs
            if not segs:
                return None
            n = sum(len(idx) for _f, idx in segs)
            if tracer is not None:
                job["trace"] = tracer.begin(n)
            if packed_ok:
                parts = [
                    f["batch"].pack_wire_subset(np.ascontiguousarray(idx, np.int64))
                    for f, idx in segs
                ]
                width = max(w.shape[1] for w, _v4 in parts)
                wire = np.concatenate(
                    [w if w.shape[1] == width else expand_wire_v4(w)
                     for w, _v4 in parts]
                )
                pad = _bucket(n) - n
                if pad:
                    padrows = np.zeros((pad, width), np.uint32)
                    padrows[:, 0] = KIND_OTHER
                    wire = np.concatenate([wire, padrows])
                v4_only = all(v4 for _w, v4 in parts)
                if can_stage and h2d_overlap:
                    try:
                        t_h2d0 = time.perf_counter()
                        plan = clf.prepare_packed(
                            wire, v4_only, depth=job.get("depth")
                        )
                        tr = job.get("trace")
                        if tr is not None:
                            tr.add("pack", t_h2d0 - t_prep0)
                            tr.add("h2d", time.perf_counter() - t_h2d0)
                        return ("plan", plan)
                    except RuntimeError:
                        if clf.supports_packed() or clf.active_path is None:
                            raise
                        packed_ok = can_stage = False
                        log.warning(
                            "table flipped to wide-ruleId mid-tick; "
                            "falling back to unpacked classify"
                        )
                else:
                    return ("wire", wire, v4_only, job.get("depth"))
            merged = packets_mod.concat(
                [f["batch"].take(idx) for f, idx in segs]
            ).pad_to(_bucket(n))
            return ("batch", merged)

        def launch(job, prep):
            """Dispatch the prepared job.  Returns a PendingClassify, or
            raises (eager backends raise HERE, async ones at .result())."""
            nonlocal packed_ok, can_stage
            if prep[0] == "plan":
                return clf.classify_prepared(prep[1], apply_stats=False)
            if prep[0] == "wire":
                _tag, wire, v4_only, depth = prep
                try:
                    return clf.classify_async_packed(
                        wire, v4_only, apply_stats=False, depth=depth,
                    )
                except RuntimeError:
                    # A concurrent load_tables can flip the table to
                    # wide-ruleId mid-tick; re-check and fall through to
                    # the unpacked path instead of poisoning every
                    # in-flight file (the retry jobs would raise again,
                    # still packed).  A CLOSED classifier also fails
                    # supports_packed — that is not a format flip and the
                    # unpacked path would raise identically, so re-raise.
                    if clf.supports_packed() or clf.active_path is None:
                        raise
                    packed_ok = can_stage = False  # sticky for this tick
                    log.warning(
                        "table flipped to wide-ruleId mid-tick; "
                        "falling back to unpacked classify"
                    )
                    # job["segments"] as filtered at PREPARE time — the
                    # drain's offset walk is aligned to that list, so a
                    # file failing between prepare and launch must not
                    # re-filter here (drain skips failed files on write)
                    segs = job["segments"]
                    n = sum(len(idx) for _f, idx in segs)
                    merged = packets_mod.concat(
                        [f["batch"].take(idx) for f, idx in segs]
                    ).pad_to(_bucket(n))
                    return clf.classify_async(merged, apply_stats=False)
            return clf.classify_async(prep[1], apply_stats=False)

        def job_failed(job, err) -> None:
            """A merged job's fault cannot be attributed to one file:
            re-dispatch each segment as its own single-file job.  A retry
            job's fault CAN be attributed — poison that file."""
            if not job["retry"]:
                log.warning("ingest job failed (%s); retrying per file", err)
                for f, idx in job["segments"]:
                    jobs.append({"segments": [(f, idx)], "retry": True,
                                 "depth": job.get("depth")})
                return
            for f, _idx in job["segments"]:
                if not f["failed"]:
                    f["failed"] = True
                    log.error("ingest classify failed for %s: %s", f["fn"], err)
                seg_done(f)

        def note_sched_drain(job, t_done: float) -> None:
            """Scheduler accounting at job completion: feed the observed
            launch->materialize latency into the service model (the
            admit-by-deadline sizing input for the NEXT jobs), count
            per-packet deadline misses from each file's ARRIVAL time,
            and emit a DeadlineMissRecord on the obs event ring."""
            n = sum(len(idx) for _f, idx in job["segments"])
            t_launch = job.get("t_launch")
            if n and t_launch is not None:
                from .scheduler import ladder_bucket

                # bucket by the tick's admission cap (chunk), not the
                # per-chip max_admit: mesh jobs dispatch at width-scaled
                # shapes and must feed the estimate for THAT bucket
                policy.service.observe(
                    ladder_bucket(n, chunk), t_done - t_launch
                )
            n_miss, worst = 0, 0.0
            for f, idx in job["segments"]:
                lat = t_done - f["t_arrival"]
                worst = max(worst, lat)
                if lat > policy.deadline_s:
                    n_miss += len(idx)
            if sched_stats is not None:
                sched_stats.note_complete(n, n_miss)
                sched_stats.set_queue_depth(
                    max(0, sched_stats.queue_depth - n)
                )
            if n_miss:
                from .obs.events import DeadlineMissRecord

                self.ring.push(DeadlineMissRecord(
                    n_miss=n_miss, batch=n, worst_us=worst * 1e6,
                    deadline_us=policy.deadline_s * 1e6,
                ))

        def drain_one() -> None:
            job, pending = inflight.popleft()
            tr = job.get("trace")
            try:
                t_mat0 = time.perf_counter()
                out = pending.result()
                if tr is not None:
                    tr.add("materialize", time.perf_counter() - t_mat0)
            except Exception as e:
                job_failed(job, e)
                return
            if policy is not None:
                try:
                    note_sched_drain(job, time.monotonic())
                except Exception as e:
                    log.error("scheduler accounting failed: %s", e)
            t_drain0 = time.perf_counter()
            off = 0
            for f, idx in job["segments"]:
                k = len(idx)
                if not f["failed"]:
                    f["results"][idx] = np.asarray(out.results)[off : off + k]
                    f["xdp"][idx] = np.asarray(out.xdp)[off : off + k]
                off += k
                seg_done(f)
            if tr is not None:
                tr.add("drain", time.perf_counter() - t_drain0)
                tracer.finish(tr)

        inflight: deque = deque()
        staged: deque = deque()
        stage_depth = (
            getattr(self, "h2d_stage_depth", DEFAULT_H2D_STAGE_DEPTH)
            if h2d_overlap else 1
        )
        def stage_more() -> None:
            # keep the staging window full: the NEXT jobs' host pack +
            # codec encode + H2D start run while earlier classifies are
            # still on device (and while drain_one blocks below)
            while jobs and len(staged) < stage_depth:
                job = jobs.popleft()
                try:
                    prep = prepare(job)
                except Exception as e:
                    job_failed(job, e)
                    continue
                if prep is not None:
                    staged.append((job, prep))

        if sched_stats is not None:
            sched_stats.set_queue_depth(total)
        edits_ok = hasattr(self, "txn_batcher")  # bench harness: __new__
        while jobs or staged or inflight:
            # apply/classify interleaving: a tripped edit-transaction
            # flush lands BETWEEN admissions — in-flight classifies keep
            # running on the generation they were dispatched against,
            # and the next launched job picks up the patched tables
            if edits_ok:
                try:
                    self._maybe_flush_edits()
                except Exception as e:
                    log.error("edit flush error: %s", e)
            stage_more()
            while staged and len(inflight) < self.pipeline_depth:
                job, prep = staged.popleft()
                job["t_launch"] = time.monotonic()
                try:
                    t_disp0 = time.perf_counter()
                    pending = launch(job, prep)
                    tr = job.get("trace")
                    if tr is not None:
                        tr.add("dispatch", time.perf_counter() - t_disp0)
                except Exception as e:
                    job_failed(job, e)
                    continue
                if pending is not None:
                    inflight.append((job, pending))
                    if sched_stats is not None:
                        n_job = sum(len(i) for _f, i in job["segments"])
                        from .scheduler import ladder_bucket

                        sched_stats.note_admit(
                            n_job, ladder_bucket(n_job, chunk)
                        )
                # top up staging as the window drains so the lookahead
                # never collapses mid-burst
                stage_more()
            if inflight:
                drain_one()
        return processed

    # -- ring ingest (persistent pinned host ring, ISSUE-12) -----------------

    def process_ring_once(self, budget: Optional[int] = None) -> int:
        """Drain committed ring records through the packed dispatch:
        admission by ring cursor — the popped slot views ARE the H2D
        staging buffers (zero-copy on the CPU backend), and each slot is
        released back to the producer only after the dispatch that read
        it materialized, so the producer can never overwrite a record
        mid-copy.  Up to ``pipeline_depth`` dispatches stay in flight
        (the same double-buffer discipline as the file ingest).  Returns
        packets processed."""
        ring = self.ingest_ring
        if ring is None:
            return 0
        clf = self.syncer.classifier
        if clf is None:
            return 0
        supports = getattr(clf, "supports_packed", None)
        packed = supports is not None and supports()
        if packed and getattr(clf, "active_path", None) is None:
            return 0
        if self._sched_policy is not None and packed:
            self._maybe_prewarm_ladder(clf)
        budget = self.max_tick_packets if budget is None else int(budget)
        processed = 0
        inflight = self._ring_inflight
        tracer = getattr(self, "tracer", None)
        super_k = self.superbatch_k
        can_super = (
            packed and super_k >= 2
            and getattr(clf, "prepare_packed_super", None) is not None
        )
        carry: list = []  # popped-but-undispatched (shape-class break)

        def dispatch_one(chunk, trace) -> bool:
            try:
                if packed:
                    plan = clf.prepare_packed(
                        chunk.wire, chunk.v4_only,
                        tcp_flags=chunk.tcp_flags,
                        payload=chunk.payload,
                        payload_len=chunk.payload_len,
                    )
                    if trace is not None:
                        trace.mark("h2d")
                    pending = clf.classify_prepared(plan, apply_stats=True)
                else:
                    # non-packed backend (the cpu reference): rebuild
                    # the batch from the wire record — slower, but the
                    # ring must drain on every backend
                    pending = clf.classify_async(
                        _batch_from_wire(chunk.wire, chunk.tcp_flags),
                        apply_stats=True,
                    )
                if trace is not None:
                    trace.mark("dispatch")
            except Exception as e:
                log.error("ring ingest dispatch failed: %s", e)
                chunk.release()
                return False
            inflight.append((chunk, pending, trace))
            return True

        while processed < budget:
            t0 = time.perf_counter()
            chunk = carry.pop(0) if carry else ring.pop(timeout=0.0)
            if chunk is None:
                break
            trace = None
            if tracer is not None:
                # span taxonomy on the ring path: ingest = cursor pop,
                # h2d = prepare_packed (staging device_put; the record
                # arrives pre-packed so pack is the producer's cost),
                # dispatch = program launch, materialize = readback,
                # drain = slot release + bookkeeping
                trace = tracer.begin(chunk.wire.shape[0])
                trace.add("ingest", time.perf_counter() - t0)
            group = [chunk]
            if can_super and not carry:
                # gather up to K committed records of ONE shape class —
                # same (n, width, v4_only, flags presence); the jit
                # cache keys on exactly those, a mixed stack would
                # recompile.  A mismatch carries to the next loop turn
                # (releases stay in pop order either way).
                while len(group) < super_k:
                    try:
                        nxt = ring.pop(timeout=0.0)
                    except ValueError as e:
                        log.error("ring ingest pop failed: %s", e)
                        break
                    if nxt is None:
                        break
                    if (nxt.wire.shape != chunk.wire.shape
                            or nxt.v4_only != chunk.v4_only
                            or (nxt.tcp_flags is None)
                            != (chunk.tcp_flags is None)
                            or (nxt.payload is None)
                            != (chunk.payload is None)
                            or (chunk.payload is not None
                                and nxt.payload.shape
                                != chunk.payload.shape)):
                        carry.append(nxt)
                        break
                    group.append(nxt)
            if len(group) >= 2:
                # one stacked H2D (the stack copy is the staging write —
                # slot views are not contiguous across slots) + ONE
                # device epoch-loop dispatch for the whole group
                wire_stack = np.stack([c.wire for c in group])
                flags_stack = (
                    None if chunk.tcp_flags is None
                    else np.stack([c.tcp_flags for c in group])
                )
                pay_stack = plen_stack = None
                if chunk.payload is not None:
                    pay_stack = np.stack([c.payload for c in group])
                    plen_stack = np.stack(
                        [c.payload_len for c in group]
                    )
                plan = None
                try:
                    plan = clf.prepare_packed_super(
                        wire_stack, chunk.v4_only,
                        tcp_flags_stack=flags_stack,
                        payload_stack=pay_stack,
                        payload_len_stack=plen_stack,
                    )
                    if plan is not None:
                        if trace is not None:
                            trace.mark("h2d")
                        pends = clf.classify_prepared_super(
                            plan, apply_stats=True
                        )
                        if trace is not None:
                            trace.mark("dispatch")
                except Exception as e:
                    log.error("ring superbatch dispatch failed: %s", e)
                    plan = None
                if plan is not None:
                    for j, (c, p) in enumerate(zip(group, pends)):
                        inflight.append((c, p, trace if j == 0 else None))
                        processed += c.wire.shape[0]
                    while len(inflight) > self.pipeline_depth:
                        self._ring_drain_one()
                    continue
                # superbatch declined (resident fallback): serve each
                # gathered record through the single-admission path
            for j, c in enumerate(group):
                if dispatch_one(c, trace if j == 0 else None):
                    processed += c.wire.shape[0]
            while len(inflight) > self.pipeline_depth:
                self._ring_drain_one()
        # a shape-class break popped one record past the budget: it must
        # still dispatch (releases are strictly in pop order)
        for c in carry:
            if dispatch_one(c, None):
                processed += c.wire.shape[0]
        while inflight:
            self._ring_drain_one()
        return processed

    def _ring_drain_one(self) -> None:
        chunk, pending, trace = self._ring_inflight.popleft()
        try:
            out = pending.result()
            if trace is not None:
                trace.mark("materialize")
            if self.ring_verdict_sink is not None:
                self.ring_verdict_sink(chunk.seq, out.results)
        except Exception as e:
            log.error("ring ingest classify failed: %s", e)
        finally:
            chunk.release()
            if trace is not None:
                trace.mark("drain")
                self.tracer.finish(trace)  # trace only exists when tracer does

    def _maybe_prewarm_ladder(self, clf) -> None:
        """Pre-warm every batch-size ladder shape against the CURRENT
        table generation, once per generation: shape-driven jit
        specialization (and each executable's first-dispatch cost)
        lands here, never inside a serving-path latency budget.  Covers
        batch=32 (the smallest ladder rung) and every depth-steering
        class."""
        gen = (id(clf), id(clf.tables), getattr(clf, "_depth_gen", 0))
        if gen == self._prewarmed_gen:
            return
        from .scheduler import (
            batch_ladder, data_parallel_width, prewarm_ladder,
        )

        try:
            # the ladder extends to max_batch * data shards: a mesh
            # classifier's tick jobs span the whole pool (chunk =
            # service_cap * width), so those shapes must be warm too —
            # the compile-free timing pass also seeds the admission
            # policy's service model, so the first tick's job sizing is
            # measured, not the cold-model default
            ladder = batch_ladder(self.max_batch * data_parallel_width(clf))
            prewarm_ladder(clf, ladder,
                           service=self._sched_policy.service)
        except Exception as e:
            log.error("ladder prewarm failed: %s", e)
        self._prewarmed_gen = gen

    # -- HTTP endpoints ------------------------------------------------------

    def _make_handler(daemon_self):
        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _send(self, code: int, body: str, ctype="text/plain; charset=utf-8"):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                if self.path == "/metrics":
                    self._send(200, daemon_self.metrics_registry.render_text())
                elif self.path in ("/healthz", "/readyz"):
                    self._send(200, "ok")
                elif self.path == "/debug/lookup-keys":
                    keys = daemon_self.debug_buffer.snapshot()
                    self._send(
                        200,
                        json.dumps(
                            [{"ifindex": k[0], "ip_words": list(k[1])} for k in keys]
                        ),
                        ctype="application/json",
                    )
                else:
                    self._send(404, "not found")

        return Handler

    # -- lifecycle -----------------------------------------------------------

    def start(self) -> None:
        handler = self._make_handler()
        for port in {self.metrics_port, self.health_port}:
            srv = ThreadingHTTPServer(("127.0.0.1", port), handler)
            self._servers.append(srv)
            t = spawn(srv.serve_forever, name="infw-daemon-http")
            self._threads.append(t)
        self.events_logger.start()
        t = spawn(self._file_loop, name="infw-file-loop")
        self._threads.append(t)
        log.info(
            "daemon started node=%s backend=%s metrics=127.0.0.1:%d",
            self.node_name, self.backend, self.metrics_port,
        )

    def _file_loop(self) -> None:
        while not self._stop.wait(self.file_poll_interval_s):
            # Scan and ingest are isolated from each other: a persistently
            # bad nodestate file must not starve packet classification.
            try:
                self.scan_nodestates_once()
            except Exception as e:  # keep the loop alive
                log.error("nodestate scan error: %s", e)
            try:
                self.scan_edits_once()
                self._maybe_flush_edits()
            except Exception as e:
                log.error("edit scan error: %s", e)
            try:
                self.scan_tenant_edits_once()
            except Exception as e:
                log.error("tenant edit scan error: %s", e)
            try:
                self._tenant_dedup_maintenance()
            except Exception as e:
                log.error("tenant dedup sweep error: %s", e)
            try:
                self.process_ring_once()
            except Exception as e:
                log.error("ring ingest error: %s", e)
            try:
                self.process_ingest_once()
            except Exception as e:
                log.error("ingest error: %s", e)
            try:
                self._flow_maintenance()
            except Exception as e:
                log.error("flow maintenance error: %s", e)
            try:
                self._telemetry_maintenance()
            except Exception as e:
                log.error("telemetry maintenance error: %s", e)
            try:
                self._mlscore_maintenance()
            except Exception as e:
                log.error("mlscore maintenance error: %s", e)
            try:
                self._payload_maintenance()
            except Exception as e:
                log.error("payload maintenance error: %s", e)

    def _attach_flow_events(self, clf) -> None:
        """Wire a classifier's flow tier to the obs event ring (once
        per tier): eviction storms surface as FlowEvictRecords next to
        the deny events."""
        tier = getattr(clf, "flow", None)
        if tier is None or id(tier) in self._flow_attached:
            return
        from .obs.events import FlowEvictRecord

        tier.on_evict = lambda ev, ins, ep: self.ring.push(
            FlowEvictRecord(evicted=int(ev), inserted=int(ins),
                            epoch=int(ep))
        )
        self._flow_attached.add(id(tier))

    def _flow_maintenance(self) -> None:
        """Idle-loop flow upkeep: attach eviction events to any new
        classifier generation and run the epoch-based age sweep every
        few seconds (stale entries never serve regardless — the sweep
        just returns their slots ahead of LRU pressure)."""
        if self.flow_table is None:
            return
        now = time.monotonic()
        for clf in (self.syncer.classifier,
                    self.tenant_registry.classifier
                    if self.tenant_registry is not None else None):
            if clf is None:
                continue
            self._attach_flow_events(clf)
            if now - self._flow_age_last >= 5.0:
                age = getattr(clf, "flow_age_tick", None)
                if age is not None:
                    age()
        if now - self._flow_age_last >= 5.0:
            self._flow_age_last = now

    def _tenant_dedup_maintenance(self) -> None:
        """Idle-loop CoW arena upkeep: every few seconds, re-hash
        tenant slabs whose content hash went stale (in-place patches /
        CoW clones) and re-merge pages that re-converged onto one
        shared slab — page-table flips only, never a slab write, so
        the sweep is serving-path-safe at any cadence.  On spliced
        arenas the same pass re-merges subtree planes that unspliced
        apart and later re-converged (splice-row flips, ISSUE-17).
        Bounded per pass (``limit``) so one sweep never monopolizes
        the idle loop on a large pool."""
        if self.tenant_registry is None:
            return
        now = time.monotonic()
        if now - self._tenant_dedup_last < 5.0:
            return
        self._tenant_dedup_last = now
        sweep = getattr(self.tenant_registry.classifier, "dedup_sweep", None)
        if sweep is not None:
            rep = sweep(limit=64)
            if rep.get("merged") or rep.get("plane_merged"):
                log.info("tenant dedup sweep: %d page(s) re-hashed, "
                         "%d tenant row(s) re-merged, "
                         "%d subtree plane(s) re-merged",
                         rep["hashed"], rep["merged"],
                         rep.get("plane_merged", 0))

    def _telemetry_maintenance(self) -> None:
        """Idle-loop telemetry upkeep: attach the obs ring + drain
        cadence to any new classifier generation's tier, and force a
        time-based drain every few seconds so low-traffic windows still
        produce timely summaries (the admission-count decimation only
        fires under load)."""
        if self.telemetry is None:
            return
        clf = self.syncer.classifier
        tier = getattr(clf, "telemetry", None)
        if tier is None:
            return
        if id(tier) not in self._telemetry_attached:
            tier.attach_ring(self.ring)
            tier.drain_every = self.telemetry_drain
            self._telemetry_attached.add(id(tier))
        now = time.monotonic()
        if now - self._telemetry_drain_last >= 5.0:
            self._telemetry_drain_last = now
            with tier._lock:
                pending = tier._window_admissions > 0
            if pending:
                tier.drain(force=True)

    def _mlscore_maintenance(self) -> None:
        """Idle-loop scoring upkeep: attach the obs ring to any new
        classifier generation's tier, force a time-based drain so
        low-traffic windows still produce timely anomaly-verdict
        records, and consume dropped model artifacts from
        <state-dir>/models/ — each *.npz (+ required .json manifest)
        hot-swaps through set_score_model (a swap behaves like a rule
        patch: the flow generation bumps); bad or mismatched artifacts
        are consumed and logged, never retried forever (the edits-dir
        bad-file discipline)."""
        if self.mlscore is None:
            return
        clf = self.syncer.classifier
        tier = getattr(clf, "mlscore", None)
        if tier is None:
            return
        if id(tier) not in self._mlscore_attached:
            tier.attach_ring(self.ring)
            self._mlscore_attached.add(id(tier))
            # a classifier REBUILD (rules-edit escalation, re-place)
            # constructs its tier from the factory's launch-time model —
            # re-apply the last hot-swapped artifact (already consumed
            # from the models dir) so a rebuild can't silently revert
            swapped = getattr(self, "_mlscore_swapped_model", None)
            if (swapped is not None
                    and tier.model_version != swapped.version):
                try:
                    clf.set_score_model(swapped)
                    log.info("mlscore: re-applied hot-swapped model "
                             "%s to new classifier generation",
                             swapped.version)
                except Exception as e:
                    log.error("mlscore: re-apply of swapped model "
                              "failed: %s", e)
        now = time.monotonic()
        if now - self._mlscore_drain_last >= 5.0:
            self._mlscore_drain_last = now
            with tier._lock:
                pending = tier._window_admissions > 0
            if pending:
                tier.drain(force=True)
        # model hot-swap dir: consume complete npz+manifest pairs
        from .mlscore import load_model

        try:
            names = sorted(os.listdir(self.models_dir))
        except OSError:
            return
        for fn in names:
            if not fn.endswith(".npz"):
                continue
            path = os.path.join(self.models_dir, fn)
            if not os.path.exists(path + ".json"):
                continue  # manifest not landed yet — next tick
            try:
                model = load_model(path)
                clf.set_score_model(model)
                self._mlscore_swapped_model = model
                log.info("mlscore: hot-swapped model %s (version %s)",
                         fn, tier.model_version)
            except Exception as e:
                log.error("mlscore: model artifact %s rejected: %s",
                          fn, e)
            for p in (path, path + ".json"):
                try:
                    os.unlink(p)
                except OSError:
                    pass

    def _payload_maintenance(self) -> None:
        """Idle-loop payload-tier upkeep: re-apply the last hot-swapped
        pattern set to any rebuilt classifier generation (a rules-edit
        escalation rebuild constructs its tier from the factory's
        launch-time set), then consume dropped pattern-set artifacts
        from <state-dir>/patterns/ — each *.npz (+ required .json
        manifest, infw.payload.save_patterns) hot-swaps through
        set_payload_patterns.  An in-bucket swap recompiles nothing
        (the zero-recompile discipline); a swap behaves like a rule
        patch — the flow generation bumps so cached payload verdicts
        can't serve stale.  Bad or mismatched artifacts are consumed
        and logged, never retried forever (the edits-dir bad-file
        discipline)."""
        if self.payload is None:
            return
        clf = self.syncer.classifier
        tier = getattr(clf, "payload", None)
        if tier is None:
            return
        if id(tier) not in self._payload_attached:
            self._payload_attached.add(id(tier))
            swapped = getattr(self, "_payload_swapped", None)
            if swapped is not None:
                pats, plen, label = swapped
                try:
                    clf.set_payload_patterns(pats, plen=plen)
                    log.info("payload: re-applied hot-swapped pattern "
                             "set %s to new classifier generation",
                             label)
                except Exception as e:
                    log.error("payload: re-apply of swapped pattern "
                              "set failed: %s", e)
        # pattern hot-swap dir: consume complete npz+manifest pairs
        from .payload import load_patterns

        try:
            names = sorted(os.listdir(self.patterns_dir))
        except OSError:
            return
        for fn in names:
            if not fn.endswith(".npz"):
                continue
            path = os.path.join(self.patterns_dir, fn)
            if not os.path.exists(path + ".json"):
                continue  # manifest not landed yet — next tick
            try:
                pats, spec, label = load_patterns(path)
                clf.set_payload_patterns(pats, plen=spec.plen)
                self._payload_swapped = (pats, spec.plen, label)
                log.info("payload: hot-swapped pattern set %s "
                         "(version %s, %d patterns)", fn, label,
                         len(pats))
            except Exception as e:
                log.error("payload: pattern artifact %s rejected: %s",
                          fn, e)
            for p in (path, path + ".json"):
                try:
                    os.unlink(p)
                except OSError:
                    pass

    def _emit_deny_sampled(self, clf, results, ifindex, pkt_len, frames,
                           batch) -> None:
        """Deny-event export with the telemetry tier's per-tenant token
        bucket in front (ISSUE-13): the full firehose is replaced by
        bounded raw evidence — exact totals always travel in the sketch
        summaries; the bucket releases at most its budget of raw
        records, the rest counts as telemetry_suppressed_events (never
        as ring loss — suppression is policy, not overflow)."""
        tel = getattr(clf, "telemetry", None)
        if tel is None:
            emit_deny_events(self.ring, results, ifindex, pkt_len, frames,
                             batch=batch)
            return
        results = np.asarray(results)
        deny_idx = np.nonzero((results & 0xFF) == DENY)[0]
        if len(deny_idx) == 0:
            return
        grant = tel.sample_allow(0, len(deny_idx))
        if grant >= len(deny_idx):
            emit_deny_events(self.ring, results, ifindex, pkt_len, frames,
                             batch=batch)
            return
        if grant == 0:
            return
        keep = deny_idx[:grant]
        emit_deny_events(
            self.ring, results[keep], np.asarray(ifindex)[keep],
            np.asarray(pkt_len)[keep],
            None if frames is None else [frames[int(i)] for i in keep],
        )

    def stop(self) -> None:
        """SIGTERM path: stop polling/serving, detach the dataplane but
        keep the checkpoint (ebpfsyncer.go:90-97 — rules keep enforcing
        across daemon restarts via the pinned state)."""
        self._stop.set()
        for srv in self._servers:
            srv.shutdown()
        self.events_logger.stop()
        self.stats.stop_poll()
        self.stats.unregister()
        self.syncer.shutdown()
        self._event_file.close()
        if self._events_socket_sink is not None:
            self._events_socket_sink.close()
        if self.ingest_ring is not None:
            while self._ring_inflight:
                self._ring_drain_one()
            self.ingest_ring.close()

    @property
    def actual_metrics_port(self) -> int:
        return self._servers[0].server_address[1] if self._servers else self.metrics_port


def main(argv: Optional[List[str]] = None) -> int:
    """CLI entry with the reference env contract
    (cmd/daemon/daemon.go:69-84): flags override env, env overrides
    defaults."""
    p = argparse.ArgumentParser(prog="infw-daemon")
    p.add_argument("--state-dir", required=True)
    p.add_argument("--node-name", default=os.environ.get("NODE_NAME", ""))
    p.add_argument(
        "--namespace",
        default=os.environ.get("NAMESPACE", "ingress-node-firewall-system"),
    )
    p.add_argument("--backend", default=os.environ.get("INFW_BACKEND", "cpu"),
                   choices=["tpu", "cpu"])
    p.add_argument(
        "--poll-period-seconds",
        type=float,
        default=float(os.environ.get("POLL_PERIOD_SECONDS", "30")),
    )
    p.add_argument("--metrics-port", type=int, default=DEFAULT_METRICS_PORT)
    p.add_argument("--health-port", type=int, default=DEFAULT_HEALTH_PORT)
    p.add_argument("--ingest-chunk", type=int, default=DEFAULT_INGEST_CHUNK)
    p.add_argument("--pipeline-depth", type=int, default=DEFAULT_PIPELINE_DEPTH)
    p.add_argument(
        "--superbatch-k", type=int, default=None,
        help="stack up to K same-shape ring records into one device-side "
             "epoch-loop dispatch (default INFW_SUPERBATCH_K or 1 = off)")
    p.add_argument("--max-tick-packets", type=int,
                   default=DEFAULT_MAX_TICK_PACKETS)
    p.add_argument("--event-ring-size", type=int, default=1 << 21,
                   help="deny-event ring capacity, minimum 64 (overflow "
                        "drops new records and counts them as lost "
                        "samples, like the kernel perf ring)")
    p.add_argument(
        "--no-fused-deep", action="store_true",
        default=os.environ.get("INFW_FUSED_DEEP", "") in ("0", "false", "no"),
        help="disable the fused Pallas deep-walk dispatch for full-depth "
             "v6 chunks (kernels.pallas_walk); the XLA per-level walk "
             "serves them instead",
    )
    p.add_argument(
        "--compressed", action="store_true",
        default=os.environ.get("INFW_COMPRESSED", "")
        not in ("", "0", "false", "no"),
        help="serve trie-sized tables from the path/level-compressed "
             "poptrie layout (jaxpath.build_cpoptrie): merged skip-node "
             "array + per-tidx joined rows — the 10M-tier working-set "
             "layout.  Ineligible tables (wide ruleIds) fall back to "
             "the level walk per load.  CLI beats INFW_COMPRESSED",
    )
    p.add_argument(
        "--no-compressed", action="store_true",
        help="force the per-level walk layout even when INFW_COMPRESSED "
             "is set (the off direction of --compressed, so the CLI can "
             "beat the env var both ways)",
    )
    p.add_argument(
        "--wire-codec", choices=["auto", "wire8", "delta"],
        default=os.environ.get("INFW_WIRE_CODEC") or None,
        help="H2D wire format for packed trie chunks (the --no-fused-deep "
             "precedence pattern: CLI beats INFW_WIRE_CODEC, env beats the "
             "default): auto = per-chunk choice by measured compressed "
             "size (delta when it beats wire8's 8 B/packet), wire8/delta "
             "= force, with eligibility fallbacks",
    )
    p.add_argument(
        "--mesh",
        default=os.environ.get("INFW_MESH") or None,
        help="multi-chip serving mesh as DATAxRULES (e.g. 8x1, 4x2) or a "
             "bare device count (rules=1); CLI beats INFW_MESH.  Packets "
             "shard over the data axis, the rule table over the rules "
             "axis (per-shard tries above the dense limit).  When the "
             "visible device pool is smaller than the spec the daemon "
             "logs a warning and serves single-chip",
    )
    p.add_argument(
        "--no-h2d-overlap", action="store_true",
        default=os.environ.get("INFW_H2D_OVERLAP", "") in ("0", "false", "no"),
        help="disable double-buffered ingestion (the next chunk's H2D "
             "copy overlapping the current chunk's classify); chunks then "
             "stage one at a time — the A/B control the bench's overlap "
             "margin line measures against",
    )
    p.add_argument(
        "--tenants", type=int,
        default=os.environ.get("INFW_TENANTS") or None,
        help="enable the multi-tenant paged table arena with this many "
             "tenant ids: one preallocated slab pool per layout family, "
             "tenants created lazily from <state-dir>/tenants/<name>/"
             "edits/ (same edit-file codec as the single-tenant dir), "
             "ruleset activation by page-table flip, tenant_* counters "
             "on /metrics.  Slab geometry via INFW_TENANT_SLAB_ENTRIES "
             "(default 1024) and INFW_TENANT_RULE_SLOTS (default 16).  "
             "CLI beats INFW_TENANTS",
    )
    p.add_argument(
        "--flow-table", type=int,
        default=os.environ.get("INFW_FLOW_TABLE") or None,
        help="enable the stateful flow tier with this many entries per "
             "flow slab (bucketed to a power of two): a device-resident "
             "exact-match verdict cache probed before the LPM + rule "
             "scan — established flows serve their cached verdict and "
             "only misses pay classification; rule patches / tenant "
             "swaps invalidate by generation bump.  Capacity knobs: "
             "INFW_FLOW_WAYS (set associativity, default 4) and "
             "INFW_FLOW_MAX_AGE (hit freshness horizon in probe epochs)."
             "  CLI beats INFW_FLOW_TABLE",
    )
    p.add_argument(
        "--deadline-us", type=float,
        default=os.environ.get("INFW_DEADLINE_US") or None,
        help="per-packet verdict deadline budget in microseconds: enables "
             "the deadline-aware continuous microbatching scheduler "
             "(infw.scheduler) — ingest jobs coalesce to the largest "
             "batch whose measured service time still meets the budget "
             "(admit-by-deadline, not the fixed --ingest-chunk), the "
             "batch-size ladder is pre-warmed at table load, and "
             "scheduler observability lands on /metrics and the event "
             "ring.  CLI beats INFW_DEADLINE_US",
    )
    p.add_argument(
        "--max-batch", type=int,
        default=os.environ.get("INFW_MAX_BATCH") or None,
        help="scheduler admission cap per chip (default: --ingest-chunk); "
             "on a --mesh pool one admission spreads over the data axis, "
             "so the effective cap multiplies by the data shards.  CLI "
             "beats INFW_MAX_BATCH",
    )
    p.add_argument(
        "--patch-staleness-us", type=float,
        default=os.environ.get("INFW_PATCH_STALENESS_US") or None,
        help="bounded verdict staleness for batched rule edits "
             "(infw.txn): edits dropped into <state-dir>/edits/ "
             "coalesce into ONE folded patch transaction and flush "
             "when the oldest queued edit exceeds this budget (or "
             "--patch-max-ops trips) — between classify admissions, "
             "never stalling them.  Default 2000us.  CLI beats "
             "INFW_PATCH_STALENESS_US",
    )
    p.add_argument(
        "--patch-max-ops", type=int,
        default=os.environ.get("INFW_PATCH_MAX_OPS") or None,
        help="batch-size flush threshold for queued rule edits "
             "(default 1024): a queue this deep flushes regardless of "
             "staleness.  CLI beats INFW_PATCH_MAX_OPS",
    )
    p.add_argument(
        "--resident", action="store_true",
        default=os.environ.get("INFW_RESIDENT", "")
        not in ("", "0", "false", "no"),
        help="zero-copy resident serving loop (tpu backend): one fused "
             "device program per admission (wire decode + flow probe + "
             "classify + stats + flow insert) over donated/aliased "
             "device buffers — zero steady-state pool allocations, "
             "resident_* gauges on /metrics.  Implies a flow table (a "
             "default one is synthesized when --flow-table is absent).  "
             "CLI beats INFW_RESIDENT",
    )
    p.add_argument(
        "--telemetry", nargs="?", const="2048",
        default=os.environ.get("INFW_TELEMETRY") or None,
        help="device-resident telemetry plane (tpu backend): count-min "
             "+ top-K heavy-hitter sketches updated inside the serving "
             "dispatch, per-tenant top-talker / deny-storm / SYN-rate "
             "summaries on the obs event ring at a decimated cadence, "
             "telemetry_* counters on /metrics, and per-tenant "
             "token-bucket sampling of raw deny-event export.  Optional "
             "value = count-min width (default 2048).  CLI beats "
             "INFW_TELEMETRY",
    )
    p.add_argument(
        "--telemetry-drain", type=int,
        default=os.environ.get("INFW_TELEMETRY_DRAIN") or 256,
        help="summarizer decimation: admissions per sketch drain (one "
             "small D2H each; default 256).  CLI beats "
             "INFW_TELEMETRY_DRAIN",
    )
    p.add_argument(
        "--trace", action="store_true",
        default=os.environ.get("INFW_TRACE", "")
        not in ("", "0", "false", "no"),
        help="serving-path tracing: per-stage span clocks (ingest -> "
             "pack -> H2D -> dispatch -> materialize -> drain) exported "
             "as Prometheus histograms on /metrics, with sampled "
             "TraceSpanRecords for slow admissions on the obs event "
             "ring.  CLI beats INFW_TRACE",
    )
    p.add_argument(
        "--trace-slow-us", type=float,
        default=os.environ.get("INFW_TRACE_SLOW_US") or 50_000.0,
        help="slow-admission threshold for sampled trace records "
             "(default 50000us)",
    )
    p.add_argument(
        "--mlscore", nargs="?", const="default",
        default=os.environ.get("INFW_MLSCORE") or None,
        help="MXU anomaly-scoring tier (tpu backend): per-flow "
             "quantized decision-forest (+optional int8 MLP) inference "
             "fused into the serving dispatch — SYN-flood / port-scan "
             "/ rate-anomaly verdicts the rule tables cannot express.  "
             "Optional value = path to a versioned model artifact "
             "(.npz + .json manifest, infw.mlscore.save_model); bare "
             "flag loads the built-in detection forest.  Anomaly-"
             "verdict records ride the obs event ring, mlscore_* "
             "counters /metrics, and <state-dir>/models/ hot-swaps "
             "artifacts live (a swap behaves like a rule patch).  CLI "
             "beats INFW_MLSCORE",
    )
    p.add_argument(
        "--mlscore-mode", choices=("shadow", "enforce"),
        default=os.environ.get("INFW_MLSCORE_MODE") or "shadow",
        help="anomaly mitigation policy: shadow (default) scores and "
             "records only; enforce rewrites over-threshold flows to "
             "Deny (ruleId 0) — NEVER failsafe-port cells "
             "(infw.failsaferules, the coverage-proof port list) and "
             "never existing rule Denies.  CLI beats INFW_MLSCORE_MODE",
    )
    p.add_argument(
        "--payload", nargs="?", const="default",
        default=os.environ.get("INFW_PAYLOAD") or None,
        help="payload matching tier (tpu backend): batched "
             "Aho-Corasick multi-pattern matching over ring-sliced "
             "payload prefixes, fused into the serving dispatch.  "
             "Optional value = path to a versioned pattern-set "
             "artifact (.npz + .json manifest, "
             "infw.payload.save_patterns) or a pattern count for the "
             "seeded built-in signature set; bare flag loads the "
             "built-in set.  payload_* counters + the pattern-set "
             "version gauge export on /metrics, and "
             "<state-dir>/patterns/ hot-swaps artifacts live (an "
             "in-bucket swap recompiles nothing; a swap behaves like "
             "a rule patch).  CLI beats INFW_PAYLOAD",
    )
    p.add_argument(
        "--payload-mode", choices=("shadow", "enforce"),
        default=os.environ.get("INFW_PAYLOAD_MODE") or "shadow",
        help="payload mitigation policy: shadow (default) matches and "
             "counts only; enforce rewrites matched packets to Deny "
             "(ruleId 0) — NEVER failsafe-port cells and never "
             "existing rule Denies.  CLI beats INFW_PAYLOAD_MODE",
    )
    p.add_argument(
        "--payload-plen", type=int,
        default=int(os.environ.get("INFW_PAYLOAD_PLEN") or 0) or None,
        help="payload prefix width in bytes (64 or 128): how much of "
             "each packet's payload the ring slices and the automaton "
             "scans (prefix-truncation semantics — patterns crossing "
             "the boundary cannot match).  Default 64, or the "
             "artifact's compiled width.  CLI beats INFW_PAYLOAD_PLEN",
    )
    p.add_argument(
        "--ring",
        default=os.environ.get("INFW_RING") or None,
        help="persistent pinned host ingest ring: path of a "
             "shared-memory ring file the daemon CREATES and consumes "
             "(producers attach with tools/loadgen.py --ring PATH).  "
             "Producers write packed wire records in place; the ingest "
             "loop admits by ring cursor — no per-chunk file syscalls.  "
             "CLI beats INFW_RING",
    )
    p.add_argument(
        "--events-socket",
        default=os.environ.get("INFW_EVENTS_SOCKET", ""),
        help="unixgram socket to ship deny-event lines to (the events "
        "sidecar composition, daemonset.yaml:54-67); run "
        "`python -m infw.obs.sidecar --socket PATH` as the follower",
    )
    args = p.parse_args(argv)

    if not args.node_name:
        p.error("environment variable NODE_NAME or --node-name is required")

    # argparse validates `choices` only for explicitly passed args, not
    # env-derived defaults — a bad INFW_WIRE_CODEC must fail the launch
    # here, not fail-open later (TpuClassifier raising inside the sync
    # loop leaves an empty dataplane that PASSes every packet)
    if args.wire_codec is not None and args.wire_codec not in (
        "auto", "wire8", "delta"
    ):
        p.error(
            f"invalid INFW_WIRE_CODEC {args.wire_codec!r} "
            "(expected auto|wire8|delta)"
        )

    # Scheduler knobs share the launch-time validation posture: a
    # non-positive deadline or batch cap (flag OR env-derived) must fail
    # the launch with a usage error, not raise inside the serving loop.
    if args.deadline_us is not None and not args.deadline_us > 0:
        p.error(f"--deadline-us must be positive, got {args.deadline_us}")
    if args.max_batch is not None and args.max_batch < 1:
        p.error(f"--max-batch must be >= 1, got {args.max_batch}")
    if args.patch_staleness_us is not None and not args.patch_staleness_us > 0:
        p.error(
            f"--patch-staleness-us must be positive, got "
            f"{args.patch_staleness_us}"
        )
    if args.patch_max_ops is not None and args.patch_max_ops < 1:
        p.error(f"--patch-max-ops must be >= 1, got {args.patch_max_ops}")
    if args.tenants is not None and int(args.tenants) < 1:
        p.error(f"--tenants must be >= 1, got {args.tenants}")
    # Flow-tier knobs share the launch-time validation posture: a bad
    # entry count / way count / age horizon (flag OR env-derived) must
    # fail the launch with a usage error, not raise inside the sync loop
    # and leave an empty PASS-everything dataplane.
    flow_cfg = None
    if args.flow_table is not None and str(args.flow_table) not in (
        "0", "", "false", "no"
    ):
        if int(args.flow_table) < 1:
            p.error(f"--flow-table must be >= 1, got {args.flow_table}")
        from .flow import FlowConfig

        try:
            flow_cfg = FlowConfig.make(
                entries=int(args.flow_table),
                ways=int(os.environ.get("INFW_FLOW_WAYS") or 4),
                max_age=int(os.environ.get("INFW_FLOW_MAX_AGE")
                            or FlowConfig().max_age),
            )
        except ValueError as e:
            p.error(str(e))

    # Resident/ring knobs share the launch-time validation posture.
    if args.resident and args.backend == "cpu":
        p.error("--resident requires the tpu backend (the cpu reference "
                "classifier has no device-resident serving loop)")
    # Telemetry knobs share it too: a bad sketch width / drain cadence
    # (flag OR env-derived) fails the launch, never the sync loop.
    telemetry_spec = None
    if args.telemetry is not None and str(args.telemetry) not in (
        "0", "", "false", "no"
    ):
        if args.backend == "cpu":
            p.error("--telemetry requires the tpu backend (the cpu "
                    "reference classifier has no device sketch plane)")
        from .kernels.sketch import SketchSpec

        raw = str(args.telemetry)
        if raw in ("1", "true", "yes"):
            raw = "2048"  # bare flag / truthy env: the default geometry
        try:
            if int(raw) < 8:
                raise ValueError(
                    f"--telemetry width must be >= 8, got {raw}"
                )
            telemetry_spec = SketchSpec.make(
                width=int(raw),
                depth=int(os.environ.get("INFW_TELEMETRY_DEPTH") or 4),
                topk=int(os.environ.get("INFW_TELEMETRY_TOPK") or 256),
            )
        except ValueError as e:
            p.error(str(e))
    if int(args.telemetry_drain) < 1:
        p.error(f"--telemetry-drain must be >= 1, got "
                f"{args.telemetry_drain}")
    # Scoring knobs share the launch-time validation posture: a bad
    # model artifact, an env-derived mode typo or a cpu backend must
    # fail the launch with a usage error, never raise inside the sync
    # loop and leave an empty PASS-everything dataplane.
    mlscore_bundle = None
    if args.mlscore is not None and str(args.mlscore) not in (
        "0", "", "false", "no"
    ):
        if args.backend == "cpu":
            p.error("--mlscore requires the tpu backend (the cpu "
                    "reference classifier has no scoring plane)")
        if args.mlscore_mode not in ("shadow", "enforce"):
            p.error(f"invalid INFW_MLSCORE_MODE {args.mlscore_mode!r} "
                    "(expected shadow|enforce)")
        from .kernels.mxu_score import ScoreSpec, default_model

        raw = str(args.mlscore)
        try:
            if raw in ("default", "1", "true", "yes"):
                spec = ScoreSpec.make()
                model = default_model(spec)
            else:
                from .mlscore import load_model

                model = load_model(raw)
                spec = model.spec
            mlscore_bundle = (spec, model)
        except (ValueError, OSError) as e:
            p.error(f"--mlscore: {e}")
    elif args.mlscore_mode == "enforce":
        # scoring resolved OFF (flag absent OR an explicit falsy env
        # value like INFW_MLSCORE=0): enforce mode with no scoring tier
        # would silently serve unmitigated — fail the launch either way
        p.error("--mlscore-mode enforce requires --mlscore")
    # Payload knobs: same launch-time validation posture — a bad
    # pattern artifact, a bad prefix width or a cpu backend must fail
    # the launch with a usage error, never inside the sync loop.
    payload_patterns = None
    payload_plen = None
    if args.payload is not None and str(args.payload) not in (
        "0", "", "false", "no"
    ):
        if args.backend == "cpu":
            p.error("--payload requires the tpu backend (the cpu "
                    "reference classifier has no payload plane)")
        if args.payload_mode not in ("shadow", "enforce"):
            p.error(f"invalid INFW_PAYLOAD_MODE {args.payload_mode!r} "
                    "(expected shadow|enforce)")
        from .kernels.wire_decode import PAYLOAD_PREFIX_WIDTHS

        if args.payload_plen is not None:
            if int(args.payload_plen) not in PAYLOAD_PREFIX_WIDTHS:
                p.error(f"--payload-plen must be one of "
                        f"{PAYLOAD_PREFIX_WIDTHS}, got "
                        f"{args.payload_plen}")
            payload_plen = int(args.payload_plen)
        raw = str(args.payload)
        try:
            if raw in ("default", "1", "true", "yes") or raw.isdigit():
                from .payload import signature_patterns

                count = int(raw) if raw.isdigit() else 32
                payload_patterns = signature_patterns(
                    np.random.default_rng(0), count,
                    plen=payload_plen or PAYLOAD_PREFIX_WIDTHS[0],
                )
            else:
                from .payload import load_patterns

                payload_patterns, pspec, _pver = load_patterns(raw)
                if payload_plen is None:
                    payload_plen = int(pspec.plen)
        except (ValueError, OSError) as e:
            p.error(f"--payload: {e}")
    elif args.payload_mode == "enforce":
        # matching resolved OFF: enforce mode with no payload tier
        # would silently serve unmitigated — fail the launch
        p.error("--payload-mode enforce requires --payload")
    if not float(args.trace_slow_us) > 0:
        p.error(f"--trace-slow-us must be positive, got "
                f"{args.trace_slow_us}")
    if args.ring:
        ring_dir = os.path.dirname(os.path.abspath(args.ring)) or "."
        if not os.path.isdir(ring_dir):
            p.error(f"--ring directory does not exist: {ring_dir}")

    # Same launch-time validation posture as the wire codec: a bad
    # INFW_MESH (or --mesh) must fail here with a usage error, not raise
    # inside the sync loop and leave an empty PASS-everything dataplane.
    # Gated on the tpu backend: the cpu backend ignores the knob, and
    # importing backend.mesh (which imports jax) would break the jax-free
    # CPU deployment path for a fleet-wide INFW_MESH setting.
    if args.mesh is not None and args.backend == "tpu":
        from .backend.mesh import parse_mesh_spec

        try:
            parse_mesh_spec(args.mesh)
        except ValueError as e:
            p.error(str(e))

    logging.basicConfig(
        level=logging.INFO,
        format="%(asctime)s %(name)s %(levelname)s %(message)s",
    )
    if args.backend == "tpu":
        # Join the multi-host process group when configured
        # (INFW_COORDINATOR / INFW_NUM_PROCESSES / INFW_PROCESS_ID) — the
        # DaemonSet-scale-out analogue; single-process is a no-op.
        from .parallel.multihost import init_distributed

        init_distributed()
        # --backend tpu on a host where JAX came up on another platform
        # would serve through the CPU interpreter without saying so; only
        # an explicit JAX_PLATFORMS=cpu (the tests) may run it there.
        info = platform_mod.get_platform_info()
        cpu_asked = os.environ.get("JAX_PLATFORMS", "").strip() == "cpu"
        if info.backend != "tpu" and not cpu_asked:
            print(f"infw-daemon: --backend tpu, but JAX came up on "
                  f"{info.backend!r} (set JAX_PLATFORMS=cpu to run the "
                  "device path on the CPU on purpose)", file=sys.stderr)
            return 1
    debug = os.environ.get("ENABLE_LPM_LOOKUP_DBG", "0") not in ("0", "", "false")
    daemon = Daemon(
        state_dir=args.state_dir,
        node_name=args.node_name,
        namespace=args.namespace,
        backend=args.backend,
        poll_period_s=args.poll_period_seconds,
        debug_lookup=debug,
        metrics_port=args.metrics_port,
        health_port=args.health_port,
        ingest_chunk=args.ingest_chunk,
        max_tick_packets=args.max_tick_packets,
        event_ring_size=args.event_ring_size,
        pipeline_depth=args.pipeline_depth,
        superbatch_k=args.superbatch_k,
        events_socket=args.events_socket or None,
        fused_deep=False if args.no_fused_deep else None,
        wire_codec=args.wire_codec,
        compressed=False if args.no_compressed
        else (True if args.compressed else None),
        h2d_overlap=not args.no_h2d_overlap,
        mesh=args.mesh,
        deadline_us=args.deadline_us,
        max_batch=args.max_batch,
        patch_staleness_us=args.patch_staleness_us,
        patch_max_ops=args.patch_max_ops,
        tenants=int(args.tenants) if args.tenants else None,
        flow_table=flow_cfg,
        resident=args.resident,
        telemetry=telemetry_spec,
        telemetry_drain=int(args.telemetry_drain),
        trace=args.trace,
        trace_slow_us=float(args.trace_slow_us),
        mlscore=mlscore_bundle,
        mlscore_mode=args.mlscore_mode,
        payload=payload_patterns,
        payload_mode=args.payload_mode,
        payload_plen=payload_plen,
        ring=args.ring,
    )
    stop = threading.Event()

    def on_term(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, on_term)
    signal.signal(signal.SIGINT, on_term)
    daemon.start()
    try:
        while not stop.wait(0.5):
            pass
    finally:
        daemon.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
