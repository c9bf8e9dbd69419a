"""Device-resident telemetry plane: decimated summarizer + serving-path
tracing (ISSUE-13).

The per-packet deny-event stream is the XDP reference's observability
model, and it collapses at replay scale — millions of packets per batch
turn host-side event emission into the bottleneck.  This module is the
other half of the in-kernel sketches (kernels.sketch): aggregation
happens ON DEVICE inside the serving dispatch, and the host reads ONE
small snapshot per N admissions (the decimated drain), never per
packet.  What crosses the link per drain: the (D, W) count-min rows,
the K-slot heavy-hitter table and the per-tenant counters — a few tens
of kilobytes, amortized over thousands of admissions.

Three pieces:

- ``TelemetryTier`` — owner of the device SketchState: classic-path
  update launches (one follow-on device program per admission, no
  readback), the donated exchange the resident fused step chains
  through, the optional bit-exact HostSketchModel mirror (the
  statecheck ``telemetry`` config's oracle), and the drain itself —
  snapshot + donated zero-reset under one lock, so every count lands in
  EXACTLY one drain window regardless of concurrent patches or tenant
  swaps, and every summary record carries a gap-free ``seq`` stamp (the
  generation discipline flow entries use).
- ``summarize_snapshot`` — per-tenant top-talker / deny-storm /
  SYN-rate summary records from one drained snapshot, pushed on the obs
  event ring as line records; raw deny-event export decimates through a
  per-tenant ``TokenBucket`` (sampled evidence, never a firehose).
- ``SpanTracer`` / ``SpanHistograms`` — per-stage serving-path span
  clocks (ingest ring pop -> pack/encode -> H2D -> dispatch ->
  materialize -> drain) exported as Prometheus histograms on /metrics
  (weak-registered, the obs.statistics discipline) plus a sampled
  ``TraceSpanRecord`` on the ring for slow admissions, so "where did
  the milliseconds go" is answerable from a live daemon.
"""
from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

from ..kernels.sketch import (
    HostSketchModel,
    SketchSpec,
    SketchState,
    zero_state_host,
)

# --- summary / trace ring records --------------------------------------------


@dataclass
class TelemetrySummaryRecord:
    """One decimated drain window, exactly once: per-tenant traffic
    summaries (packets / allow / deny / pure-SYN counts with deny-storm
    and SYN-flood flags) plus the window's heavy hitters decoded from
    the device top-K table.  ``seq`` is the gap-free drain generation —
    consumers detect loss by sequence, not by absence."""

    seq: int
    admissions: int
    tenants: List[dict] = field(default_factory=list)
    top: List[dict] = field(default_factory=list)

    def lines(self) -> List[str]:
        out = [
            f"telemetry-summary seq={self.seq} "
            f"admissions={self.admissions} tenants={len(self.tenants)}"
        ]
        for t in self.tenants:
            flags = []
            if t.get("deny_storm"):
                flags.append("DENY-STORM")
            if t.get("syn_flood"):
                flags.append("SYN-FLOOD")
            tag = (" [" + ",".join(flags) + "]") if flags else ""
            out.append(
                f"\ttenant {t['tenant']}: {t['packets']} pkts, "
                f"{t['allow']} allow, {t['deny']} deny, "
                f"{t['syn']} syn{tag}"
            )
        for h in self.top:
            out.append(
                f"\ttop-talker tenant {h['tenant']} {h['src']} "
                f"{h['verdict']}: ~{h['count']} pkts"
            )
        return out


@dataclass
class TraceSpanRecord:
    """One sampled slow admission's per-stage span breakdown (the
    histogram carries the population; the record carries the shape of
    one outlier)."""

    total_us: float
    n_packets: int
    spans_us: Dict[str, float] = field(default_factory=dict)

    def lines(self) -> List[str]:
        parts = " ".join(
            f"{k}={v:.0f}us" for k, v in self.spans_us.items() if v > 0
        )
        return [
            f"trace-span: {self.total_us:.0f}us over {self.n_packets} "
            f"pkt(s) [{parts}]"
        ]


# --- token-bucket sampling ---------------------------------------------------


class TokenBucket:
    """Deterministic token bucket (rate tokens/s, ``burst`` cap).
    ``take(n, now)`` grants min(n, available) — the raw-event sampler's
    budget is a hard ceiling, never a target; time is injected so tests
    drive it deterministically."""

    def __init__(self, rate: float, burst: float) -> None:
        self.rate = float(rate)
        self.burst = float(burst)
        self._tokens = float(burst)
        self._last: Optional[float] = None
        self._lock = threading.Lock()

    def take(self, n: int, now: Optional[float] = None) -> int:
        if now is None:
            now = time.monotonic()
        with self._lock:
            if self._last is not None and now > self._last:
                self._tokens = min(
                    self.burst, self._tokens + (now - self._last) * self.rate
                )
            self._last = now
            grant = min(int(n), int(self._tokens))
            if grant > 0:
                self._tokens -= grant
            return max(grant, 0)


# --- the summarizer ----------------------------------------------------------


class SketchSnapshot(NamedTuple):
    """One drained window's host copies."""

    seq: int
    admissions: int
    cms: np.ndarray
    keys: np.ndarray
    cnt: np.ndarray
    tcnt: np.ndarray


def _format_src(keys_row: np.ndarray) -> str:
    kind = (int(keys_row[5]) >> 8) & 3
    if kind == 1:
        return ".".join(str(b) for b in int(keys_row[1]).to_bytes(4, "big"))
    import ipaddress

    return str(ipaddress.IPv6Address(
        keys_row[1:5].astype(">u4").tobytes()
    ))


def summarize_snapshot(
    snap: SketchSnapshot, *, top_n: int = 8,
    deny_storm_frac: float = 0.5, syn_flood_frac: float = 0.5,
    min_packets: int = 64,
) -> TelemetrySummaryRecord:
    """Derive the drain-window summary record from one snapshot: exact
    per-tenant counts (tcnt) drive the deny-storm / SYN-flood flags;
    the heavy-hitter table (keys sorted by estimated count, stable on
    slot order for deterministic ties) becomes the top-talker list."""
    from ..constants import ALLOW, DENY

    rec = TelemetrySummaryRecord(seq=snap.seq, admissions=snap.admissions)
    for t in np.nonzero(snap.tcnt[:, 0] > 0)[0]:
        pkts, allow, deny, syn = (int(x) for x in snap.tcnt[t])
        rec.tenants.append({
            "tenant": int(t), "packets": pkts, "allow": allow,
            "deny": deny, "syn": syn,
            "deny_storm": pkts >= min_packets
            and deny >= deny_storm_frac * pkts,
            "syn_flood": pkts >= min_packets
            and syn >= syn_flood_frac * pkts,
        })
    occ = np.nonzero(snap.cnt > 0)[0]
    # stable sort on (-count, slot): deterministic ties
    order = occ[np.argsort(-snap.cnt[occ], kind="stable")][:top_n]
    for slot in order:
        row = snap.keys[slot]
        act = int(row[5]) & 0xFF
        rec.top.append({
            "tenant": int(row[0]),
            "src": _format_src(row),
            "verdict": {DENY: "deny", ALLOW: "allow"}.get(act, f"act{act}"),
            "count": int(snap.cnt[slot]),
            "slot": int(slot),
        })
    return rec


# --- the device tier ---------------------------------------------------------


class TelemetryTier:
    """Host-side owner of the device telemetry plane.

    Thread-safety / ordering: every device mutation (classic update
    launch, resident donated exchange, drain snapshot+reset) runs under
    ONE lock, so sketch updates land in a total device order; the
    optional HostSketchModel mirror replays the SAME order through a
    pending queue (resident admissions' verdicts are host-resident only
    at materialize, the FlowTier mirror discipline).  Lock nesting: the
    flow tier's dispatch lock may be held when this lock is taken,
    never the reverse.
    """

    def __init__(self, spec: SketchSpec, device=None,
                 track_model: bool = False, drain_every: int = 256,
                 sample_rate: float = 128.0, sample_burst: float = 256.0,
                 ring=None) -> None:
        import jax
        import jax.numpy as jnp

        self.spec = spec
        self._device = device
        self._lock = threading.Lock()
        host = zero_state_host(spec)
        put = lambda a: jax.device_put(jnp.asarray(a), device)
        self._state = SketchState(*(put(a) for a in host))
        self.model = HostSketchModel(spec) if track_model else None
        #: pending model mirrors in device-dispatch order: entries whose
        #: verdicts are still device-resident hold the fused buffer and
        #: a decoder; replay drains the head as results materialize
        self._mirror_q: list = []
        self.drain_every = int(drain_every)
        self._admissions = 0
        self._window_admissions = 0
        self._drain_seq = 0
        self._ring = ring
        #: per-tenant raw deny-event sampling budget (events/s + burst):
        #: the firehose replacement — summaries carry the totals, the
        #: bucket releases bounded raw evidence
        self._sample_rate = float(sample_rate)
        self._sample_burst = float(sample_burst)
        self._buckets: Dict[int, TokenBucket] = {}
        self._zeros_cache: Dict[int, tuple] = {}
        self.counters = {
            "updates": 0, "drains": 0, "summaries": 0,
            "sampled_events": 0, "suppressed_events": 0,
        }
        #: summary knobs (summarize_snapshot)
        self.top_n = 8
        self.deny_storm_frac = 0.5
        self.syn_flood_frac = 0.5
        self.min_packets = 64

    # -- plumbing ------------------------------------------------------------

    def attach_ring(self, ring) -> None:
        with self._lock:
            self._ring = ring

    def _put(self, a):
        import jax

        return jax.device_put(a, self._device)

    def _zeros(self, b: int):
        z = self._zeros_cache.get(b)
        if z is None:
            z = (
                self._put(np.zeros(b, np.int32)),
                self._put(np.zeros(b, np.int32)),
            )
            self._zeros_cache[b] = z
        return z

    def _note(self, key: str, n: int = 1) -> None:
        self.counters[key] = self.counters.get(key, 0) + n

    # -- updates -------------------------------------------------------------

    def update(self, wire_np: np.ndarray, res: np.ndarray,
               tenant_np: Optional[np.ndarray] = None,
               tflags_np: Optional[np.ndarray] = None) -> None:
        """The multi-dispatch path's telemetry launch: ONE device
        program per admission over (wire, verdicts), donated state, no
        readback — dispatched at materialize time, when the merged
        verdicts exist host-side."""
        from ..kernels import sketch as sketch_mod

        b = wire_np.shape[0]
        wire = self._put(np.ascontiguousarray(wire_np, np.uint32))
        res_dev = self._put(np.asarray(res, np.uint32))
        zt, zf = None, None
        if tenant_np is None or tflags_np is None:
            zt, zf = self._zeros(b)
        tenant = (zt if tenant_np is None
                  else self._put(np.ascontiguousarray(tenant_np, np.int32)))
        tflags = (zf if tflags_np is None
                  else self._put(np.ascontiguousarray(tflags_np, np.int32)))
        fn = sketch_mod.jitted_sketch_update(self.spec)
        with self._lock:
            self._state = fn(self._state, wire, tenant, tflags, res_dev)
            self._admissions += 1
            self._window_admissions += 1
            self._note("updates")
            if self.model is not None:
                self._mirror_q.append(
                    (np.asarray(wire_np, np.uint32).copy(),
                     None if tenant_np is None
                     else np.asarray(tenant_np, np.int32).copy(),
                     None if tflags_np is None
                     else np.asarray(tflags_np, np.int32).copy(),
                     np.asarray(res, np.uint32).copy(), None)
                )
                self._replay_ready_locked()
        self.maybe_drain()

    def resident_exchange(self, launch: Callable, epoch: int,
                          wire_np, tenant_np, tflags_np):
        """The resident fused step's donated sketch chain: ``launch(sk)
        -> (sk', rest)`` runs under this tier's lock so telemetry
        updates land in device-dispatch order; the model mirror (when
        tracking) queues with the fused buffer and replays once the
        admission materializes (resident_note_materialized)."""
        with self._lock:
            sk2, rest = launch(self._state)
            self._state = sk2
            self._admissions += 1
            self._window_admissions += 1
            self._note("updates")
            if self.model is not None:
                fused = rest[-1]
                self._mirror_q.append(
                    (np.asarray(wire_np, np.uint32).copy(),
                     None if tenant_np is None
                     else np.asarray(tenant_np, np.int32).copy(),
                     None if tflags_np is None
                     else np.asarray(tflags_np, np.int32).copy(),
                     None, fused)
                )
        return rest

    def resident_exchange_super(self, launch: Callable, epoch0: int,
                                k: int, wire_np, tenant_np, tflags_np):
        """The superbatch variant of ``resident_exchange`` (ISSUE-16):
        one launch carries ``k`` stacked admissions, the donated sketch
        state chained through the device-side scan carry — so the model
        mirror queues ``k`` entries, one per admission, each holding its
        row of the stacked (k, L) fused readback."""
        with self._lock:
            sk2, rest = launch(self._state)
            self._state = sk2
            self._admissions += k
            self._window_admissions += k
            self._note("updates", k)
            if self.model is not None:
                fused = rest[-1]
                wire_stack = np.asarray(wire_np, np.uint32)
                for j in range(k):
                    self._mirror_q.append(
                        (wire_stack[j].copy(),
                         None if tenant_np is None
                         else np.asarray(tenant_np[j], np.int32).copy(),
                         None if tflags_np is None
                         else np.asarray(tflags_np[j], np.int32).copy(),
                         None, (fused, j))
                    )
        return rest

    def _replay_ready_locked(self) -> None:
        """Drain the head of the mirror queue in device order.  A
        resident entry's verdicts live in its fused buffer (or its row
        of a superbatch's stacked readback) — resident_fused_host
        blocks until the dispatch lands, which is correct (the entry is
        already in flight) and keeps classic entries behind it in
        order."""
        from ..kernels import jaxpath

        while self._mirror_q:
            wire, tenant, tflags, res, fused = self._mirror_q[0]
            if res is None:
                res16, _hit, _h, _s, _c = jaxpath.split_resident_outputs(
                    jaxpath.resident_fused_host(fused), wire.shape[0]
                )
                res = res16.astype(np.uint32)
            self.model.update(wire, res, tenant, tflags)
            self._mirror_q.pop(0)

    def resident_note_materialized(self, epoch: int) -> None:
        """Materialize hook for resident admissions: replay pending
        model mirrors (track_model only) and run the decimated-drain
        cadence check — the resident exchange itself only counts the
        window (it runs under the lock), so this is where drain_every
        fires on the resident path."""
        if self.model is not None:
            with self._lock:
                self._replay_ready_locked()
        self.maybe_drain()

    # -- the decimated drain -------------------------------------------------

    def maybe_drain(self) -> List[TelemetrySummaryRecord]:
        """Drain when the decimation cadence is due (one small D2H per
        ``drain_every`` admissions, NEVER per packet)."""
        with self._lock:
            due = self._window_admissions >= self.drain_every
        return self.drain() if due else []

    def drain(self, force: bool = True) -> List[TelemetrySummaryRecord]:
        """Snapshot + reset the device tensors and emit the window's
        summary record(s) on the attached ring.

        Exactly-once contract: snapshot and reset happen under the
        tier lock, atomically with the admission counters — every
        admission's counts land in exactly one window, every window
        drains exactly once, and ``seq`` stamps are gap-free even under
        concurrent classify / patch / tenant-swap traffic (mutations
        elsewhere never touch sketch state; dispatches serialize on
        this lock)."""
        from ..kernels import sketch as sketch_mod

        with self._lock:
            if not force and self._window_admissions < self.drain_every:
                return []
            if self.model is not None:
                self._replay_ready_locked()
            snap = SketchSnapshot(
                seq=self._drain_seq + 1,
                admissions=self._window_admissions,
                cms=np.asarray(self._state.cms),
                keys=np.asarray(self._state.keys),
                cnt=np.asarray(self._state.cnt),
                tcnt=np.asarray(self._state.tcnt),
            )
            self._state = sketch_mod.jitted_sketch_clear()(self._state)
            if self.model is not None:
                self.model.clear()
            self._drain_seq += 1
            self._window_admissions = 0
            self._note("drains")
            # summarize + publish INSIDE the lock: ring consumers see
            # records in strict seq order even when drains race (the
            # summary is a few hundred rows of host numpy — decimated,
            # never on the per-admission path)
            rec = summarize_snapshot(
                snap, top_n=self.top_n,
                deny_storm_frac=self.deny_storm_frac,
                syn_flood_frac=self.syn_flood_frac,
                min_packets=self.min_packets,
            )
            self._note("summaries")
            if self._ring is not None:
                self._ring.push(rec)
        return [rec]

    # -- raw-event sampling --------------------------------------------------

    def sample_allow(self, tenant: int, n: int,
                     now: Optional[float] = None) -> int:
        """How many of ``n`` raw deny events tenant ``tenant`` may
        export right now (per-tenant token bucket) — the adaptive
        replacement for the full firehose.  Suppressed counts surface
        on /metrics; the totals are ALWAYS exact in the sketch
        summaries."""
        with self._lock:
            bucket = self._buckets.get(int(tenant))
            if bucket is None:
                bucket = TokenBucket(self._sample_rate, self._sample_burst)
                self._buckets[int(tenant)] = bucket
        grant = bucket.take(n, now)
        with self._lock:
            self._note("sampled_events", grant)
            self._note("suppressed_events", int(n) - grant)
        return grant

    # -- introspection -------------------------------------------------------

    def columns(self) -> Dict[str, np.ndarray]:
        """Host copies of the device tensors (the model-compare side).
        Materialized INSIDE the lock: the state is donated per
        admission, so an off-lock snapshot could be consumed mid-read."""
        with self._lock:
            s = self._state
            return {k: np.asarray(getattr(s, k)) for k in s._fields}

    @property
    def admissions(self) -> int:
        with self._lock:
            return self._admissions

    @property
    def drain_seq(self) -> int:
        with self._lock:
            return self._drain_seq

    def counter_values(self) -> Dict[str, int]:
        """telemetry_* counters for /metrics."""
        with self._lock:
            out = {
                f"telemetry_{k}_total": int(v)
                for k, v in self.counters.items()
            }
            out["telemetry_admissions_total"] = self._admissions
            out["telemetry_drain_seq"] = self._drain_seq
            out["telemetry_window_admissions"] = self._window_admissions
        return out

    def warm(self, ladder) -> int:
        """Pre-compile the classic sketch-update executable for every
        wire shape in ``ladder`` (inert KIND_OTHER rows: every lane
        ineligible, state bit-unchanged) — the zero-recompile serving
        contract, same shape discipline as FlowTier.warm.  Dispatches
        the jitted update directly: prewarm launches must NOT count as
        admissions (telemetry_* counters, the drain window and the
        model mirror all see served traffic only)."""
        from ..kernels import sketch as sketch_mod

        fn = sketch_mod.jitted_sketch_update(self.spec)
        n = 0
        for b in sorted(set(int(x) for x in ladder)):
            for width in (4, 7):
                wire_np = np.zeros((b, width), np.uint32)
                wire_np[:, 0] = 3  # KIND_OTHER
                wire = self._put(wire_np)
                zt, zf = self._zeros(b)
                res = self._put(np.zeros(b, np.uint32))
                with self._lock:
                    self._state = fn(self._state, wire, zt, zf, res)
                n += 1
        return n


# --- serving-path tracing ----------------------------------------------------

#: the span taxonomy, in serving order: ingest (ring pop / file read
#: wait), pack (parse + wire pack + encode), h2d (staging device_put),
#: dispatch (program launch), inflight (launched, waiting for the drain
#: to reach it behind the records ahead of it; the ring path),
#: materialize (readback + host finalize), drain (event/stat emission)
SPAN_STAGES = ("ingest", "pack", "h2d", "dispatch", "inflight",
               "materialize", "drain")

#: log2 bucket upper bounds in microseconds: 1us .. ~1.05s, +Inf
SPAN_BUCKETS_US = tuple(float(1 << i) for i in range(21))


class SpanHistograms:
    """Fixed-bucket per-stage latency histograms, rendered in the
    Prometheus histogram exposition.  Registered WEAKLY in the metrics
    registry (obs.statistics.Registry.register_histograms) so a dropped
    daemon generation disappears from /metrics instead of double
    counting after a reload — and a LIVE tracer survives the reload
    (the weak-registry discipline)."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        nb = len(SPAN_BUCKETS_US) + 1
        self._counts = {s: np.zeros(nb, np.int64) for s in SPAN_STAGES}
        self._sums_us = {s: 0.0 for s in SPAN_STAGES}
        self._totals = {s: 0 for s in SPAN_STAGES}

    def observe(self, stage: str, us: float) -> None:
        if stage not in self._counts:
            return
        us = max(float(us), 0.0)
        i = int(np.searchsorted(SPAN_BUCKETS_US, us))
        with self._lock:
            self._counts[stage][i] += 1
            self._sums_us[stage] += us
            self._totals[stage] += 1

    def values(self) -> Dict[str, dict]:
        with self._lock:
            return {
                s: {
                    "count": int(self._totals[s]),
                    "sum_us": float(self._sums_us[s]),
                    "buckets": self._counts[s].copy(),
                }
                for s in SPAN_STAGES
            }

    def render_histograms(self) -> str:
        """Prometheus histogram text: one series per stage under
        ingressnodefirewall_node_span_us{stage=...}."""
        name = "ingressnodefirewall_node_span_us"
        out = [
            f"# HELP {name} Serving-path span latency by stage "
            "(microseconds)",
            f"# TYPE {name} histogram",
        ]
        vals = self.values()
        for s in SPAN_STAGES:
            v = vals[s]
            cum = 0
            for le, c in zip(SPAN_BUCKETS_US, v["buckets"]):
                cum += int(c)
                out.append(
                    f'{name}_bucket{{stage="{s}",le="{le:g}"}} {cum}'
                )
            cum += int(v["buckets"][-1])
            out.append(f'{name}_bucket{{stage="{s}",le="+Inf"}} {cum}')
            out.append(f'{name}_sum{{stage="{s}"}} {v["sum_us"]:.0f}')
            out.append(f'{name}_count{{stage="{s}"}} {v["count"]}')
        return "\n".join(out) + "\n"


class AdmissionTrace:
    """Span clock of one admission: ``mark(stage)`` charges the time
    since the previous mark to ``stage`` (monotonic clock); ``add``
    charges an externally measured interval."""

    __slots__ = ("spans_us", "_t_last", "t0", "n_packets")

    def __init__(self, n_packets: int = 0) -> None:
        self.t0 = time.perf_counter()
        self._t_last = self.t0
        self.spans_us: Dict[str, float] = {}
        self.n_packets = int(n_packets)

    def mark(self, stage: str) -> None:
        now = time.perf_counter()
        self.spans_us[stage] = (
            self.spans_us.get(stage, 0.0) + (now - self._t_last) * 1e6
        )
        self._t_last = now

    def add(self, stage: str, dt_s: float) -> None:
        self.spans_us[stage] = (
            self.spans_us.get(stage, 0.0) + float(dt_s) * 1e6
        )
        self._t_last = time.perf_counter()

    @property
    def total_us(self) -> float:
        return sum(self.spans_us.values())


class SpanTracer:
    """End-to-end serving-path tracer: histograms for the population,
    token-bucket-sampled TraceSpanRecords for slow admissions, and
    program spans on the profiler's clock (``span``)."""

    def __init__(self, ring=None, histograms: Optional[SpanHistograms] = None,
                 slow_us: float = 50_000.0, sample_rate: float = 4.0,
                 sample_burst: float = 16.0) -> None:
        from jax.profiler import TraceAnnotation

        self._annotation = TraceAnnotation
        self.histograms = histograms or SpanHistograms()
        self._ring = ring
        self.slow_us = float(slow_us)
        self._bucket = TokenBucket(sample_rate, sample_burst)
        self._lock = threading.Lock()
        self.counters = {"traces": 0, "slow_sampled": 0,
                         "slow_suppressed": 0}

    def attach_ring(self, ring) -> None:
        with self._lock:
            self._ring = ring

    def begin(self, n_packets: int = 0) -> AdmissionTrace:
        return AdmissionTrace(n_packets)

    def span(self, name: str, seq: Optional[int] = None):
        """A context manager that records ``name`` as a span of an
        in-process ``jax.profiler`` trace, on the clock of the device
        ops, with the ring record's ``seq`` as its identifier; nothing is
        recorded while no trace is being taken.  The profiler keeps the
        spans in memory and writes them when the trace stops.  ``seq``
        can be set once known: ``with tracer.span(n) as s:
        s.set_metadata(seq=...)``."""
        if seq is None:
            return self._annotation(name)
        return self._annotation(name, seq=int(seq))

    def finish(self, trace: AdmissionTrace,
               now: Optional[float] = None) -> None:
        for stage, us in trace.spans_us.items():
            self.histograms.observe(stage, us)
        total = trace.total_us
        with self._lock:
            self.counters["traces"] += 1
            ring = self._ring
        if total >= self.slow_us:
            if self._bucket.take(1, now):
                with self._lock:
                    self.counters["slow_sampled"] += 1
                if ring is not None:
                    ring.push(TraceSpanRecord(
                        total_us=total, n_packets=trace.n_packets,
                        spans_us=dict(trace.spans_us),
                    ))
            else:
                with self._lock:
                    self.counters["slow_suppressed"] += 1

    def counter_values(self) -> Dict[str, int]:
        with self._lock:
            return {
                f"trace_{k}_total": int(v)
                for k, v in self.counters.items()
            }
