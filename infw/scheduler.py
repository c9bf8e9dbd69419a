"""Deadline-aware continuous microbatching: the serving-tier scheduler.

At serving scale, tail latency is decided by how arrivals are coalesced
into device-sized work units, not by the kernel alone (hXDP makes the
same argument for offloaded packet processing): every dispatch pays a
fixed host-device round trip on top of its compute.  This module turns that into policy:

- **admit-by-deadline, not by fixed chunk size** (``DeadlinePolicy``):
  while the device pipeline is busy, arrivals queue; each admission
  takes the LARGEST batch whose oldest packet still meets its per-packet
  deadline budget given the measured service time of that batch size.
  When the pipeline has a free slot the policy is work-conserving — the
  queued packets ship immediately, whatever their count — so the device
  never idles while packets wait (continuous batching, the vLLM-style
  serving loop applied to packet verdicts).
- **service-time model** (``ServiceModel``): an EWMA of observed
  dispatch->materialize latency per batch-size bucket, so the admission
  decision reasons about THIS deployment's measured service curve (the
  round trip differs by orders of magnitude between deployments)
  instead of a constant.
- **batch-size ladder** (``batch_ladder``): admitted batches pad to
  power-of-two buckets from ``MIN_LADDER_BATCH`` (32) up to the admission cap, and
  ``prewarm_ladder`` runs every ladder shape through the production
  dispatch once at startup so shape-driven jit recompiles never land on
  the serving path.
- **mesh spillover** (``ContinuousScheduler(spill_clf=...)``): a
  coalesced batch larger than the per-chip budget dispatches through the
  MeshTpuClassifier, which shards it over the ``"data"`` axis; on a
  single-chip pool (no spill target) the oversized admission is split
  into per-chip-budget jobs instead — degrade, never refuse.
- **update-storm interleaving** (``ContinuousScheduler(txn_batcher=...,
  txn_flush=...)``): queued rule edits (infw.txn) flush under their
  bounded-staleness policy WHILE serving — a tripped flush runs on its
  own thread occupying ONE pipeline slot instead of stalling
  admissions, and in-flight classifies finish on the table generation
  they were dispatched against (the double-buffer swap contract).

Observability: ``SchedulerStats`` exports queue depth, the achieved
batch-size histogram, deadline-miss and spill counters through the
metrics registry's counter-provider protocol, and every deadline miss
emits a ``DeadlineMissRecord`` on the obs event ring.

Latency accounting is coordinated-omission-safe: a packet's verdict
latency is measured from its SCHEDULED arrival time (the open-loop load
generator's timestamp), never from when the scheduler got around to
dequeuing it — a backlogged scheduler therefore reports the queueing it
caused instead of hiding it (the classic closed-loop p99 underreport).
"""
from __future__ import annotations

import logging
import threading
import time
from collections import deque
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import numpy as np

from ._threads import spawn
from .constants import KIND_IPV6, KIND_OTHER
from .packets import PacketBatch

log = logging.getLogger("infw.scheduler")

#: smallest admitted batch bucket — the BENCH_r05 anomaly shape (batch=32
#: read 11.77 ms p50-above-floor while 64/128 read ~0, a first-dispatch
#: jit specialization landing inside the timed path); the ladder starts
#: here precisely so the pre-warm covers it
MIN_LADDER_BATCH = 32


def batch_ladder(max_batch: int) -> Tuple[int, ...]:
    """Power-of-two admission buckets MIN_LADDER_BATCH..max_batch (the
    cap itself is always the last step, pow2 or not) — every batch shape
    the scheduler can emit, and therefore every shape prewarm_ladder
    must cover."""
    max_batch = max(int(max_batch), MIN_LADDER_BATCH)
    steps: List[int] = []
    b = MIN_LADDER_BATCH
    while b < max_batch:
        steps.append(b)
        b <<= 1
    steps.append(max_batch)
    return tuple(steps)


def ladder_bucket(n: int, max_batch: int) -> int:
    """Smallest ladder step >= n (capped at max_batch): the padded batch
    size an n-packet admission dispatches as."""
    if n >= max_batch:
        return int(max_batch)
    return min(1 << max(MIN_LADDER_BATCH.bit_length() - 1,
                        (n - 1).bit_length()), int(max_batch))


def ladder_floor(n: int, max_batch: int) -> int:
    """Largest ladder step <= n (never below the smallest step): the
    admission-cap quantizer — a cap that is itself a ladder member can
    only ever produce pre-warmed dispatch shapes, whatever batch sizes
    the service model's evolving estimates suggest."""
    best = MIN_LADDER_BATCH
    for b in batch_ladder(max_batch):
        if b <= n:
            best = b
        else:
            break
    return best


class ServiceModel:
    """EWMA service-time estimate per batch-size bucket.

    Unobserved buckets fall back to the nearest observed bucket (the
    service curve is RPC-floor-flat for small batches and near-linear
    for large ones, so nearest-bucket is conservative in both regimes);
    a fully cold model uses ``base + per_packet * n`` seeds."""

    def __init__(self, default_base_s: float = 1e-3,
                 default_per_packet_s: float = 1e-6,
                 alpha: float = 0.3) -> None:
        self._base = float(default_base_s)
        self._per_packet = float(default_per_packet_s)
        self._alpha = float(alpha)
        self._lock = threading.Lock()
        self._est: Dict[int, float] = {}

    def observe(self, bucket: int, dt_s: float) -> None:
        if dt_s <= 0:
            return
        b = int(bucket)
        with self._lock:
            prev = self._est.get(b)
            self._est[b] = (
                dt_s if prev is None
                else prev + self._alpha * (dt_s - prev)
            )

    def estimate(self, bucket: int) -> float:
        b = int(bucket)
        with self._lock:
            if not self._est:
                return self._base + self._per_packet * b
            got = self._est.get(b)
            if got is not None:
                return got
            nearest = min(self._est, key=lambda k: abs(k.bit_length()
                                                       - b.bit_length()))
            return self._est[nearest]

    def snapshot(self) -> Dict[int, float]:
        with self._lock:
            return dict(self._est)


class SchedulerStats:
    """Thread-safe scheduler observability, exported through the metrics
    registry's counter-provider protocol (Registry.register_counters):
    admitted packets, dispatched batches, the achieved batch-size
    histogram (per ladder bucket), deadline misses, mesh spills, and the
    instantaneous queue depth."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self.admitted_total = 0
        self.batches_total = 0
        self.miss_total = 0
        self.completed_total = 0
        self.spilled_batches_total = 0
        self.queue_depth = 0
        self.batch_hist: Dict[int, int] = {}

    def set_queue_depth(self, n: int) -> None:
        with self._lock:
            self.queue_depth = int(n)

    def note_admit(self, n: int, bucket: int, spilled: bool = False) -> None:
        with self._lock:
            self.admitted_total += int(n)
            self.batches_total += 1
            self.batch_hist[int(bucket)] = (
                self.batch_hist.get(int(bucket), 0) + 1
            )
            if spilled:
                self.spilled_batches_total += 1

    def note_complete(self, n: int, misses: int) -> None:
        with self._lock:
            self.completed_total += int(n)
            self.miss_total += int(misses)

    def counter_values(self) -> Dict[str, int]:
        """Prometheus counter sources, rendered by the metrics registry
        as ingressnodefirewall_node_scheduler_* (queue depth is an
        instantaneous gauge riding the same channel)."""
        with self._lock:
            out = {
                "scheduler_admitted_packets_total": self.admitted_total,
                "scheduler_batches_total": self.batches_total,
                "scheduler_deadline_miss_total": self.miss_total,
                "scheduler_completed_packets_total": self.completed_total,
                "scheduler_spilled_batches_total": self.spilled_batches_total,
                "scheduler_queue_depth": self.queue_depth,
            }
            for b, c in sorted(self.batch_hist.items()):
                out[f"scheduler_batch_size_{b}_total"] = c
            return out

    def snapshot(self) -> Dict[str, object]:
        with self._lock:
            return {
                "admitted": self.admitted_total,
                "batches": self.batches_total,
                "misses": self.miss_total,
                "completed": self.completed_total,
                "spilled_batches": self.spilled_batches_total,
                "queue_depth": self.queue_depth,
                "batch_hist": dict(self.batch_hist),
            }


class WireStatsCounters:
    """Adapter exposing a classifier's per-format H2D accounting
    (``TpuClassifier.wire_stats()``) as metrics-registry counters:
    ingressnodefirewall_node_wire_<fmt>_{packets,bytes}_total.  Takes a
    zero-arg getter (not the classifier) so the provider survives table
    reloads and backend swaps; classifiers without wire_stats (the CPU
    reference) render nothing."""

    def __init__(self, clf_getter: Callable[[], object]) -> None:
        self._get = clf_getter

    def counter_values(self) -> Dict[str, int]:
        clf = self._get()
        ws = getattr(clf, "wire_stats", None)
        if clf is None or ws is None:
            return {}
        out: Dict[str, int] = {}
        for fmt, (pkts, nbytes) in sorted(ws().items()):
            out[f"wire_{fmt}_packets_total"] = int(pkts)
            out[f"wire_{fmt}_bytes_total"] = int(nbytes)
        return out


class AdmitDecision(NamedTuple):
    n: int                  # packets to admit now (0 = keep waiting)
    wait_s: Optional[float]  # max time to wait before re-deciding


class DeadlinePolicy:
    """Admit-by-deadline batch coalescing.

    ``admit`` is called with the queue state and the number of batches
    currently in the dispatch pipeline; it returns how many packets to
    admit NOW (0 = wait up to ``wait_s`` for the batch to grow).  Rules,
    in order:

    1. queue >= max_admit: ship a full admission (overload — coalescing
       can only help, the deadline is already the queue's problem).
    2. pipeline has a free slot (in_flight < busy_depth): ship whatever
       is queued immediately — work-conserving, the device must never
       idle while packets wait (the "continuous" in continuous
       batching).
    3. otherwise the oldest packet's remaining slack is
       deadline - wait - est_service(bucket) - margin: positive slack
       means waiting grows the batch for free (largest-batch-that-meets-
       deadline); exhausted slack ships the queue as-is.
    """

    def __init__(self, deadline_s: float, max_admit: int,
                 service: Optional[ServiceModel] = None,
                 margin_frac: float = 0.1, busy_depth: int = 2) -> None:
        if deadline_s <= 0:
            raise ValueError(f"deadline must be positive, got {deadline_s}")
        if max_admit < 1:
            raise ValueError(f"max_admit must be >= 1, got {max_admit}")
        self.deadline_s = float(deadline_s)
        self.max_admit = int(max_admit)
        self.service = service if service is not None else ServiceModel()
        self.margin_s = float(margin_frac) * self.deadline_s
        self.busy_depth = max(1, int(busy_depth))

    def admit(self, now: float, queue_len: int, oldest_ts: float,
              in_flight: int, eof: bool = False) -> AdmitDecision:
        if queue_len <= 0:
            return AdmitDecision(0, None)
        if queue_len >= self.max_admit:
            return AdmitDecision(self.max_admit, 0.0)
        if in_flight < self.busy_depth or eof:
            return AdmitDecision(queue_len, 0.0)
        bucket = ladder_bucket(queue_len, self.max_admit)
        slack = (
            self.deadline_s - (now - oldest_ts)
            - self.service.estimate(bucket) - self.margin_s
        )
        if slack <= 0:
            return AdmitDecision(queue_len, 0.0)
        return AdmitDecision(0, slack)

    def service_cap(self) -> int:
        """Largest ladder batch whose estimated service time still fits
        inside the deadline budget — the replay-tick analogue of the
        admit rule, where every queued packet shares one arrival burst
        and batch size is the only latency lever.  Never below the
        smallest ladder step: a deadline tighter than one minimal
        dispatch cannot be met by starving the queue."""
        cap = MIN_LADDER_BATCH
        for b in batch_ladder(self.max_admit):
            if self.service.estimate(b) + self.margin_s <= self.deadline_s:
                cap = b
            else:
                break
        return cap


class FixedChunkPolicy:
    """The pre-scheduler baseline as a policy: dispatch only when the
    queue holds a full ``chunk`` (the daemon's historical fixed
    ``ingest_chunk`` behavior), flushing the remainder at end of stream.
    Exists so bench_slo can A/B the deadline scheduler against the exact
    semantics it replaced, in the same record."""

    def __init__(self, chunk: int) -> None:
        self.max_admit = int(chunk)
        self.deadline_s = float("inf")
        self.service = ServiceModel()

    def admit(self, now: float, queue_len: int, oldest_ts: float,
              in_flight: int, eof: bool = False) -> AdmitDecision:
        if queue_len >= self.max_admit:
            return AdmitDecision(self.max_admit, 0.0)
        if eof and queue_len > 0:
            return AdmitDecision(queue_len, 0.0)
        return AdmitDecision(0, None)

    def service_cap(self) -> int:
        return self.max_admit


# -- ladder pre-warm ---------------------------------------------------------


def _inert_wire(n: int, width: int) -> np.ndarray:
    """(n, width) KIND_OTHER wire rows: always PASS, no stats — the
    shape-only payload of the pre-warm dispatches."""
    w = np.zeros((n, width), np.uint32)
    w[:, 0] = KIND_OTHER
    return w


def prewarm_ladder(clf, ladder, include_depth_classes: bool = True,
                   service: Optional[ServiceModel] = None) -> int:
    """Run every wire shape the scheduler can emit through the
    production dispatch once, so jit specialization (and each
    executable's first-dispatch cost) happens at startup
    instead of inside a serving-path latency budget.

    Covers, per ladder size: the v4-compact 4-word wire (the v4_only
    specialization) and the 7-word mixed-family wire, the latter across
    every depth-steering class of the current table generation
    including the declared full-depth class.  Classifiers
    without the packed wire contract (the CPU reference) are a no-op.
    Returns the number of dispatches; failures degrade to fewer warmed
    shapes, never to an exception — a cold shape costs one compile at
    serve time, exactly what this makes rare."""
    supports = getattr(clf, "supports_packed", None)
    if supports is None or not supports():
        return 0
    depth_keys: List[Optional[tuple]] = [None]
    if include_depth_classes:
        # every steering class of the CURRENT generation plus the
        # declared full-depth class (the fused-walk shape)
        shape_classes = getattr(clf, "serving_shape_classes", None)
        if shape_classes is not None:
            depth_keys += list(shape_classes())
    n_done = 0
    t0 = time.perf_counter()
    for bs in ladder:
        # the two wire shapes the pack path can emit: the v4-compact
        # 4-word wire (v4_only jobs) and the 7-word mixed/v6 wire; the
        # depth-steering jit variants specialize the 7-word v6 walk
        for width, v4_only in ((4, True), (7, False)):
            wire = _inert_wire(int(bs), width)
            for depth in (depth_keys if width == 7 else [None]):
                try:
                    if hasattr(clf, "prepare_packed"):
                        pending = clf.classify_prepared(
                            clf.prepare_packed(wire, v4_only, depth=depth),
                            apply_stats=False,
                        )
                    else:
                        pending = clf.classify_async_packed(
                            wire, v4_only, apply_stats=False, depth=depth,
                        )
                    pending.result()
                    n_done += 1
                except Exception as e:  # degrade, never refuse
                    log.debug("prewarm skip @%d w%d v4=%s depth=%s: %s",
                              bs, width, v4_only, depth, e)
    warm_flow = getattr(clf, "warm_flow_ladder", None)
    if warm_flow is not None:
        # stateful flow tier: pre-compile the probe/insert executables
        # for every ladder shape too, so the warm flow lifecycle (probe,
        # miss fall-through, batch insert, age) is compile-free on the
        # serving path — the same zero-recompile contract as the
        # classify ladder above (also covers the pow2 miss buckets,
        # which are a subset of the ladder shapes).
        try:
            n_done += int(warm_flow([int(b) for b in ladder]) or 0)
        except Exception as e:  # degrade, never refuse
            log.debug("flow prewarm skipped: %s", e)
    warm_tel = getattr(clf, "warm_telemetry_ladder", None)
    if warm_tel is not None:
        # telemetry plane (ISSUE-13): the ladder loop above warmed the
        # resident fused sketch variants through the production
        # dispatch; this compiles the classic follow-on sketch-update
        # launch for every ladder shape too, so telemetry never costs a
        # serving-path compile in either dispatch mode
        try:
            n_done += int(warm_tel([int(b) for b in ladder]) or 0)
        except Exception as e:  # degrade, never refuse
            log.debug("telemetry prewarm skipped: %s", e)
    warm_ml = getattr(clf, "warm_mlscore_ladder", None)
    if warm_ml is not None:
        # anomaly scoring (ISSUE-14): the ladder loop above warmed the
        # resident fused score variants through the production
        # dispatch; this compiles the classic follow-on score-update
        # launch for every ladder shape too, so scoring never costs a
        # serving-path compile in either dispatch mode
        try:
            n_done += int(warm_ml([int(b) for b in ladder]) or 0)
        except Exception as e:  # degrade, never refuse
            log.debug("mlscore prewarm skipped: %s", e)
    mark_resident = getattr(clf, "mark_resident_warm", None)
    if mark_resident is not None:
        # resident-pool-aware prewarm (ISSUE-12): the ladder loop above
        # already compiled every resident fused program and allocated
        # the per-rung pool state (zero columns, epoch seed, table
        # context) through the production dispatch; freeze the pool's
        # allocation baseline HERE so any later pool allocation is, by
        # definition, a serving-path allocation — the zero-alloc
        # steady-state gate bench_resident asserts
        try:
            mark_resident()
        except Exception as e:  # degrade, never refuse
            log.debug("resident warm mark skipped: %s", e)
    if service is not None:
        # seed the admission policy's service model with a COMPILE-FREE
        # timing sample per ladder step (the shapes are warm now), so
        # the first real admissions size against measured service times
        # instead of the cold-model default
        for bs in ladder:
            wire = _inert_wire(int(bs), 4)
            try:
                t1 = time.perf_counter()
                if hasattr(clf, "prepare_packed"):
                    clf.classify_prepared(
                        clf.prepare_packed(wire, True), apply_stats=False
                    ).result()
                else:
                    clf.classify_async_packed(
                        wire, True, apply_stats=False
                    ).result()
                service.observe(int(bs), time.perf_counter() - t1)
            except Exception:
                pass
    log.info("ladder prewarm: %d dispatches over %d shapes in %.1fs",
             n_done, len(ladder), time.perf_counter() - t0)
    return n_done


def data_parallel_width(clf) -> int:
    """How many ways a classifier spreads one wire batch along the
    "data" axis (``Classifier.data_shards``: MeshTpuClassifier's shard
    count, 1 single-chip/CPU) — the multiplier on the scheduler's
    per-chip admission budget."""
    return max(1, int(getattr(clf, "data_shards", 1) or 1))


# -- the continuous serving loop ---------------------------------------------


class ServeResult(NamedTuple):
    results: np.ndarray         # (n,) uint32 packed verdicts, input order
    xdp: np.ndarray             # (n,) int32 XDP actions
    latency_s: np.ndarray       # (n,) float64 completion - scheduled arrival
    batch_sizes: np.ndarray     # admitted (unpadded) size per dispatch
    stats: SchedulerStats


class ContinuousScheduler:
    """Open-loop serving harness: drive a packet stream with scheduled
    arrival times through a classifier under a coalescing policy, with
    double-buffered staging (prepare_packed ping-pong) and optional mesh
    spillover.  The daemon's ingest tick embeds the same policy; this
    class is the standalone loop the SLO bench and the tests drive."""

    def __init__(
        self,
        clf,
        policy,
        chip_budget: Optional[int] = None,
        spill_clf=None,
        pipeline_depth: int = 4,
        stage_depth: int = 2,
        ring=None,
        stats: Optional[SchedulerStats] = None,
        clock: Callable[[], float] = time.monotonic,
        txn_batcher=None,
        txn_flush: Optional[Callable] = None,
        tracer=None,
        stripe=None,
    ) -> None:
        self.clf = clf
        #: per-device admission striping (ISSUE-16,
        #: backend.mesh.DeviceStripe): when given, every PRIMARY job
        #: (not spill, not oversized-split) round-robins across the
        #: stripe's pinned classifiers — k chips run k independent
        #: overlapped pipelines; spill and tenant jobs keep their
        #: explicit targets
        self.stripe = stripe
        self.policy = policy
        self.spill_clf = spill_clf
        #: update-storm interleaving (infw.txn): when a TxnBatcher and a
        #: flush callable ``txn_flush(items, reason)`` (items =
        #: TxnBatcher.drain()'s (op, enqueue_ts) pairs) are given, the
        #: serve loop checks the batcher's bounded-staleness policy each
        #: iteration and runs a tripped flush on its own thread while it
        #: OCCUPIES A PIPELINE SLOT — admissions keep flowing (classify
        #: dispatches continue against the old generation until the
        #: swap), but the pipeline never overcommits device work while
        #: a table patch is in flight.
        self.txn_batcher = txn_batcher
        self.txn_flush = txn_flush
        #: per-chip admission budget: a coalesced batch beyond it spills
        #: to the mesh target (sharded over "data") or, with no spill
        #: target, splits into per-budget jobs on the primary
        self.chip_budget = int(
            chip_budget if chip_budget is not None else policy.max_admit
        )
        self.pipeline_depth = max(1, int(pipeline_depth))
        #: how many admissions may be host-packed with their H2D copy
        #: started ahead of the launch window (the PR-2 double-buffer
        #: bound, 2 = classic ping-pong)
        self.stage_depth = max(1, int(stage_depth))
        self.ring = ring
        self.stats = stats if stats is not None else SchedulerStats()
        self._clock = clock
        #: serving-path span tracer (obs.telemetry.SpanTracer): when
        #: given, every admitted job charges pack / dispatch /
        #: materialize / drain spans to the shared histograms (the
        #: daemon's ingest tick charges ingest/pack the same way)
        self.tracer = tracer

    # -- dispatch plumbing ---------------------------------------------------

    def _dispatch(self, clf, batch: PacketBatch, idx: np.ndarray,
                  bucket: int, tenant_of: Optional[np.ndarray] = None):
        """One admitted job through the production path: fused subset
        pack + ladder padding + (prepare_packed | classify_async_packed |
        classify_async), matching the daemon's prepare/launch halves.

        ``tenant_of`` (tenant-tagged admissions, the multi-tenant arena
        path): per-packet tenant ids aligned with ``batch`` — when the
        classifier serves the arena contract, ONE admitted job carries
        mixed-tenant traffic and the tenant column steers each packet's
        slab in-kernel; padding lanes get tenant -1 (UNDEF)."""
        if tenant_of is not None and hasattr(
            clf, "classify_async_packed_tenant"
        ):
            sub = np.ascontiguousarray(idx, np.int64)
            wire, _v4 = batch.pack_wire_subset(sub)
            tags = np.ascontiguousarray(tenant_of[sub], np.int32)
            pad = bucket - wire.shape[0]
            if pad > 0:
                padrows = np.zeros((pad, wire.shape[1]), np.uint32)
                padrows[:, 0] = KIND_OTHER
                wire = np.concatenate([wire, padrows])
                tags = np.concatenate([tags, np.full(pad, -1, np.int32)])
            return lambda: clf.classify_async_packed_tenant(
                wire, tags, apply_stats=False
            )
        supports = getattr(clf, "supports_packed", None)
        if supports is not None and supports():
            wire, v4_only = batch.pack_wire_subset(
                np.ascontiguousarray(idx, np.int64)
            )
            pad = bucket - wire.shape[0]
            if pad > 0:
                padrows = np.zeros((pad, wire.shape[1]), np.uint32)
                padrows[:, 0] = KIND_OTHER
                wire = np.concatenate([wire, padrows])
            if hasattr(clf, "prepare_packed"):
                plan = clf.prepare_packed(wire, v4_only)
                return lambda: clf.classify_prepared(plan, apply_stats=False)
            return lambda: clf.classify_async_packed(
                wire, v4_only, apply_stats=False
            )
        merged = batch.take(
            np.ascontiguousarray(idx, np.int64)
        ).pad_to(bucket)
        return lambda: clf.classify_async(merged, apply_stats=False)

    def _emit_miss(self, n_miss: int, n: int, worst_s: float,
                   deadline_s: float) -> None:
        self.stats.note_complete(0, n_miss)
        if self.ring is not None and n_miss:
            from .obs.events import DeadlineMissRecord

            self.ring.push(DeadlineMissRecord(
                n_miss=int(n_miss), batch=int(n),
                worst_us=float(worst_s * 1e6),
                deadline_us=float(deadline_s * 1e6),
            ))

    # -- the loop ------------------------------------------------------------

    def serve(self, batch: PacketBatch, arrival_offsets_s: np.ndarray,
              anchor: Optional[float] = None,
              tenant_of: Optional[np.ndarray] = None) -> ServeResult:
        """Classify ``batch`` as an open-loop arrival stream: packet i
        becomes eligible at ``anchor + arrival_offsets_s[i]`` (anchor
        defaults to now).  Blocks until every packet's verdict is
        host-resident; per-packet latency is completion minus SCHEDULED
        arrival (coordinated-omission-safe).  ``tenant_of`` tags each
        packet with its tenant id for arena-backed classifiers — one
        admission then dispatches ONE mixed-tenant batch instead of a
        per-tenant job fan-out."""
        n = len(batch)
        if tenant_of is not None:
            tenant_of = np.ascontiguousarray(tenant_of, np.int32)
            if tenant_of.shape != (n,):
                raise ValueError(
                    f"tenant_of shape {tenant_of.shape} != ({n},)"
                )
            # refusing beats silently classifying every tenant against
            # one table: a non-arena backend would drop the tags on the
            # floor and break cross-tenant isolation with no signal
            for target, label in ((self.clf, "classifier"),
                                  (self.spill_clf, "spill classifier")):
                if target is not None and not hasattr(
                    target, "classify_async_packed_tenant"
                ):
                    raise ValueError(
                        f"tenant_of given but the {label} does not serve "
                        "the tenant contract (classify_async_packed_tenant)"
                    )
        offs = np.asarray(arrival_offsets_s, np.float64)
        if offs.shape != (n,):
            raise ValueError(
                f"arrival offsets shape {offs.shape} != ({n},)"
            )
        order = np.argsort(offs, kind="stable")
        t0 = self._clock() if anchor is None else float(anchor)
        arrive = t0 + offs
        results = np.zeros(n, np.uint32)
        xdp = np.full(n, 2, np.int32)
        done_ts = np.zeros(n, np.float64)
        batch_sizes: List[int] = []

        queue: deque = deque()   # (packet position, arrival ts)
        staged: deque = deque()  # admitted jobs not yet launched
        pos = 0
        deadline_s = getattr(self.policy, "deadline_s", float("inf"))
        spill_width = (
            data_parallel_width(self.spill_clf)
            if self.spill_clf is not None else 1
        )
        # one coalescing DECISION may exceed the per-chip budget either
        # way: with a spill target it ships as one mesh dispatch sharded
        # over "data" (so the cap scales by the width); without one the
        # admission is split into per-budget jobs below — the policy's
        # own max_admit is the only decision-level cap
        max_admit_now = (
            self.chip_budget * max(spill_width, 1)
            if self.spill_clf is not None else self.policy.max_admit
        )

        # Completion runs on its own thread POOL (one drainer per
        # pipeline slot): a launched job's result is materialized (and
        # its packets' completion stamped) the moment the device
        # finishes — a single FIFO drainer would stamp a fast job queued
        # behind a slow one (e.g. a primary-chip job behind a spilled
        # mesh job) at the slow job's finish time, manufacturing false
        # deadline misses and poisoning the service model; lazy draining
        # in the admission loop would be worse still.
        cv = threading.Condition()
        pending_q: deque = deque()
        outstanding = [0]
        stop_flag = [False]
        errs: List[BaseException] = []

        def drain_loop() -> None:
            while True:
                with cv:
                    while not pending_q and not stop_flag[0]:
                        cv.wait()
                    if not pending_q:
                        return
                    job, pending = pending_q.popleft()
                try:
                    tr = job.get("trace")
                    t_mat0 = time.perf_counter()
                    out = pending.result()
                    if tr is not None:
                        tr.add("materialize", time.perf_counter() - t_mat0)
                    t_done = self._clock()
                    idx = job["idx"]
                    k = len(idx)
                    results[idx] = np.asarray(out.results)[:k]
                    xdp[idx] = np.asarray(out.xdp)[:k]
                    done_ts[idx] = t_done
                    self.policy.service.observe(
                        job["bucket"], t_done - job["t_launch"]
                    )
                    lat = t_done - arrive[idx]
                    n_miss = int((lat > deadline_s).sum())
                    self.stats.note_complete(k, 0)
                    self._emit_miss(n_miss, k, float(lat.max()), deadline_s)
                    if tr is not None:
                        tr.mark("drain")
                        self.tracer.finish(tr)
                except BaseException as e:  # surfaced by serve() at exit
                    errs.append(e)
                with cv:
                    outstanding[0] -= 1
                    cv.notify_all()

        kinds_all = np.asarray(batch.kind)

        def admit_job(count: int) -> None:
            take = [queue.popleft() for _ in range(count)]
            idx = np.asarray([t[0] for t in take], np.int64)
            # family-homogeneous jobs, like the daemon's ingest tick: the
            # v4 share ships compact and walks the truncated trie instead
            # of riding the v6 sub-batch's full-depth walk
            k = kinds_all[idx]
            for g in (idx[k != KIND_IPV6], idx[k == KIND_IPV6]):
                if len(g) == 0:
                    continue
                if len(g) > self.chip_budget:
                    if self.spill_clf is not None:
                        # overflow path: one mesh dispatch, sharded over
                        # the "data" axis
                        _push_job(self.spill_clf, g, True)
                        continue
                    # single-chip pool: split the oversized admission
                    # into per-budget jobs (degrade, never refuse)
                    for s in range(0, len(g), self.chip_budget):
                        _push_job(self.clf, g[s: s + self.chip_budget],
                                  False)
                    continue
                _push_job(self.clf, g, False)

        def _push_job(target, idx, spilled: bool) -> None:
            if target is self.clf and self.stripe is not None:
                # device round-robin: each admission lands whole on one
                # chip of the stripe (its own flow state and donated
                # epoch chain) — striping scales admissions/s, the mesh
                # spill target scales one admission
                target = self.stripe.next_classifier()
            cap = self.policy.max_admit * max(spill_width, 1)
            bucket = ladder_bucket(len(idx), max(cap, len(idx)))
            self.stats.note_admit(len(idx), bucket, spilled=spilled)
            batch_sizes.append(len(idx))
            trace = (
                self.tracer.begin(len(idx))
                if self.tracer is not None else None
            )
            thunk = self._dispatch(target, batch, idx, bucket,
                                   tenant_of=tenant_of)
            if trace is not None:
                # subset pack + ladder pad + prepare_packed (H2D start)
                trace.mark("pack")
            # the bucket travels with the job: the drain thread must
            # feed the service observation to the bucket the job was
            # DISPATCHED at, not a recomputation that forgets spill
            # scaling
            staged.append((
                {"idx": idx, "bucket": bucket, "trace": trace}, thunk,
            ))

        def launch_ready() -> None:
            while staged:
                with cv:
                    if outstanding[0] >= self.pipeline_depth:
                        return
                job, thunk = staged.popleft()
                job["t_launch"] = self._clock()
                t_disp0 = time.perf_counter()
                pending = thunk()
                tr = job.get("trace")
                if tr is not None:
                    tr.add("dispatch", time.perf_counter() - t_disp0)
                with cv:
                    pending_q.append((job, pending))
                    outstanding[0] += 1
                    cv.notify_all()

        flush_busy = [False]

        def maybe_flush_txn(now: float) -> None:
            """Bounded-staleness edit flush, interleaved with serving:
            when the batcher's deadline/batch threshold trips, the flush
            runs on its own thread while holding ONE pipeline slot — the
            admission loop keeps coalescing and dispatching (in-flight
            classifies finish on the old generation; the swap is a
            reference assignment), but device work never overcommits
            while the patch is in flight."""
            if (
                self.txn_batcher is None or self.txn_flush is None
                or flush_busy[0]
            ):
                return
            reason = self.txn_batcher.should_flush(now)
            if reason is None:
                return
            items = self.txn_batcher.drain()
            if not items:
                return
            flush_busy[0] = True
            with cv:
                outstanding[0] += 1  # the flush occupies a pipeline slot

            def run_flush() -> None:
                try:
                    self.txn_flush(items, reason)
                except BaseException as e:  # surfaced by serve() at exit
                    errs.append(e)
                finally:
                    with cv:
                        outstanding[0] -= 1
                        cv.notify_all()
                    flush_busy[0] = False

            spawn(run_flush, name="infw-txn-flush")

        drainers = [
            spawn(drain_loop, name=f"infw-sched-drain-{i}", start=False)
            for i in range(self.pipeline_depth)
        ]
        for t in drainers:
            t.start()
        try:
            while True:
                now = self._clock()
                maybe_flush_txn(now)
                while pos < n and arrive[order[pos]] <= now:
                    p = int(order[pos])
                    queue.append((p, arrive[p]))
                    pos += 1
                with cv:
                    infl = outstanding[0]
                self.stats.set_queue_depth(len(queue))
                eof = pos >= n
                if eof and not queue and not staged and infl == 0:
                    break
                dec = self.policy.admit(
                    now, len(queue), queue[0][1] if queue else now,
                    infl + len(staged), eof=eof,
                )
                if dec.n > 0 and len(staged) < self.stage_depth:
                    # ping-pong staging bound: at most stage_depth
                    # admissions have their host pack + H2D copy started
                    # ahead of the launch window — overload coalesces in
                    # the arrival queue, not in prepared device buffers
                    admit_job(min(dec.n, len(queue), max_admit_now))
                    launch_ready()
                    continue
                launch_ready()
                # wait for the next event: an arrival, the policy's
                # re-decision point, the edit batcher's staleness
                # deadline, or a completion (cv notify)
                now2 = self._clock()
                next_arrival = (
                    arrive[order[pos]] - now2 if pos < n else float("inf")
                )
                wait = min(
                    next_arrival,
                    dec.wait_s if dec.wait_s is not None else float("inf"),
                )
                if (
                    self.txn_batcher is not None and not flush_busy[0]
                    and len(self.txn_batcher)
                ):
                    # the staleness budget bounds the sleep too — a 2 ms
                    # deadline must not ride the 50 ms poll cap through
                    # an arrival gap
                    wait = min(wait, max(
                        self.txn_batcher.staleness_s
                        - self.txn_batcher.oldest_age(now2), 0.0,
                    ))
                with cv:
                    cv.wait(min(wait, 0.05) if wait > 0 else 0.001)
        finally:
            with cv:
                stop_flag[0] = True
                cv.notify_all()
            for t in drainers:
                t.join()
        if errs:
            raise errs[0]
        self.stats.set_queue_depth(0)
        return ServeResult(
            results=results, xdp=xdp, latency_s=done_ts - arrive,
            batch_sizes=np.asarray(batch_sizes, np.int64),
            stats=self.stats,
        )
