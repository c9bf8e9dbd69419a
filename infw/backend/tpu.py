"""TPU classifier backend.

The device-resident dataplane: compiled rule tensors live in HBM/VMEM, the
classify step is the fused Pallas kernel (tables up to the dense limit) or
the XLA trie path (100K+ CIDRs).  Design points:

- **double-buffered table swap** (SURVEY.md §2: the TPU analogue of the
  reference's mutex-serialized map rewrite,
  /root/reference/pkg/ebpfsyncer/ebpfsyncer.go:56-63): the next rule
  tensors are built and device_put while classification continues on the
  current set; the swap is a single reference assignment under a lock, so
  in-flight batches finish on the old tables and new batches see the new
  ones — no torn reads, no pause.
- **async pipelining**: classify_async() dispatches the H2D transfer and
  kernel and returns a PendingClassify holding *unmaterialized* device
  arrays; nothing blocks until .result() is called, so a caller keeping
  several batches in flight overlaps H2D / kernel / D2H of consecutive
  batches (the daemon's streaming ingest does exactly this).  classify()
  is the synchronous convenience: dispatch + immediate materialize.
- statistics accumulate host-side in int64 from the device's per-batch
  (1024, 6) int32 sums, applied exactly once when a batch materializes.
"""
from __future__ import annotations

import os
import threading
from typing import Optional

import jax
import numpy as np

from .._threads import spawn
from ..compiler import CompiledTables
from ..constants import ALLOW, DENY, KIND_IPV6
from ..kernels import jaxpath, pallas_dense, pallas_walk, wire_decode
from ..obs import program_span
from ..packets import PacketBatch, encode_delta_wire, narrow_wire, wire8
from .base import ClassifyOutput, PendingClassify, StatsAccumulator

#: H2D wire codec choices (the daemon's --wire-codec knob): "auto" picks
#: per chunk by measured compressed size (delta when it beats the wire8
#: 8 B/packet floor), "wire8"/"delta" force a format (with the usual
#: eligibility fallbacks — an ineligible chunk degrades down the
#: delta -> wire8 -> narrow -> full chain, never refuses)
WIRE_CODECS = ("auto", "wire8", "delta")


class TpuClassifier:
    """Single-chip device classifier."""

    #: the syncer may route structurally-new keys to a dense side-table
    #: (load_tables(..., overlay=...), jaxpath.classify_with_overlay)
    supports_overlay = True
    #: the daemon's SpanTracer (set by the syncer under --trace): a
    #: load's device upload and walk build as infw.load.* program spans
    tracer = None

    def __init__(
        self,
        device=None,
        dense_limit: int = pallas_dense.MAX_DENSE_TARGETS,
        force_path: Optional[str] = None,  # "dense"|"trie"|"ctrie"|None (auto)
        interpret: Optional[bool] = None,
        fused_deep: Optional[bool] = None,
        wire_codec: Optional[str] = None,
        decode_pallas: Optional[bool] = None,
        check_invariants: Optional[bool] = None,
        compressed: Optional[bool] = None,
        flow_table=None,
        flow_track_model: bool = False,
        resident: Optional[bool] = None,
        telemetry=None,
        telemetry_track_model: bool = False,
        mlscore=None,
        mlscore_model=None,
        mlscore_mode: Optional[str] = None,
        mlscore_track_model: bool = False,
        payload=None,
        payload_mode: Optional[str] = None,
        payload_plen: Optional[int] = None,
        payload_track: bool = False,
    ) -> None:
        self._device = device if device is not None else jax.devices()[0]
        self._dense_limit = dense_limit
        self._force_path = force_path
        self._interpret = (
            interpret if interpret is not None else pallas_dense.default_interpret()
        )
        # Fused deep-walk dispatch (kernels.pallas_walk): the
        # depth-steered FULL-DEPTH v6 class — the throughput floor every
        # adversarial mix converges to — runs as one Pallas grid pass
        # with the deep tail VMEM-resident instead of one XLA HBM gather
        # per level.  Defaults on for real TPU; interpret mode keeps the
        # (faster-on-CPU) XLA walk unless explicitly enabled — tests opt
        # in with fused_deep=True.  Precedence: explicit constructor arg
        # (e.g. the daemon's --no-fused-deep) > INFW_FUSED_DEEP env >
        # backend default.
        if fused_deep is None:
            env = os.environ.get("INFW_FUSED_DEEP", "")
            if env:
                fused_deep = env not in ("0", "false", "no")
        self._fused_deep = (
            fused_deep if fused_deep is not None else not self._interpret
        )
        # H2D wire codec (--wire-codec / INFW_WIRE_CODEC, CLI beats env,
        # same precedence shape as fused_deep): constructor arg > env >
        # "auto" (per-chunk choice by measured compressed size).
        if wire_codec is None:
            wire_codec = os.environ.get("INFW_WIRE_CODEC") or "auto"
        if wire_codec not in WIRE_CODECS:
            raise ValueError(
                f"unknown wire codec {wire_codec!r} (expected one of "
                f"{WIRE_CODECS})"
            )
        self._wire_codec = wire_codec
        # Pallas fixed-stride decode variant (kernels.wire_decode): off by
        # default everywhere until a recorded TPU run proves it over the
        # XLA decode; tests opt in explicitly.
        if decode_pallas is None:
            env = os.environ.get("INFW_DECODE_PALLAS", "")
            decode_pallas = env not in ("", "0", "false", "no")
        self._decode_pallas = bool(decode_pallas)
        # Opt-in deep invariant contracts at every patch boundary
        # (infw.analysis.statecheck.check_device_tables): shapes, dtypes,
        # pad-fill values, mask-word reconstruction, trie child/target
        # bounds, joined-plane consistency.  The cheap shape-only half
        # (jaxpath.assert_patched_tables) is ALWAYS on; this adds the
        # data-level pass — device reads, so opt in via the constructor
        # or INFW_CHECK_INVARIANTS=1.
        if check_invariants is None:
            env = os.environ.get("INFW_CHECK_INVARIANTS", "")
            check_invariants = env not in ("", "0", "false", "no")
        self._check_invariants = bool(check_invariants)
        # Path/level-compressed poptrie layout (jaxpath.build_cpoptrie):
        # trie-sized tables serve from the merged skip-node array — the
        # 10M-tier working-set layout — instead of the per-level walk.
        # Precedence mirrors fused_deep: constructor arg (the daemon's
        # --compressed) > INFW_COMPRESSED env > off.  force_path="ctrie"
        # is the explicit per-instance form.  Ineligible tables (wide
        # int32 ruleIds) fall back to the level walk at load time, never
        # refuse.
        if compressed is None:
            env = os.environ.get("INFW_COMPRESSED", "")
            if env:
                compressed = env not in ("0", "false", "no")
        self._compressed = bool(compressed) or force_path == "ctrie"
        self._lock = threading.Lock()
        # Stateful flow tier (infw.flow, the --flow-table knob): a
        # device-resident exact-match verdict cache probed before the
        # LPM + rule scan; hits serve the cached verdict in the probe
        # dispatch and only the (compacted) misses fall through to the
        # stateless classify below.  Precedence mirrors the other knobs:
        # constructor arg (FlowConfig or an entry count) > the
        # INFW_FLOW_TABLE env (entry count) > off.
        if flow_table is None:
            env = os.environ.get("INFW_FLOW_TABLE", "")
            if env and env not in ("0", "false", "no"):
                flow_table = int(env)
        # Zero-copy resident serving loop (--resident / INFW_RESIDENT,
        # ISSUE-12): one fused device program per admission (decode +
        # flow probe + stateless classify + merge + stats + miss
        # insert) over donated/aliased buffers, replacing the
        # probe-then-classify multi-dispatch plan.  The fused step IS
        # the flow tier's serving form, so resident implies a flow
        # table (a default one when none was configured).  Precedence
        # mirrors the other knobs: constructor arg > INFW_RESIDENT env
        # > off.
        if resident is None:
            env = os.environ.get("INFW_RESIDENT", "")
            if env:
                resident = env not in ("0", "false", "no")
        self._resident = None
        if resident:
            from ..resident import ResidentPool

            if flow_table is None or flow_table is False:
                from ..flow import FlowConfig

                flow_table = FlowConfig.make()
            self._resident = ResidentPool(device=self._device)
        self._flow = None
        if flow_table is not None and flow_table is not False:
            from ..flow import FlowConfig, FlowTier

            if not isinstance(flow_table, FlowConfig):
                flow_table = FlowConfig.make(entries=int(flow_table))
            self._flow = self._make_flow_tier(
                flow_table, track_model=flow_track_model
            )
        # Device-resident telemetry plane (ISSUE-13, --telemetry /
        # INFW_TELEMETRY): count-min + top-K heavy-hitter tensors
        # updated inside the resident fused step (donated, in-program)
        # or as one follow-on launch per admission on the multi-dispatch
        # wire path — observability as a batched tensor workload, the
        # host reads one snapshot per N admissions (the decimated
        # drain), never a per-packet event.  Precedence mirrors the
        # other knobs: constructor arg (SketchSpec or count-min width)
        # > INFW_TELEMETRY env (width) > off.
        if telemetry is None:
            env = os.environ.get("INFW_TELEMETRY", "")
            if env and env not in ("0", "false", "no"):
                telemetry = (
                    True if env in ("1", "true", "yes") else int(env)
                )
        self._telemetry = None
        if telemetry is not None and telemetry is not False:
            from ..kernels.sketch import SketchSpec
            from ..obs.telemetry import TelemetryTier

            if not isinstance(telemetry, SketchSpec):
                telemetry = (
                    SketchSpec.make() if telemetry is True
                    else SketchSpec.make(width=int(telemetry))
                )
            self._telemetry = TelemetryTier(
                telemetry, device=self._device,
                track_model=telemetry_track_model,
            )
        # MXU anomaly-scoring tier (ISSUE-14, --mlscore / INFW_MLSCORE):
        # quantized per-flow inference fused into the resident step or
        # launched once per admission on the multi-dispatch path; the
        # AnomalyTier applies per-tenant shadow/enforce policy, and a
        # model hot swap bumps the flow generation like a rule patch.
        # Precedence mirrors the other knobs: constructor arg
        # (ScoreSpec or truthy) > INFW_MLSCORE env > off; the mode knob
        # reads INFW_MLSCORE_MODE when unset (default shadow).
        if mlscore is None:
            env = os.environ.get("INFW_MLSCORE", "")
            if env and env not in ("0", "false", "no"):
                mlscore = True
        if mlscore_mode is None:
            mlscore_mode = os.environ.get("INFW_MLSCORE_MODE") or "shadow"
        self._mlscore = None
        if mlscore is not None and mlscore is not False:
            from ..kernels.mxu_score import ScoreSpec
            from ..mlscore import AnomalyTier

            if not isinstance(mlscore, ScoreSpec):
                mlscore = (
                    ScoreSpec.make() if mlscore is True
                    else ScoreSpec.make(slots=int(mlscore))
                )
            self._mlscore = AnomalyTier(
                mlscore, model=mlscore_model, device=self._device,
                mode=mlscore_mode, track_model=mlscore_track_model,
            )
            # a model swap behaves like a rule patch: resident flow
            # entries caching pre-swap (possibly enforced) verdicts go
            # stale through the same generation stamps
            self._mlscore.on_swap = self._on_score_model_swap
        # Payload-matching tier (ISSUE-19, --payload-patterns /
        # INFW_PAYLOAD): batched Aho-Corasick multi-pattern matching
        # over the optional ring-sliced payload-prefix column, fused
        # into the resident admission program as the fourth
        # verdict-merge tier or launched once per admission on the
        # multi-dispatch path.  The automaton is STATELESS on device
        # (value operands only, nothing donated), so engaging it never
        # disturbs the resident donation aliasing.  Precedence mirrors
        # the other knobs: constructor arg (PayloadTier / AcModel /
        # pattern list / artifact path / pattern count) > INFW_PAYLOAD
        # env (artifact path or seeded-set count) > off; the mode knob
        # reads INFW_PAYLOAD_MODE when unset (default shadow).
        if payload is None:
            env = os.environ.get("INFW_PAYLOAD", "")
            if env and env not in ("0", "false", "no"):
                payload = env
        if payload_mode is None:
            payload_mode = os.environ.get("INFW_PAYLOAD_MODE") or "shadow"
        self._payload = None
        if payload is not None and payload is not False:
            from ..kernels.acmatch import AcModel
            from ..payload import (
                PayloadTier, load_patterns, signature_patterns,
            )

            plen = int(payload_plen or 64)
            if isinstance(payload, PayloadTier):
                tier = payload
            elif isinstance(payload, AcModel):
                tier = PayloadTier(
                    payload, mode=payload_mode, device=self._device
                )
            elif isinstance(payload, (list, tuple)):
                tier = PayloadTier(
                    payload, plen=plen, mode=payload_mode,
                    device=self._device,
                )
            elif isinstance(payload, str) and payload not in (
                "1", "true", "yes"
            ) and not payload.isdigit():
                pats, spec, _ver = load_patterns(payload)
                tier = PayloadTier(
                    pats, plen=spec.plen, mode=payload_mode, spec=spec,
                    device=self._device,
                )
            else:
                count = (
                    64 if payload is True
                    or payload in ("1", "true", "yes")
                    else int(payload)
                )
                tier = PayloadTier(
                    signature_patterns(
                        np.random.default_rng(0), count, plen=plen
                    ),
                    plen=plen, mode=payload_mode, device=self._device,
                )
            self._payload = tier
            if payload_track:
                self._payload.set_keep_masks(256)
            # a pattern-set swap behaves like a rule patch: flow
            # entries caching pre-swap (possibly enforced) verdicts go
            # stale through the same generation stamps
            self._payload.on_swap = self._on_pattern_swap
        self._stats = StatsAccumulator()
        # per-format H2D accounting {fmt: [packets, payload bytes]} — the
        # bench reads this to put bytes/packet in the replay record
        self._wire_counts = {}
        self._pallas_counts = {}  # kernel -> packets (pallas_stats)
        self._tables: Optional[CompiledTables] = None
        # (path, dev tables, block_b|None, wide_rids, overlay dev|None,
        #  fused walk dev|None)
        self._active = None
        self._last_load = None  # ("patch"|"full", rows) — introspection/tests
        self._ov_cache = None   # (overlay CompiledTables, device copy)
        # host meta of the resident fused-walk tables: (tidx_sorted,
        # min_depth) — the rules-only-edit staleness check (see
        # load_tables); guarded by _lock alongside _active
        self._walk_meta = None
        # depth-class steering state (trie path): (root_lut np, depth
        # LUT np, class tuple, generation); None off the trie path.
        # The generation token guards callers that grouped against an
        # older table: a stale depth silently degrades to the full
        # walk (always correct) instead of under-walking.
        self._depth_steer = None
        self._depth_gen = 0
        self._closed = False

    # -- rule loading -------------------------------------------------------

    def load_tables(self, tables: CompiledTables, dirty_hint=None,
                    overlay: Optional[CompiledTables] = None) -> None:
        """Swap in a newly compiled ruleset.

        ``dirty_hint`` (IncrementalTables.peek_dirty()) accelerates the
        incremental device patch: with it, the patch scatters exactly the
        hinted rows with NO full-table host diff — a 1-key edit costs a
        couple of small transfers regardless of table size.  The hint is
        also how a FOLDED edit transaction (infw.txn) lands: N coalesced
        edits arrive as one merged dirty-row set, one H2D staging pass
        and one fused scatter launch (jaxpath.txn_scatter, pre-warmed
        across the dirty-row ladder at full-load time), so per-edit
        device cost amortizes toward O(dirty rows).  A transaction that
        exceeds the capped-scatter budget or forces trie renumbering
        past the row buckets escalates to the full rebuild below — the
        OLD generation keeps serving until the swap (the double-buffer
        contract), so classification never stalls on an oversized
        flush.

        ``overlay`` is a SMALL dense side-table of structurally-new keys
        (CIDR adds since the main table's last full build): it uploads in
        kilobytes and the classify combines both tables by longest
        prefix (jaxpath.classify_with_overlay), so a 1-key CIDR add
        never pays the main trie's re-transform.  Callers (the syncer)
        keep identities disjoint between main and overlay."""
        if self._closed:
            raise RuntimeError("classifier is closed")
        path = self._force_path or (
            "dense" if tables.num_entries <= self._dense_limit else "trie"
        )
        if path == "trie" and self._compressed and self._force_path is None:
            # the compressed upgrade applies to the AUTO-selected trie
            # path only: an explicit per-instance force_path="trie" must
            # beat the constructor/env knob (the documented precedence),
            # or every test/statecheck config pinning the per-level walk
            # silently flips under INFW_COMPRESSED=1
            path = "ctrie"
        # Build the next buffer off-lock (host packing + device_put can be
        # slow); swap under the lock.
        wide_rids = False
        if path == "ctrie":
            # Rules-only edit: carry the host caches forward BEFORE the
            # eligibility probes below — joined_by_tidx and
            # check_wire_ruleids memoize on first touch, so a fresh
            # snapshot would repack the full rules tensor right here.
            with self._lock:
                seed_prev = self._tables
            if seed_prev is not None and dirty_hint is not None:
                jaxpath.seed_ctrie_caches_forward(
                    seed_prev, tables, dirty_hint
                )
            # Compressed-layout eligibility: the per-tidx joined rows are
            # u16-packed and the wire result carries the ruleId — wide
            # tables serve from the level walk's u32 path instead (the
            # same fallback contract as the fused deep walk).
            try:
                jaxpath.check_wire_ruleids(tables)
            except ValueError:
                path = "trie"
            else:
                if jaxpath.joined_by_tidx(tables) is None:
                    path = "trie"
        if path == "dense":
            try:
                pt = pallas_dense.build_pallas_tables(tables)
            except ValueError as e:
                if "ruleId" not in str(e):
                    raise
                # Adversarial direct content whose ruleIds exceed the
                # Pallas packing: serve it from the trie path instead of
                # refusing the table at load time.
                path = "trie"
        with program_span(self.tracer, "infw.load.upload"):
            if path == "dense":
                dev = jax.tree.map(lambda a: jax.device_put(a, self._device), pt)
                block_b = pallas_dense.choose_block_b(pt.mdt.shape[1])
                self._last_load = ("full", tables.num_entries)
            elif path == "ctrie":
                # Compressed-poptrie resident form: dev is (CTrieTables,
                # d_max) — d_max is the static walk-unroll bound and travels
                # beside the pytree, not inside it.  Same incremental
                # contract as the trie path: rules-only edits scatter the
                # per-tidx joined rows, structural edits diff the merged
                # node/target arrays row-wise; a layout shift past the row
                # buckets (or a d_max change) re-uploads.
                dev = None
                block_b = None
                with self._lock:
                    prev_tables, prev_active = self._tables, self._active
                if (
                    prev_tables is not None
                    and prev_active is not None
                    and prev_active[0] == "ctrie"
                ):
                    patched = jaxpath.patch_ctrie(
                        prev_active[1][0], prev_tables, tables, self._device,
                        hint=dirty_hint,
                    )
                    if patched is None and jaxpath.hint_trie_unchanged(
                        dirty_hint
                    ):
                        # only a rules-only hint takes a different path on
                        # retry (structural row-diff instead of the joined
                        # fast path); a structural hint already ran exactly
                        # the diff a no-hint attempt would repeat
                        patched = jaxpath.patch_ctrie(
                            prev_active[1][0], prev_tables, tables, self._device
                        )
                    if patched is not None:
                        dev = (patched[0], prev_active[1][1])
                        self._last_load = ("patch", patched[1])
                if dev is None:
                    dev = jaxpath.device_ctrie(tables, self._device, pad=True)
                    self._last_load = ("full", tables.num_entries)
                    # same first-edit contract as the level walk: the patch
                    # scatters compile at load time, not on the first edit
                    jaxpath.warm_ctrie_patch_scatters(dev[0], self._device)
            else:
                try:
                    jaxpath.check_wire_ruleids(tables)
                except ValueError:
                    # ruleIds > 255: the 2B wire result can't carry them —
                    # fall back to the u32 (non-wire) classify path.
                    wide_rids = True
                dev = None
                with self._lock:
                    prev_tables, prev_active = self._tables, self._active
                if (
                    prev_tables is not None
                    and prev_active is not None
                    and prev_active[0] == "trie"
                ):
                    # Incremental device patch (the Map.Update analogue):
                    # ship only the rows that changed since the resident
                    # generation; falls back to a full upload on structural
                    # change (trie growth, compaction, path flip).
                    patched = jaxpath.patch_device_tables(
                        prev_active[1], prev_tables, tables, self._device,
                        hint=dirty_hint,
                    )
                    if patched is None and dirty_hint is not None:
                        # hint didn't apply (bucket growth / oversized delta):
                        # try the diff-based patch before a full re-upload
                        patched = jaxpath.patch_device_tables(
                            prev_active[1], prev_tables, tables, self._device
                        )
                    if patched is not None:
                        dev, n_rows = patched
                        self._last_load = ("patch", n_rows)
                if dev is None:
                    # pad=True buckets device row counts so later small edits
                    # keep array shapes and can take the patch path
                    dev = jaxpath.device_tables(tables, self._device, pad=True)
                    self._last_load = ("full", tables.num_entries)
                    # Pre-compile the patch scatters against the fresh layout:
                    # the first post-load rule edit then ships in milliseconds
                    # instead of paying the scatter-jit compile (the pinned-map
                    # re-adoption contract is rules keep enforcing AND stay
                    # editable immediately, loader.go:381-407).
                    jaxpath.warm_patch_scatters(dev, self._device)
                block_b = None
        steer_parts = None
        walk_dev = None
        walk_meta = None
        defer_walk = False
        with program_span(self.tracer, "infw.load.walk"):
            if path in ("trie", "ctrie"):
                # per-root-slot deep-level requirement (conservative across
                # rules-only patches via the cache carry-forward; recomputed
                # from the snapshot's slot arrays on structural loads);
                # thresholds are TUNED to this table's depth histogram
                # (jaxpath.tune_depth_classes) rather than the static set.
                # The LUT is in LEVEL terms — conservative for the
                # compressed walk, whose skip nodes only shrink step counts.
                lut = jaxpath.build_depth_lut(tables)
                classes = jaxpath.tune_depth_classes(tables)
                steer_parts = (
                    np.asarray(tables.root_lut, np.int64),
                    lut,
                    classes,
                )
                if self._fused_deep and not wide_rids:
                    structural_patch = dirty_hint is not None and any(
                        len(h) for h in dirty_hint.get("levels", ())
                    )
                    if structural_patch:
                        # A structural incremental edit (CIDR delete, overlay
                        # merge) must stay at diff-scatter-patch latency: the
                        # full walk rebuild (depth LUT + extraction + byte
                        # packing + upload) runs in the BACKGROUND and
                        # installs when ready; until then the full-depth
                        # class takes the XLA walk — the fallback contract,
                        # never a wrong verdict.
                        defer_walk = True
                    else:
                        walk_dev, walk_meta = self._build_walk(
                            tables, classes, dirty_hint, path == "ctrie"
                        )
                        if walk_dev is not None:
                            # pre-compile the walk's joined-plane patch
                            # scatters (one per array shape, lru-deduped) so
                            # the first fused-path rules edit is compile-free
                            if path == "ctrie":
                                pallas_walk.warm_cwalk_patch_scatters(
                                    walk_dev[0], self._device
                                )
                            else:
                                pallas_walk.warm_walk_patch_scatters(
                                    walk_dev, self._device
                                )
        ov_dev = None
        if overlay is not None and overlay.num_entries > 0:
            if path not in ("trie", "ctrie") or wide_rids:
                # refusing beats silently dropping live rules: the caller
                # (syncer) must merge the overlay into the main table when
                # the classifier cannot honor it on this path
                raise ValueError(
                    f"overlay not supported on path={path} "
                    f"(wide_rids={wide_rids}); merge it into the main table"
                )
            with self._lock:
                cached = self._ov_cache
            if cached is not None and cached[0] is overlay:
                ov_dev = cached[1]  # unchanged overlay: keep device copy
            else:
                # bucket-padded like the main table so overlay growth
                # re-specializes jit only per pow2 bucket
                ov_dev = jaxpath.device_tables(
                    overlay, self._device, pad=True
                )
                with self._lock:
                    self._ov_cache = (overlay, ov_dev)
        if self._check_invariants:
            # deep contract pass BEFORE install: a violating generation
            # never serves (the patch boundary is the mutation site)
            self._run_invariant_check(dev, ov_dev)
        with self._lock:
            self._tables = tables
            self._active = (path, dev, block_b, wide_rids, ov_dev, walk_dev)
            self._walk_meta = walk_meta
            # the generation token is assigned INSIDE the install lock:
            # two concurrent loads must never install different tables
            # under one token, or a stale grouping would pass the
            # classify-time staleness check and under-walk
            self._depth_gen += 1
            self._depth_steer = (
                steer_parts + (self._depth_gen,)
                if steer_parts is not None else None
            )
        if self._flow is not None:
            # THE invalidation chokepoint: every table mutation — the
            # incremental patch, a folded txn flush, a full rebuild, an
            # overlay change — flows through load_tables, so one
            # generation bump here guarantees no resident flow entry
            # can serve a verdict computed against superseded tables.
            self._flow.bump_generation(0)
        if defer_walk:
            self._spawn_walk_rebuild(tables, steer_parts[2], path == "ctrie")

    def _make_flow_tier(self, cfg, track_model: bool = False):
        """Flow-tier factory (the mesh subclass overrides to place the
        flow columns by the declared partition rules)."""
        from ..flow import FlowTier

        return FlowTier(cfg, device=self._device, track_model=track_model)

    @property
    def flow(self):
        """The FlowTier when the stateful flow tier is enabled."""
        return self._flow

    def flow_counters(self):
        """flow_* counters + occupancy gauge for /metrics (empty when
        the tier is off)."""
        return {} if self._flow is None else self._flow.counter_values()

    def flow_age_tick(self, horizon=None) -> int:
        """Run one epoch-based age sweep (the daemon's idle-loop
        maintenance); returns entries reclaimed."""
        return 0 if self._flow is None else self._flow.age(horizon)

    def warm_flow_ladder(self, ladder) -> int:
        """Pre-compile the probe/insert executables across the batch
        ladder (called by scheduler.prewarm_ladder), so the warm flow
        lifecycle performs zero jit compiles on the serving path."""
        return 0 if self._flow is None else self._flow.warm(ladder)

    def _run_invariant_check(self, dev, ov_dev) -> None:
        """Opt-in deep invariant pass (INFW_CHECK_INVARIANTS=1 /
        check_invariants=True) over the about-to-install device tables;
        raises statecheck.InvariantViolation so the bad generation never
        installs.  Only DeviceTables layouts are checkable (the dense
        path's Pallas tables and the mesh shard structures have their own
        minimal checks)."""
        from ..analysis import statecheck  # lazy: no import cycle

        viols = []
        if isinstance(dev, jaxpath.DeviceTables):
            viols += statecheck.check_device_tables(dev)
        elif (
            isinstance(dev, tuple)
            and dev
            and isinstance(dev[0], jaxpath.CTrieTables)
        ):
            viols += statecheck.check_ctrie_tables(dev[0])
        if ov_dev is not None:
            viols += [
                f"overlay: {v}"
                for v in statecheck.check_device_tables(ov_dev)
            ]
        if viols:
            raise statecheck.InvariantViolation(
                "device-table invariant contract violated at the patch "
                "boundary:\n  " + "\n  ".join(viols)
            )

    def _build_walk(self, tables: CompiledTables, classes, dirty_hint,
                    compressed: bool = False):
        """Fused-walk tables for the full-depth steering class (level
        walk or the compressed skip-node walk, per ``compressed``).

        The joined byte planes bake RULE BYTES into the resident layout,
        so a rules-only edit whose dirty targets intersect the walk's
        kept tidx set must rebuild; a non-intersecting edit (the common
        1-key case at scale — the deep tail is a small extracted subset)
        carries the resident walk forward untouched.  Any build failure
        degrades to the XLA walk, never to a refusal.

        Compressed-path resident form: (CWalkTables, d_max) — the unroll
        bound travels beside the pytree into the jit-factory cache key."""
        want_path = "ctrie" if compressed else "trie"
        min_depth = classes[-2] if len(classes) >= 2 else None
        rules_only = jaxpath.hint_trie_unchanged(dirty_hint)
        with self._lock:
            prev_active, prev_meta = self._active, self._walk_meta
        if (
            rules_only
            and prev_meta is not None
            and prev_active is not None
            and prev_active[0] == want_path
            and len(prev_active) > 5
            and prev_active[5] is not None
            and prev_meta["min_depth"] == min_depth
        ):
            dirty = np.unique(np.asarray(dirty_hint.get("dense", ()), np.int64))
            if not compressed:
                # level walk: the extracted joined planes hold ONLY the
                # kept tidx rows — a non-intersecting edit carries the
                # resident walk forward untouched.  The compressed
                # walk's per-tidx matrix is FULL (root-level best0 hits
                # index it directly, outside the kept target set), so
                # every rules edit patches it.
                tidx_sorted = prev_meta["tidx_sorted"]
                if not bool(np.isin(dirty, tidx_sorted).any()):
                    return prev_active[5], prev_meta
            # dirty targets ARE resident: rewrite exactly their joined
            # rows on device (kilobytes) — the trie is untouched, so
            # levels/l0/nodes carry over
            try:
                if compressed:
                    p = pallas_walk.patch_cwalk_joined(
                        prev_active[5][0], prev_meta, tables, dirty,
                        self._device,
                    )
                    patched = None if p is None else (p, prev_active[5][1])
                else:
                    patched = pallas_walk.patch_walk_joined(
                        prev_active[5], prev_meta, tables, dirty,
                        self._device,
                    )
            except Exception:
                patched = None
            if patched is not None:
                return patched, prev_meta
        built = self._walk_build_fn(compressed)(tables, min_depth)
        if built is None:
            return None, None
        wt, meta = built
        return ((wt, meta["d_max"]) if compressed else wt), meta

    def _walk_build_fn(self, compressed: bool):
        """(tables, min_depth) -> (walk tables, meta) | None, exception-
        safe — the shared builder of the sync and background paths."""
        def build(tables, min_depth):
            try:
                if compressed:
                    return pallas_walk.build_cwalk_tables_meta(
                        tables, min_depth=min_depth, device=self._device
                    )
                return pallas_walk.build_walk_tables_meta(
                    tables, min_depth=min_depth, device=self._device
                )
            except Exception:
                return None

        return build

    def _spawn_walk_rebuild(self, tables: CompiledTables, classes,
                            compressed: bool = False) -> None:
        """Background fused-walk rebuild after a structural edit: build
        off-thread, install under the lock ONLY if this table generation
        is still resident (a newer load supersedes the result — its own
        walk build wins).  Classify dispatches read ``_active`` under the
        lock, so they pick the walk up at the next chunk."""
        want_path = "ctrie" if compressed else "trie"
        min_depth = classes[-2] if len(classes) >= 2 else None

        def work():
            built = self._walk_build_fn(compressed)(tables, min_depth)
            if built is None:
                return
            wt, meta = built
            if compressed:
                pallas_walk.warm_cwalk_patch_scatters(wt, self._device)
                resident = (wt, meta["d_max"])
            else:
                pallas_walk.warm_walk_patch_scatters(wt, self._device)
                resident = wt
            with self._lock:
                if (
                    self._tables is tables
                    and self._active is not None
                    and self._active[0] == want_path
                ):
                    self._active = self._active[:5] + (resident,)
                    self._walk_meta = meta

        spawn(work, name="infw-walk-rebuild")

    # -- classify -----------------------------------------------------------

    def classify_async(
        self, batch: PacketBatch, apply_stats: bool = True
    ) -> PendingClassify:
        """Dispatch H2D + kernel now; return a handle whose .result()
        materializes D2H and applies the stats increment exactly once.
        JAX's async dispatch means this returns as soon as the work is
        enqueued — in-flight batches finish on whatever table buffer they
        were dispatched against (the double-buffer swap contract).

        ``apply_stats=False`` defers the accumulator increment to the
        caller (who applies ``stats_delta`` itself) — used by the daemon's
        ingest so statistics land exactly once only after the source file
        is consumed, never on a batch that will be re-classified after a
        mid-pipeline failure."""
        with self._lock:
            if self._active is None:
                raise RuntimeError("no rule tables loaded")
            path, dev, block_b, wide_rids, ov_dev, _walk = self._active
        if wide_rids:
            return self._classify_async_wide(dev, batch, apply_stats)
        # Packed wire format: 24B/packet H2D (12B for v4-compactable
        # chunks, via the narrow transform in _dispatch_wire; 28B/16B
        # when wide ifindex/pkt_len disqualify narrowing), 2B/packet D2H
        # — the host<->device link is the streaming bottleneck, not the
        # kernel.  The daemon regroups ingest by family, so the majority
        # family of real traffic ships compact.
        kind = np.asarray(batch.kind)
        v4_only = not bool((kind == KIND_IPV6).any())
        compact = v4_only and not bool(np.asarray(batch.ip_words)[:, 1:].any())
        wire_np = batch.pack_wire_v4() if compact else batch.pack_wire()
        pay_np = plen_np = None
        if self._payload is not None and batch.payload is not None:
            pay_np = np.asarray(batch.payload)
            plen_np = (
                np.asarray(batch.payload_len, np.int32)
                if batch.payload_len is not None
                else np.full(pay_np.shape[0], pay_np.shape[1], np.int32)
            )
        if self._flow is not None:
            # flow tier first: the probe serves established flows and
            # only misses fall through to the stateless dispatch
            return self.classify_prepared(
                self.prepare_packed(
                    wire_np, v4_only,
                    tcp_flags=getattr(batch, "tcp_flags", None),
                    payload=pay_np, payload_len=plen_np,
                ),
                apply_stats=apply_stats,
            )
        pending = self._dispatch_wire(
            path, dev, block_b, wire_np, v4_only, kind, apply_stats,
            ov_dev=ov_dev,
        )
        if pay_np is None:
            return pending

        def materialize() -> ClassifyOutput:
            # one follow-on payload-match launch per admission (the
            # multi-dispatch form of the fused fourth tier)
            return self._apply_payload_wire(
                pending.result(), pay_np, plen_np, wire_np, apply_stats,
            )

        return PendingClassify(materialize)

    def supports_packed(self) -> bool:
        """True when classify_async_packed can take this table generation
        (the wide-ruleId fallback needs the full u32 batch path)."""
        with self._lock:
            return self._active is not None and not self._active[3]

    def v6_depth_groups(self, ifindex: np.ndarray, ip_words: np.ndarray,
                        idx: np.ndarray):
        """Split ``idx`` (positions of a v6 sub-batch) into depth-class
        groups [((class_or_None, generation), positions)] using the
        current generation's LUT — the v6 analogue of the family split:
        a group with class d is fully classified by trie_levels[:1+d]
        (52%% of bench v6 packets land at d<=3 while the full walk is 14
        deep levels); class None = full depth.  The generation token
        must travel with the class into classify_async_packed.  Returns
        [((None, 0), idx)] when steering is unavailable (gen 0 never
        matches a live generation, so the walk stays full-depth)."""
        with self._lock:
            steer = self._depth_steer
        if steer is None or len(idx) == 0:
            return [((None, 0), idx)]
        root_lut, lut, classes, gen = steer
        return [
            ((d, gen), sub)
            for d, sub in jaxpath.depth_group_indices(
                root_lut, lut, classes, ifindex, ip_words, idx
            )
        ]

    def serving_shape_classes(self):
        """The depth-steering classes of the CURRENT table generation as
        ``(class_or_None, generation)`` pairs (the ``depth`` argument of
        prepare_packed), full-depth class last — what the scheduler's
        ladder pre-warm must cover so no steering-specialized jit
        compiles on the serving path.  Empty when steering is off
        (dense / wide-ruleId paths)."""
        with self._lock:
            steer = self._depth_steer
        if steer is None:
            return []
        classes, gen = steer[2], steer[3]
        return [(int(d), gen) for d in classes] + [(None, gen)]

    #: data-axis width of one dispatched wire batch — 1 on a single
    #: chip; MeshTpuClassifier overrides with its "data" shard count.
    #: The scheduler multiplies its per-chip admission budget by this.
    data_shards = 1

    def classify_async_packed(
        self, wire_np: np.ndarray, v4_only: bool, apply_stats: bool = True,
        depth=None, tcp_flags: Optional[np.ndarray] = None,
        payload: Optional[np.ndarray] = None,
        payload_len: Optional[np.ndarray] = None,
    ) -> PendingClassify:
        # ``depth`` is the (class, generation) pair from v6_depth_groups;
        # a generation mismatch (table swapped since grouping) falls back
        # to the full walk — never under-walk against a newer table.
        """classify_async for a pre-packed (B, 4|7) uint32 wire array
        (PacketBatch.pack_wire_subset): the daemon's hot loop skips the
        9-array subset copy entirely.  Caller contract: supports_packed()
        is True for the current table generation; kind is recovered from
        wire w0 for the host-side XDP rebuild."""
        return self.classify_prepared(
            self.prepare_packed(wire_np, v4_only, depth=depth,
                                tcp_flags=tcp_flags, payload=payload,
                                payload_len=payload_len),
            apply_stats=apply_stats,
        )

    def prepare_packed(self, wire_np: np.ndarray, v4_only: bool, depth=None,
                       tcp_flags: Optional[np.ndarray] = None,
                       payload: Optional[np.ndarray] = None,
                       payload_len: Optional[np.ndarray] = None):
        """First half of classify_async_packed: choose the wire format
        (delta / wire8 / narrow / full per the codec knob and chunk
        eligibility) and START the H2D copy of the chosen payload,
        returning an opaque plan for classify_prepared.  The daemon's
        double-buffered ingest stages the NEXT chunk's plan while the
        current chunk's classify runs, so the transfer hides under
        compute; the plan snapshots the table generation at prepare
        time — in-flight plans finish on the tables they were staged
        against (the double-buffer swap contract)."""
        if self._resident is not None and self._flow is not None:
            plan = self._plan_resident(wire_np, v4_only, depth, tcp_flags,
                                       payload=payload,
                                       payload_len=payload_len)
            if plan is not None:
                return plan
        flow_probe = None
        if self._flow is not None and wire_np.shape[1] in (4, 7):
            # Flow tier engaged: dispatch the fused probe NOW (its H2D
            # + kernel overlap other in-flight work).  The probe MUST
            # run BEFORE the stateless snapshot below: it captures the
            # flow generation vector, and a concurrent load_tables
            # between the two capture points can then only make the
            # stamped generation OLDER than the tables that compute the
            # miss verdicts — those inserts are stale on arrival
            # (safe).  The reverse order would stamp old-table verdicts
            # with the NEW generation and serve them as live (the
            # flowstale bug, raced into existence).
            with self._lock:
                probe_ok = self._active is not None and not self._active[3]
            if probe_ok:
                fused, ctx = self._flow.probe(wire_np, tflags_np=tcp_flags)
                try:
                    fused.copy_to_host_async()
                except (AttributeError, RuntimeError):
                    pass
                flow_probe = (fused, ctx)
        with self._lock:
            if self._active is None:
                raise RuntimeError("no rule tables loaded")
            path, dev, block_b, wide_rids, ov_dev, walk_dev = self._active
        if wide_rids:
            raise RuntimeError(
                "wide-ruleId tables need the full-batch path (supports_packed)"
            )
        kind = (wire_np[:, 0] & 3).astype(np.int32)
        d = None
        use_walk = None
        if depth is not None:
            dclass, gen = depth
            with self._lock:
                cur_gen = self._depth_steer[3] if self._depth_steer else -1
            if dclass is not None and gen == cur_gen:
                d = int(dclass)
            elif dclass is None and gen == cur_gen:
                # the declared FULL-DEPTH class of the current
                # generation: eligible for the fused Pallas deep walk
                # (its extraction threshold came from the same class
                # list this grouping used — the gen token proves it)
                use_walk = walk_dev
        if flow_probe is not None:
            fused, ctx = flow_probe
            plan = {
                "flow": True, "fused": fused, "ctx": ctx,
                "wire_np": wire_np, "tcp_flags": tcp_flags,
                "path": path, "dev": dev, "block_b": block_b,
                "ov_dev": ov_dev, "depth": d, "walk_dev": use_walk,
                "v4_only": v4_only, "kind": kind, "n": wire_np.shape[0],
            }
        else:
            plan = self._plan_wire(
                path, dev, block_b, wire_np, v4_only, kind,
                ov_dev=ov_dev, depth=d, walk_dev=use_walk,
            )
        if self._telemetry is not None:
            # multi-dispatch telemetry (ISSUE-13): the sketch update
            # launches at materialize time with the admission's merged
            # verdicts (one extra async device program, no host
            # round-trip); the miss sub-dispatch inside _launch_flow
            # goes through _plan_wire/_launch_wire directly and so never
            # double-counts
            plan["telem_wire"] = wire_np
            plan["telem_flags"] = tcp_flags
        if self._mlscore is not None:
            # multi-dispatch anomaly scoring (ISSUE-14): one in-stream
            # follow-on launch per admission over (wire, merged RULE
            # verdicts) — on flow plans it runs INSIDE _launch_flow,
            # between the verdict merge and the miss insert, so the
            # flow table caches the ENFORCED verdicts exactly like the
            # fused path; the miss sub-dispatch never double-scores
            plan["ml_wire"] = wire_np
            plan["ml_flags"] = tcp_flags
        if self._payload is not None and payload is not None:
            # multi-dispatch payload matching (ISSUE-19): one follow-on
            # AC-match launch per admission — on flow plans it runs
            # INSIDE _launch_flow, between the verdict merge and the
            # miss insert, so the flow table caches the ENFORCED
            # verdicts exactly like the fused path
            plan["pay_np"] = np.asarray(payload)
            plan["plen_np"] = (
                np.asarray(payload_len, np.int32)
                if payload_len is not None
                else np.full(payload.shape[0], payload.shape[1], np.int32)
            )
            plan["pay_wire"] = wire_np
        return plan

    def classify_prepared(self, plan, apply_stats: bool = True) -> PendingClassify:
        """Second half: launch the classify on a prepare_packed plan."""
        if plan.get("resident"):
            # telemetry/scoring (when on) already rode the fused program
            return self._launch_resident(plan, apply_stats)
        if plan.get("flow"):
            # flow plans run the scoring launch INSIDE _launch_flow
            # (between merge and insert — the enforced verdicts must be
            # what the flow table caches)
            pending = self._launch_flow(plan, apply_stats)
            ml_done = True
        else:
            pending = self._launch_wire(plan, apply_stats)
            ml_done = False
        ml = self._mlscore
        tel = self._telemetry
        run_ml = ml is not None and not ml_done and "ml_wire" in plan
        run_pay = (
            self._payload is not None and "pay_np" in plan
            and not plan.get("flow")
        )
        run_tel = tel is not None and "telem_wire" in plan
        if not run_ml and not run_pay and not run_tel:
            return pending

        def materialize() -> ClassifyOutput:
            out = pending.result()
            if run_ml:
                # one follow-on scoring launch per admission over the
                # merged rule verdicts; in enforce mode the rewritten
                # res16 replaces the output (verdicts, XDP and stats
                # re-derive host-side — the wire8 contract)
                out = self._apply_mlscore_wire(
                    out, plan["ml_wire"], plan["ml_flags"], apply_stats,
                )
            if run_pay:
                # one follow-on AC-match launch over the (possibly
                # score-rewritten) verdicts — same ordering as the
                # fused step: score, then payload, then telemetry
                # counts what was served
                out = self._apply_payload_wire(
                    out, plan["pay_np"], plan["plen_np"],
                    plan["pay_wire"], apply_stats,
                )
            if run_tel:
                # one follow-on telemetry program per admission: wire +
                # SERVED verdicts in, nothing back (the decimated drain
                # is the only telemetry readback)
                tel.update(plan["telem_wire"], out.results,
                           tflags_np=plan["telem_flags"])
            return out

        return PendingClassify(materialize)

    def _apply_mlscore_wire(self, out: ClassifyOutput, wire_np, tcp_flags,
                            apply_stats: bool) -> ClassifyOutput:
        """Score one flow-less wire admission (the follow-on launch) and
        apply the policy rewrite host-side when it changed anything."""
        from ..daemon import stats_from_results  # lazy: no import cycle
        from ..flow import host_unpack_wire

        res16, _anom, _scores = self._mlscore.update(
            wire_np, out.results, tflags_np=tcp_flags,
        )
        if np.array_equal(res16, (out.results & 0xFFFF).astype(np.uint16)):
            return out
        f = host_unpack_wire(wire_np)
        results, xdp = jaxpath.host_finalize_wire(res16, f["kind"])
        stats_delta = stats_from_results(
            results, f["pkt_len"].astype(np.int64)
        )
        if apply_stats:
            # the device-side stats already applied inside the launch:
            # swap them for the post-policy derivation
            self._stats.add(stats_delta - out.stats_delta)
        return ClassifyOutput(
            results=results, xdp=xdp, stats_delta=stats_delta
        )

    def _apply_payload_wire(self, out: ClassifyOutput, pay_np, plen_np,
                            wire_np, apply_stats: bool) -> ClassifyOutput:
        """Payload-match one flow-less wire admission (the follow-on
        launch) and apply the enforce-mode rewrite host-side when it
        changed anything.  Counters accrue inside the tier."""
        from ..daemon import stats_from_results  # lazy: no import cycle
        from ..flow import host_unpack_wire

        f = host_unpack_wire(wire_np)
        res16 = (out.results & 0xFFFF).astype(np.uint16)
        new16, _hit = self._payload.apply_wire(
            res16, pay_np, plen_np, f["proto"], f["dst_port"],
        )
        new16 = np.asarray(new16, np.uint16)
        if np.array_equal(new16, res16):
            return out
        results, xdp = jaxpath.host_finalize_wire(new16, f["kind"])
        stats_delta = stats_from_results(
            results, f["pkt_len"].astype(np.int64)
        )
        if apply_stats:
            # the device-side stats already applied inside the launch:
            # swap them for the post-policy derivation
            self._stats.add(stats_delta - out.stats_delta)
        return ClassifyOutput(
            results=results, xdp=xdp, stats_delta=stats_delta
        )

    # -- resident serving loop (ISSUE-12) ------------------------------------

    @staticmethod
    def _clamp_payload(pay, plen, cap):
        """Fix a payload column to the tier's prefix cap: (…, L) uint8
        zero-padded/truncated to (…, cap), lengths clipped to cap (the
        prefix-truncation contract: only occurrences ending wholly
        within min(len, cap) count)."""
        pay = np.ascontiguousarray(pay, np.uint8)
        w = pay.shape[-1]
        if plen is None:
            plen = np.full(pay.shape[:-1], w, np.int32)
        if w != cap:
            fixed = np.zeros(pay.shape[:-1] + (cap,), np.uint8)
            k = min(cap, w)
            fixed[..., :k] = pay[..., :k]
            pay = fixed
        plen = np.minimum(
            np.ascontiguousarray(plen, np.int32), np.int32(cap)
        )
        return pay, plen

    def _plan_resident(self, wire_np, v4_only, depth, tcp_flags,
                       payload=None, payload_len=None):
        """Plan + DISPATCH one admission through the resident fused
        step (jaxpath.jitted_resident_step): unlike the multi-dispatch
        plan there is no separate launch half — the whole admission is
        one device program, already in flight when this returns; the
        plan only carries what the materialize needs.  Returns None
        when this admission cannot ride the resident path (wide
        ruleIds, unsupported wire width) — the caller falls back to the
        probe-then-classify plan, degrade never refuse."""
        if wire_np.shape[1] not in (4, 7):
            return None
        tier = self._flow
        pool = self._resident
        # generation-ordering contract: capture the flow-generation
        # snapshot BEFORE the table snapshot (see resident_gens_snapshot)
        gens_snap = tier.resident_gens_snapshot()
        ctx = pool.context(self)
        if ctx is None:
            pool.note("fallbacks")
            return None
        d = None
        if depth is not None and ctx.path == "trie":
            dclass, gen = depth
            with self._lock:
                cur_gen = self._depth_steer[3] if self._depth_steer else -1
            if dclass is not None and gen == cur_gen:
                d = int(dclass)
        n = wire_np.shape[0]
        kind = (wire_np[:, 0] & 3).astype(np.int32)
        v4_walk = bool(v4_only) and ctx.path == "trie"
        tel = self._telemetry
        ml = self._mlscore
        pt = self._payload
        use_pay = pt is not None and payload is not None
        pay_np = plen_np = None
        if use_pay:
            pay_np, plen_np = self._clamp_payload(
                payload, payload_len, pt.spec.plen
            )
        fn = jaxpath.jitted_resident_step(
            tier.config.entries, tier.config.ways, ctx.path, v4_walk, d,
            ctx.d_max,
            ctx.ov_dev is not None,
            sketch=tel.spec if tel is not None else None,
            score=ml.spec if ml is not None else None,
            payload=pt.spec if use_pay else None,
        )
        tables_args = (
            (ctx.tdev, ctx.ov_dev) if ctx.ov_dev is not None
            else (ctx.tdev,)
        )
        wire_dev = pool.stage_wire(self, wire_np)
        payload_ops = payload_dev = None
        if use_pay:
            # automaton value operands + this admission's payload
            # column: the pattern tensors are persistent device values
            # (swapped whole on a pattern hot-swap, never recompiled),
            # the pay/plen pair rides the wire tail
            payload_ops = pt.device_ops()
            payload_dev = (
                jax.device_put(pay_np, self._device),
                jax.device_put(plen_np, self._device),
            )
        fused, epoch = tier.resident_dispatch(
            fn, tables_args, wire_dev, n, wire_np=wire_np,
            tflags_np=tcp_flags, gens_snap=gens_snap,
            alloc_note=pool.note_alloc, telemetry=tel, mlscore=ml,
            payload_ops=payload_ops, payload_dev=payload_dev,
        )
        pool.note("dispatches")
        pool.note(f"slot{(epoch - 1) & 1}_dispatches")
        pool.note_walk(ctx, kind, v4_walk, d)
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        self._note_wire(f"wire{wire_np.shape[1]}", n, wire_np.nbytes)
        if use_pay:
            self._note_wire(
                "payload", n, pay_np.nbytes + plen_np.nbytes
            )
        return {"resident": True, "fused": fused, "n": n, "kind": kind,
                "epoch": epoch, "mlscore": ml is not None,
                "payload": use_pay, "pay_np": pay_np, "plen_np": plen_np,
                "pkt_len": self._wire4_pkt_len(wire_np)}

    def prepare_packed_super(self, wire_stack: np.ndarray, v4_only: bool,
                             tcp_flags_stack: Optional[np.ndarray] = None,
                             payload_stack: Optional[np.ndarray] = None,
                             payload_len_stack: Optional[np.ndarray] = None):
        """Plan + DISPATCH ``k`` stacked same-shape admissions through
        the superbatch device epoch program (ISSUE-16,
        jaxpath.jitted_resident_superbatch): flow probe/insert, sketch
        updates and anomaly-score state chain through the device-side
        scan carry, with one stacked (k, L) fused readback instead of k
        host round-trips — bit-identical to k sequential fused
        dispatches by construction.  ``wire_stack`` is (k, b, w) with
        every row one admission of ONE shape class (same b/w/v4_only/
        flags presence — jit shape keying would recompile otherwise).
        Returns None when the resident path cannot serve (the caller
        falls back to k single-admission plans, degrade never
        refuse)."""
        if (
            self._resident is None or self._flow is None
            or wire_stack.ndim != 3 or wire_stack.shape[2] not in (4, 7)
        ):
            return None
        tier = self._flow
        pool = self._resident
        # generation-ordering contract: flow-generation snapshot BEFORE
        # the table snapshot (see resident_gens_snapshot)
        gens_snap = tier.resident_gens_snapshot()
        ctx = pool.context(self)
        if ctx is None:
            pool.note("fallbacks")
            return None
        k, n, w = wire_stack.shape
        tel = self._telemetry
        ml = self._mlscore
        pt = self._payload
        use_pay = pt is not None and payload_stack is not None
        pay_np = plen_np = None
        if use_pay:
            pay_np, plen_np = self._clamp_payload(
                payload_stack, payload_len_stack, pt.spec.plen
            )
        fn = jaxpath.jitted_resident_superbatch(
            tier.config.entries, tier.config.ways, ctx.path,
            bool(v4_only) and ctx.path == "trie", None, ctx.d_max,
            ctx.ov_dev is not None,
            sketch=tel.spec if tel is not None else None,
            score=ml.spec if ml is not None else None,
            payload=pt.spec if use_pay else None,
        )
        tables_args = (
            (ctx.tdev, ctx.ov_dev) if ctx.ov_dev is not None
            else (ctx.tdev,)
        )
        wire_dev = pool.stage_wire(self, wire_stack.reshape(k * n, w))
        wire_dev = wire_dev.reshape(k, n, w)
        payload_ops = payload_dev = None
        if use_pay:
            # the stacked (k, b, L) payload columns ride the scan xs
            # next to the wire; automaton operands stay loop-invariant
            payload_ops = pt.device_ops()
            payload_dev = (
                jax.device_put(pay_np, self._device),
                jax.device_put(plen_np, self._device),
            )
        fused, epoch = tier.resident_dispatch_super(
            fn, tables_args, wire_dev, k, n, wire_np=wire_stack,
            tflags_np=tcp_flags_stack, gens_snap=gens_snap,
            alloc_note=pool.note_alloc, telemetry=tel, mlscore=ml,
            payload_ops=payload_ops, payload_dev=payload_dev,
        )
        pool.note("dispatches")
        pool.note("superbatch_dispatches")
        pool.note("superbatch_admissions", k)
        kinds = (wire_stack[:, :, 0] & 3).astype(np.int32)
        pool.note_walk(ctx, kinds, bool(v4_only) and ctx.path == "trie", None)
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        self._note_wire(f"wire{w}", k * n, wire_stack.nbytes)
        if use_pay:
            self._note_wire(
                "payload", k * n, pay_np.nbytes + plen_np.nbytes
            )
        pkt_lens = [self._wire4_pkt_len(wire_stack[j]) for j in range(k)]
        return {"resident_super": True, "fused": fused, "k": k, "n": n,
                "kinds": kinds, "epoch0": epoch - k,
                "mlscore": ml is not None, "payload": use_pay,
                "pay_np": pay_np, "plen_np": plen_np,
                "pkt_lens": pkt_lens}

    def classify_prepared_super(self, plan, apply_stats: bool = True):
        """Materialize half of a superbatch plan: ONE pending per
        admission row, in dispatch order — the daemon pairs each with
        its ring chunk and releases slots independently; out-of-order
        result() calls stay correct because every tier's mirror queue
        drains in device-epoch order (resident_note_materialized)."""
        tier = self._flow
        k, n, epoch0 = plan["k"], plan["n"], plan["epoch0"]

        def make_row(j: int) -> PendingClassify:
            epoch = epoch0 + 1 + j
            kind = plan["kinds"][j]
            pkt_len = plan["pkt_lens"][j]

            def materialize() -> ClassifyOutput:
                from ..daemon import stats_from_results  # lazy: no cycle

                row = jaxpath.resident_fused_host((plan["fused"], j))
                anom = scores = pay_hit = pay_rw = None
                if plan.get("payload"):
                    parts = jaxpath.split_resident_payload_outputs(
                        row, n, score=bool(plan.get("mlscore"))
                    )
                    pay_hit, pay_rw = parts[-2], parts[-1]
                    parts = parts[:-2]
                    if plan.get("mlscore"):
                        (res16, _hit, hits, stale, counts, anom,
                         scores) = parts
                    else:
                        res16, _hit, hits, stale, counts = parts
                elif plan.get("mlscore"):
                    res16, _hit, hits, stale, counts, anom, scores = (
                        jaxpath.split_resident_score_outputs(row, n)
                    )
                else:
                    res16, _hit, hits, stale, counts = (
                        jaxpath.split_resident_outputs(row, n)
                    )
                inserts, evictions, promotes = counts
                tier.stats.add(
                    hits=hits, misses=n - hits, stale_rejects=stale,
                    inserts=inserts, evictions=evictions,
                    promotes=promotes,
                )
                tier.resident_note_materialized(epoch)
                if self._telemetry is not None:
                    self._telemetry.resident_note_materialized(epoch)
                if anom is not None and self._mlscore is not None:
                    self._mlscore.resident_note_materialized(
                        epoch, anom_np=anom, score_np=scores,
                    )
                if pay_hit is not None and self._payload is not None:
                    self._note_payload_resident(
                        plan, pay_hit, pay_rw, row=j
                    )
                if evictions and tier.on_evict is not None:
                    try:
                        tier.on_evict(evictions, inserts, epoch)
                    except Exception:
                        pass
                results, xdp = jaxpath.host_finalize_wire(res16, kind)
                stats_delta = stats_from_results(results, pkt_len)
                if apply_stats:
                    self._stats.add(stats_delta)
                return ClassifyOutput(
                    results=results, xdp=xdp, stats_delta=stats_delta
                )

            # np.asarray keeps the host copy on the array: the row's
            # materialize reads it again without a second transfer
            return PendingClassify(
                materialize, readback=lambda: np.asarray(plan["fused"])
            )

        return [make_row(j) for j in range(k)]

    def _launch_resident(self, plan, apply_stats: bool) -> PendingClassify:
        """Materialize half of the resident plan: ONE ~100 B fused
        readback carries the merged verdicts, the hit bitmap and the
        flow counters; statistics derive host-side from the verdicts +
        the pkt_len column that never crossed the link (the wire8
        readback contract) — verdict- and stats-bit-identical to the
        multi-dispatch flow plan (the statecheck resident config and
        the bench_resident oracle gate pin this)."""
        tier = self._flow
        n, kind, epoch = plan["n"], plan["kind"], plan["epoch"]
        pkt_len = plan["pkt_len"]

        def materialize() -> ClassifyOutput:
            from ..daemon import stats_from_results  # lazy: no import cycle

            arr = np.asarray(plan["fused"])
            anom = scores = pay_hit = pay_rw = None
            if plan.get("payload"):
                # payload extension of the fused readback: the last
                # 2*ceil(n/32) words are the matched-lane + rewritten-
                # lane bitmaps; res16 is the POLICY verdict vector
                # (payload-rewritten in enforce mode)
                parts = jaxpath.split_resident_payload_outputs(
                    arr, n, score=bool(plan.get("mlscore"))
                )
                pay_hit, pay_rw = parts[-2], parts[-1]
                parts = parts[:-2]
                if plan.get("mlscore"):
                    res16, _hit, hits, stale, counts, anom, scores = parts
                else:
                    res16, _hit, hits, stale, counts = parts
            elif plan.get("mlscore"):
                # scoring extension of the fused readback: res16 is
                # the POLICY verdict vector (rewritten in enforce
                # mode) — stats and XDP derive from what was served
                res16, _hit, hits, stale, counts, anom, scores = (
                    jaxpath.split_resident_score_outputs(arr, n)
                )
            else:
                res16, _hit, hits, stale, counts = (
                    jaxpath.split_resident_outputs(arr, n)
                )
            inserts, evictions, promotes = counts
            tier.stats.add(
                hits=hits, misses=n - hits, stale_rejects=stale,
                inserts=inserts, evictions=evictions, promotes=promotes,
            )
            tier.resident_note_materialized(epoch)
            if self._telemetry is not None:
                self._telemetry.resident_note_materialized(epoch)
            if anom is not None and self._mlscore is not None:
                self._mlscore.resident_note_materialized(
                    epoch, anom_np=anom, score_np=scores,
                )
            if pay_hit is not None and self._payload is not None:
                self._note_payload_resident(plan, pay_hit, pay_rw)
            if evictions and tier.on_evict is not None:
                try:
                    tier.on_evict(evictions, inserts, epoch)
                except Exception:
                    pass
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            stats_delta = stats_from_results(results, pkt_len)
            if apply_stats:
                self._stats.add(stats_delta)
            return ClassifyOutput(
                results=results, xdp=xdp, stats_delta=stats_delta
            )

        # np.asarray keeps the host copy on the array: materialize reads
        # it again without a second transfer
        return PendingClassify(
            materialize, readback=lambda: np.asarray(plan["fused"])
        )

    def _note_payload_resident(self, plan, pay_hit, pay_rw,
                               row: Optional[int] = None) -> None:
        """Fold one resident admission's payload outcome into the tier
        counters.  The ~100 B fused readback carries only the packed
        hit/rewrite bitmaps — when mask tracking is on (statecheck),
        the full (B, PW) match bitmap re-derives through one standalone
        launch over the SAME automaton operands."""
        pt = self._payload
        pay_np = plan["pay_np"]
        plen_np = plan["plen_np"]
        if row is not None:
            pay_np, plen_np = pay_np[row], plen_np[row]
        bitmap = None
        if pt.tracking:
            bitmap = pt.match(pay_np, plen_np)
        pt.note(bitmap, pay_hit, pay_rw, pay_np=pay_np, plen_np=plen_np)

    @property
    def resident(self):
        """The ResidentPool when the resident serving loop is enabled."""
        return self._resident

    def resident_counters(self):
        """resident_* pool gauges for /metrics (empty when off)."""
        return {} if self._resident is None else (
            self._resident.counter_values()
        )

    @property
    def telemetry(self):
        """The TelemetryTier when the telemetry plane is enabled."""
        return self._telemetry

    def telemetry_counters(self):
        """telemetry_* counters for /metrics (empty when off)."""
        return {} if self._telemetry is None else (
            self._telemetry.counter_values()
        )

    def warm_telemetry_ladder(self, ladder) -> int:
        """Pre-compile the classic sketch-update executables across the
        batch ladder (scheduler prewarm hook; resident fused variants
        warm through the production dispatch like every other fused
        program)."""
        if self._telemetry is None:
            return 0
        return self._telemetry.warm(ladder)

    @property
    def mlscore(self):
        """The AnomalyTier when the scoring plane is enabled."""
        return self._mlscore

    def mlscore_counters(self):
        """mlscore_* counters for /metrics (empty when off)."""
        return {} if self._mlscore is None else (
            self._mlscore.counter_values()
        )

    def warm_mlscore_ladder(self, ladder) -> int:
        """Pre-compile the classic score-update executables across the
        batch ladder (scheduler prewarm hook; the resident fused score
        variants warm through the production dispatch)."""
        if self._mlscore is None:
            return 0
        return self._mlscore.warm(ladder)

    def set_score_model(self, model, version=None) -> None:
        """Hot-swap the anomaly model (validated artifact -> new value
        operands, zero recompiles).  The tier's on_swap hook then runs
        _on_score_model_swap: a model swap behaves like a rule patch."""
        if self._mlscore is None:
            raise RuntimeError("mlscore tier is not enabled")
        self._mlscore.swap_model(model, version=version)

    def _on_score_model_swap(self) -> None:
        """Invalidate flow-cached verdicts after a model swap through
        the SAME generation stamps every table edit uses — in enforce
        mode the flow table caches enforced verdicts, and a swapped
        model must not keep serving the old model's denies."""
        if self._flow is not None:
            self._flow.bump_generation()

    @property
    def payload(self):
        """The PayloadTier when the payload-matching tier is enabled."""
        return self._payload

    def payload_counters(self):
        """payload_* counters/gauges for /metrics (empty when off)."""
        return {} if self._payload is None else (
            self._payload.counter_values()
        )

    def set_payload_patterns(self, patterns_or_model,
                             plen: Optional[int] = None) -> None:
        """Hot-swap the pattern set (must stay in the same AcSpec
        geometry buckets -> new value operands, zero recompiles).  The
        tier's on_swap hook then runs _on_pattern_swap: a pattern swap
        behaves like a rule patch."""
        if self._payload is None:
            raise RuntimeError("payload tier is not enabled")
        self._payload.swap_patterns(patterns_or_model, plen=plen)

    def set_payload_mode(self, mode: str) -> None:
        """Flip shadow/enforce — a (1,) device value swap, never a
        recompile; flow-cached verdicts invalidate like a swap (a
        pre-flip cached Deny must not outlive enforce mode)."""
        if self._payload is None:
            raise RuntimeError("payload tier is not enabled")
        self._payload.set_mode(mode)
        self._on_pattern_swap()

    def _on_pattern_swap(self) -> None:
        """Invalidate flow-cached verdicts after a pattern-set swap
        through the SAME generation stamps every table edit uses — on
        the flow paths the table caches payload-ENFORCED verdicts, and
        a swapped set must not keep serving the old set's denies."""
        if self._flow is not None:
            self._flow.bump_generation()

    def mark_resident_warm(self) -> None:
        """Freeze the pool's prewarm allocation baseline (called by
        scheduler.prewarm_ladder after the ladder warm): any pool
        allocation after this is a serving-path allocation — the
        zero-alloc steady-state gate."""
        if self._resident is not None:
            if self._flow is not None:
                # the classic probe/insert warm bumped the host epoch
                # past the donated device chain; re-sync it now so the
                # first serving dispatch rides the chain, not a re-seed
                self._flow.resident_seed_epoch()
            self._resident.mark_warm()

    def _launch_flow(self, plan, apply_stats: bool) -> PendingClassify:
        """Complete a flow-tier plan: decode the probe's fused buffer,
        serve the hit lanes from the cache, fall the compacted misses
        through the stateless dispatch (same snapshot), merge, and
        batch-insert the fresh verdicts.  Verdict bit-identity with the
        stateless path is the invariant: the key covers every
        verdict-relevant field and a hit requires a live generation, so
        hit lanes return exactly what the LPM+scan would."""
        from .. import flow as flow_mod

        tier = self._flow
        n = plan["n"]
        wire_np = plan["wire_np"]
        tcp_flags = plan["tcp_flags"]
        kind = plan["kind"]

        def materialize() -> ClassifyOutput:
            from ..daemon import stats_from_results  # lazy: no import cycle

            res16, hitmask, hits, stale = jaxpath.split_flow_probe_outputs(
                np.asarray(plan["fused"]), n
            )
            tier.stats.add(hits=hits, misses=n - hits,
                           stale_rejects=stale)
            res16 = res16.copy()
            # hit-lane statistics derive host-side from res16 + the
            # pkt_len column of the 4/7-word wire (the wire8 readback
            # contract) — the probe ships no stats tensor
            pl = (
                ((wire_np[:, 1] >> 16) & 0xFFFF)
                | ((wire_np[:, 0] >> 27) << 16)
            ).astype(np.int64)
            stats_delta = stats_from_results(res16.astype(np.uint32), pl)
            miss = np.nonzero(~hitmask)[0]
            miss_out = None
            if len(miss):
                m = len(miss)
                bucket = flow_mod.flow_miss_bucket(m)
                miss_wire = wire_np[miss]
                if bucket > m:
                    pad = np.zeros(
                        (bucket - m, miss_wire.shape[1]), np.uint32
                    )
                    pad[:, 0] = 3  # KIND_OTHER: PASS, no stats
                    miss_wire = np.concatenate([miss_wire, pad])
                sub_kind = (miss_wire[:, 0] & 3).astype(np.int32)
                miss_out = self._launch_wire(
                    self._plan_wire(
                        plan["path"], plan["dev"], plan["block_b"],
                        miss_wire, plan["v4_only"], sub_kind,
                        ov_dev=plan["ov_dev"], depth=plan["depth"],
                        walk_dev=plan["walk_dev"],
                    ),
                    apply_stats=False,
                ).result()
                res16[miss] = (
                    miss_out.results[:m] & 0xFFFF
                ).astype(np.uint16)
                stats_delta += miss_out.stats_delta
            if self._mlscore is not None:
                # the scoring launch rides between the verdict merge
                # and the miss insert: the flow table must cache the
                # ENFORCED verdicts (bit-identical to the fused path,
                # where _score_update_core runs before the in-program
                # insert), and stats re-derive from what was served
                # when the policy rewrote anything
                new16, _anom, _scores = self._mlscore.update(
                    wire_np, res16.astype(np.uint32), tflags_np=tcp_flags,
                )
                if not np.array_equal(new16, res16):
                    res16 = new16
                    stats_delta = stats_from_results(
                        res16.astype(np.uint32), pl
                    )
            if self._payload is not None and "pay_np" in plan:
                # the payload-match launch ALSO rides between merge and
                # insert (after scoring, same ordering as the fused
                # step): the flow table must cache the payload-enforced
                # verdicts — a matched flow stays denied from the cache
                f = flow_mod.host_unpack_wire(wire_np)
                new16, _pay_hit = self._payload.apply_wire(
                    res16.astype(np.uint16), plan["pay_np"],
                    plan["plen_np"], f["proto"], f["dst_port"],
                )
                new16 = np.asarray(new16, np.uint16)
                if not np.array_equal(new16, res16):
                    res16 = new16
                    stats_delta = stats_from_results(
                        res16.astype(np.uint32), pl
                    )
            if len(miss):
                verdicts = np.zeros(miss_wire.shape[0], np.uint32)
                verdicts[:m] = res16[miss].astype(np.uint32)
                mflags = None
                if tcp_flags is not None:
                    mflags = np.zeros(miss_wire.shape[0], np.int32)
                    mflags[:m] = np.asarray(tcp_flags, np.int32)[miss]
                tier.insert(plan["ctx"], miss_wire, verdicts,
                            tflags_np=mflags)
            if apply_stats:
                self._stats.add(stats_delta)
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            return ClassifyOutput(
                results=results, xdp=xdp, stats_delta=stats_delta
            )

        return PendingClassify(materialize)

    def _note_wire(self, fmt: str, n: int, nbytes: int) -> None:
        with self._lock:
            c = self._wire_counts.setdefault(fmt, [0, 0])
            c[0] += n
            c[1] += nbytes

    def wire_stats(self):
        """{format: (packets, H2D payload bytes)} since construction."""
        with self._lock:
            return {k: tuple(v) for k, v in self._wire_counts.items()}

    def _note_pallas(self, kernel: str, n: int) -> None:
        with self._lock:
            self._pallas_counts[kernel] = self._pallas_counts.get(kernel, 0) + n

    def pallas_stats(self):
        """{Pallas kernel: packets dispatched to it} since construction."""
        with self._lock:
            return dict(self._pallas_counts)

    @property
    def interpret(self) -> bool:
        """True when the Pallas kernels run in interpret mode (CPU)."""
        return self._interpret

    @staticmethod
    def _wire4_pkt_len(wire4_np: np.ndarray) -> np.ndarray:
        """Full pkt_len reconstruction from the 4-word wire (pack_wire
        w1>>16 plus the w0>>27 high-bit stash) — stays host-side for the
        sub-12B formats, whose statistics derive from the verdicts."""
        return (
            ((wire4_np[:, 1] >> 16) & 0xFFFF)
            | ((wire4_np[:, 0] >> 27) << 16)
        ).astype(np.int64)

    def _dispatch_wire(
        self, path, dev, block_b, wire_np, v4_only, kind, apply_stats,
        ov_dev=None, depth=None, walk_dev=None,
    ) -> PendingClassify:
        return self._launch_wire(
            self._plan_wire(
                path, dev, block_b, wire_np, v4_only, kind,
                ov_dev=ov_dev, depth=depth, walk_dev=walk_dev,
            ),
            apply_stats,
        )

    def _plan_wire(
        self, path, dev, block_b, wire_np, v4_only, kind,
        ov_dev=None, depth=None, walk_dev=None,
    ):
        """Format choice + H2D staging.  Returns the plan consumed by
        _launch_wire; every jax.device_put here is async, so a staged
        plan's transfer overlaps whatever the device is running."""
        n = wire_np.shape[0]
        plan = {
            "path": path, "dev": dev, "block_b": block_b, "ov_dev": ov_dev,
            "depth": depth, "walk_dev": walk_dev, "v4_only": v4_only,
            "kind": kind, "n": n,
        }
        put = lambda a: jax.device_put(a, self._device)
        if path in ("trie", "ctrie") and wire_np.shape[1] == 4 and n:
            codec = self._wire_codec
            if codec in ("auto", "delta"):
                # delta+varint compressed wire (packets.encode_delta_wire):
                # sorted-chunk IP deltas + dictionary meta, ~4-6 B/packet;
                # "auto" takes it only when it beats the wire8 floor.
                enc = encode_delta_wire(
                    wire_np,
                    max_bytes_per_pkt=8.0 if codec == "auto" else None,
                )
                if enc is not None:
                    # what actually crosses the link: the BUCKET-padded
                    # payload plus the dict/ifmap headers — the auto gate
                    # and the byte counters reason about shipped bytes,
                    # not the unpadded stream (a payload just over its
                    # bucket step would otherwise "win" on paper while
                    # shipping wire8-sized buffers)
                    shipped = (
                        wire_decode.payload_bucket(len(enc.payload))
                        + 256 * 4 + enc.ifmap.nbytes
                    )
                    if codec == "delta" or shipped < 8 * n:
                        plan.update(
                            fmt="delta", enc=enc,
                            pkt_len=self._wire4_pkt_len(wire_np),
                            payload=put(wire_decode.pad_payload(enc.payload)),
                            dictv=put(wire_decode.pad_dict(enc.dict_vals)),
                            ifmap=put(enc.ifmap),
                        )
                        self._note_wire("delta", n, shipped)
                        return plan
            # 8B/packet transfer (packets.wire8): classification never
            # reads pkt_len, so the length stays host-side and byte
            # statistics are computed from the returned verdicts; the
            # ifindex travels as a 4-bit dictionary index.
            w8 = wire8(wire_np)
            if w8 is not None:
                wire8_np, ifmap = w8
                plan.update(
                    fmt="wire8", pkt_len=self._wire4_pkt_len(wire_np),
                    wire=put(wire8_np), ifmap=put(ifmap),
                )
                self._note_wire("wire8", n, wire8_np.nbytes + ifmap.nbytes)
                return plan
        if wire_np.shape[1] in (4, 7):
            # Narrow transfer (packets.narrow_wire): one word less per
            # packet on the H2D link when the chunk qualifies — the link
            # is the streaming bottleneck, not the kernel.
            nw = narrow_wire(wire_np)
            if nw is not None:
                wire_np = nw
        plan.update(fmt="wire", wire=put(wire_np))
        self._note_wire(f"wire{wire_np.shape[1]}", n, wire_np.nbytes)
        return plan

    def _launch_wire(self, plan, apply_stats: bool) -> PendingClassify:
        if plan["fmt"] == "delta":
            return self._launch_delta(plan, apply_stats)
        if plan["fmt"] == "wire8":
            return self._launch_wire8(plan, apply_stats)
        path, dev, block_b = plan["path"], plan["dev"], plan["block_b"]
        ov_dev, depth, walk_dev = plan["ov_dev"], plan["depth"], plan["walk_dev"]
        v4_only, kind, n = plan["v4_only"], plan["kind"], plan["n"]
        wire = plan["wire"]
        # Fused single-buffer output: results + stats come back in ONE
        # D2H materialization (jaxpath.fuse_wire_outputs) — each readback
        # RPC pays the link's sync floor, so two arrays per chunk would
        # double the per-chunk latency cost.
        if path == "dense":
            self._note_pallas("pallas-dense", n)
            fused = pallas_dense.jitted_classify_pallas_wire_fused(
                self._interpret, block_b
            )(dev, wire)
        elif path == "ctrie":
            # Compressed skip-node walk: fused Pallas for the declared
            # full-depth class (the extraction threshold travels with
            # the gen token, same contract as the level walk); XLA
            # compressed walk otherwise.  Depth-class truncation does
            # not apply — d_max is already the path-compressed bound.
            cdev, d_max = dev
            if walk_dev is not None and ov_dev is None:
                wt, dw = walk_dev
                self._note_pallas("pallas-cwalk", n)
                fused = pallas_walk.jitted_classify_cwalk_wire_fused(
                    dw, self._interpret
                )(wt, wire)
            elif ov_dev is not None:
                fused = jaxpath.jitted_classify_ctrie_wire_overlay_fused(
                    d_max
                )(cdev, ov_dev, wire)
            else:
                fused = jaxpath.jitted_classify_ctrie_wire_fused(d_max)(
                    cdev, wire
                )
        elif walk_dev is not None and ov_dev is None:
            # Fused deep walk: the whole v6 descent (level walk +
            # popcount-rank child step + joined rules tail) in one
            # Pallas grid pass with the extracted deep tail
            # VMEM-resident — no per-level HBM gather excursions.  The
            # overlay combine needs the XLA walk's score plumbing, so
            # overlay generations keep the XLA path for this class.
            self._note_pallas("pallas-walk", n)
            fused = pallas_walk.jitted_classify_walk_wire_fused(
                self._interpret
            )(walk_dev, wire)
        elif ov_dev is not None:
            fused = jaxpath.jitted_classify_wire_overlay_fused(
                True, v4_only, depth
            )(dev, ov_dev, wire)
        else:
            # Depth specialization: a v4-only batch walks only the ≤/32
            # trie levels; a v6 depth-class chunk walks trie_levels[:1+d]
            # (v6_depth_groups) — the daemon steers homogeneous chunks.
            fused = jaxpath.jitted_classify_wire_fused(
                True, v4_only, depth
            )(dev, wire)
        # Start the D2H copy now so it overlaps the dispatch of subsequent
        # batches; .result() then finds the bytes already (or sooner) on
        # host.  Not all platforms expose it — best effort.
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass

        def materialize() -> ClassifyOutput:
            res16, stats = jaxpath.split_wire_outputs(np.asarray(fused), n)
            stats_delta = jaxpath.merge_stats_host(stats)
            if apply_stats:
                self._stats.add(stats_delta)
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            return ClassifyOutput(results=results, xdp=xdp, stats_delta=stats_delta)

        return PendingClassify(materialize)

    def _launch_wire8(self, plan, apply_stats: bool) -> PendingClassify:
        """The 8B-wire launch: res16-only D2H; statistics (incl. exact
        byte counts) derive host-side from the verdicts + the pkt_len
        column that never crossed the link."""
        dev, ov_dev = plan["dev"], plan["ov_dev"]
        kind, n, pkt_len = plan["kind"], plan["n"], plan["pkt_len"]
        wire, ifm = plan["wire"], plan["ifmap"]
        if plan["path"] == "ctrie":
            cdev, d_max = dev
            fn = jaxpath.jitted_classify_ctrie_wire8_fused(
                d_max, ov_dev is not None
            )
            fused = (
                fn(cdev, ov_dev, wire, ifm)
                if ov_dev is not None else fn(cdev, wire, ifm)
            )
        elif ov_dev is not None:
            fused = jaxpath.jitted_classify_wire8_fused(True)(
                dev, ov_dev, wire, ifm
            )
        else:
            fused = jaxpath.jitted_classify_wire8_fused(False)(dev, wire, ifm)
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass

        def materialize() -> ClassifyOutput:
            from ..daemon import stats_from_results  # lazy: no import cycle

            res16 = jaxpath.unpack_res16_host(np.asarray(fused), n)
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            stats_delta = stats_from_results(results, pkt_len)
            if apply_stats:
                self._stats.add(stats_delta)
            return ClassifyOutput(
                results=results, xdp=xdp, stats_delta=stats_delta
            )

        return PendingClassify(materialize)

    def _launch_delta(self, plan, apply_stats: bool) -> PendingClassify:
        """Compressed-wire launch (packets.encode_delta_wire +
        kernels.wire_decode): the device decodes the ~4-6 B/packet stream
        on-chip and classifies in SORTED order; the host inverse-permutes
        the returned verdicts back to chunk order (the permutation, like
        pkt_len, never crosses the link).  res16-only D2H, host-derived
        statistics — the wire8 readback contract."""
        dev, ov_dev = plan["dev"], plan["ov_dev"]
        kind, n, pkt_len = plan["kind"], plan["n"], plan["pkt_len"]
        enc = plan["enc"]
        use_pallas = self._decode_pallas and enc.fixed_w > 0
        if plan["path"] == "ctrie":
            cdev, d_max = dev
            fn = wire_decode.jitted_classify_delta_ctrie_fused(
                ov_dev is not None, d_max, n, enc.dict_mode, enc.fixed_w,
                use_pallas=use_pallas, interpret=self._interpret,
            )
            dev = cdev
        else:
            fn = wire_decode.jitted_classify_delta_fused(
                ov_dev is not None, n, enc.dict_mode, enc.fixed_w,
                use_pallas=use_pallas, interpret=self._interpret,
            )
        if ov_dev is not None:
            fused = fn(dev, ov_dev, plan["payload"], plan["dictv"],
                       plan["ifmap"])
        else:
            fused = fn(dev, plan["payload"], plan["dictv"], plan["ifmap"])
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass

        def materialize() -> ClassifyOutput:
            from ..daemon import stats_from_results  # lazy: no import cycle

            res16_sorted = jaxpath.unpack_res16_host(np.asarray(fused), n)
            res16 = np.empty(n, np.uint16)
            res16[enc.perm] = res16_sorted
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            stats_delta = stats_from_results(results, pkt_len)
            if apply_stats:
                self._stats.add(stats_delta)
            return ClassifyOutput(
                results=results, xdp=xdp, stats_delta=stats_delta
            )

        return PendingClassify(materialize)

    def _classify_async_wide(
        self, dev, batch: PacketBatch, apply_stats: bool
    ) -> PendingClassify:
        """u32 results path for tables whose ruleIds exceed the wire
        format's 8 bits: full DeviceBatch H2D and 4B/packet results D2H —
        slower on the link, lossless on ruleIds."""
        db = jaxpath.device_batch(batch, self._device)
        res, xdp, stats = jaxpath.jitted_classify(True)(dev, db)
        for arr in (res, xdp, stats):
            try:
                arr.copy_to_host_async()
            except (AttributeError, RuntimeError):
                break

        def materialize() -> ClassifyOutput:
            stats_delta = jaxpath.merge_stats_host(np.asarray(stats))
            if apply_stats:
                self._stats.add(stats_delta)
            return ClassifyOutput(
                results=np.asarray(res), xdp=np.asarray(xdp),
                stats_delta=stats_delta,
            )

        return PendingClassify(materialize)

    def classify(self, batch: PacketBatch, apply_stats: bool = True) -> ClassifyOutput:
        return self.classify_async(batch, apply_stats=apply_stats).result()

    # -- accessors / lifecycle ---------------------------------------------

    @property
    def stats(self) -> StatsAccumulator:
        return self._stats

    @property
    def tables(self) -> Optional[CompiledTables]:
        return self._tables

    @property
    def active_path(self) -> Optional[str]:
        return self._active[0] if self._active else None

    def close(self) -> None:
        """Release device references (the analogue of detaching the XDP
        program and closing the BPF objects, loader.go:306-333)."""
        with self._lock:
            self._active = None
            self._tables = None
            self._closed = True


class ArenaClassifier:
    """Multi-tenant paged-arena classifier (ISSUE-10): thousands of
    tenant rulesets resident in ONE pool per layout family, classify
    batches carrying MIXED-tenant traffic steered per packet by the
    device tenant -> page table, and tenant activation/hot-swap as a
    page-table row flip instead of a re-upload.

    Serves the packed-wire contract with a tenant column:
    ``classify_async_packed_tenant(wire_np, tenant_np)`` (and the
    ``classify_tenants`` batch convenience).  The wire rides the
    wire/narrow formats; the sub-8B codecs are per-chunk sequential
    transforms that would interleave tenants' sort orders, so the arena
    path keeps the 16-28B formats (same degrade-never-refuse posture as
    the mesh's delta fallback).

    Per-tenant edits reuse the incremental patch machinery PER SLAB
    (rules-only hints scatter exactly the dirty rows at the slab base);
    every executable is jit-cache-keyed on the POOL geometry only, so
    tenant create/swap/patch/destroy never recompiles on a warm arena
    (test-pinned)."""

    #: the syncer/registry may route structurally-new tenant keys into a
    #: per-tenant dense overlay side-pool (overlay_spec)
    supports_overlay = True
    data_shards = 1

    def __init__(
        self,
        spec: "jaxpath.ArenaSpec",
        device=None,
        overlay_spec: "Optional[jaxpath.ArenaSpec]" = None,
        interpret: Optional[bool] = None,
        fused_deep: Optional[bool] = None,
        check_invariants: Optional[bool] = None,
        flow_table=None,
        flow_track_model: bool = False,
    ) -> None:
        self._device = device if device is not None else jax.devices()[0]
        self._interpret = (
            interpret if interpret is not None
            else pallas_dense.default_interpret()
        )
        if fused_deep is None:
            env = os.environ.get("INFW_FUSED_DEEP", "")
            if env:
                fused_deep = env not in ("0", "false", "no")
        self._fused_deep = (
            bool(fused_deep) if fused_deep is not None
            else not self._interpret
        ) and spec.family == "ctrie"
        if check_invariants is None:
            env = os.environ.get("INFW_CHECK_INVARIANTS", "")
            check_invariants = env not in ("", "0", "false", "no")
        self._check_invariants = bool(check_invariants)
        self._alloc = jaxpath.ArenaAllocator(spec, self._device)
        if overlay_spec is not None and overlay_spec.family != "dense":
            raise ValueError("the overlay side-pool must be dense-family")
        self._ov_alloc = (
            jaxpath.ArenaAllocator(overlay_spec, self._device)
            if overlay_spec is not None else None
        )
        self._lock = threading.Lock()
        self._stats = StatsAccumulator()
        self._wire_counts = {}
        # per-tenant verdict accounting {tid: [packets, allow, deny]}
        self._tenant_counts = {}
        # paged Pallas walk planes, rebuilt when the node pool moves
        self._planes = None
        self._planes_gen = -1
        # Per-tenant flow slabs (infw.flow): one flow slab per ARENA
        # page, steered by the same tenant -> page mapping that steers
        # classification; the flow key embeds the tenant id, so slab
        # reuse across tenants can never serve a foreign verdict.
        if flow_table is None:
            env = os.environ.get("INFW_FLOW_TABLE", "")
            if env and env not in ("0", "false", "no"):
                flow_table = int(env)
        self._flow = None
        if flow_table is not None and flow_table is not False:
            from ..flow import FlowConfig, FlowTier

            if isinstance(flow_table, FlowConfig):
                fcfg = flow_table._replace(
                    pages=spec.pages, max_tenants=spec.max_tenants
                )
            else:
                fcfg = FlowConfig.make(
                    entries=int(flow_table), pages=spec.pages,
                    max_tenants=spec.max_tenants,
                )
            self._flow = FlowTier(fcfg, device=self._device,
                                  track_model=flow_track_model)
        self._closed = False
        if self._fused_deep:
            self._refresh_planes()

    # -- tenant lifecycle (allocator proxies + invariant hooks) -------------

    @property
    def allocator(self) -> "jaxpath.ArenaAllocator":
        return self._alloc

    @property
    def overlay_allocator(self):
        return self._ov_alloc

    @property
    def spec(self) -> "jaxpath.ArenaSpec":
        return self._alloc.spec

    def load_tenant(self, tenant: int, tables: CompiledTables,
                    hint=None) -> str:
        if self._closed:
            raise RuntimeError("classifier is closed")
        had_page = self._alloc.page_of(tenant) is not None
        rules_only = had_page and jaxpath.hint_trie_unchanged(hint)
        if not self._fused_deep or rules_only:
            # rules-only edits of a PRIVATE slab never touch the node
            # pool, so the planes need no refresh ordering; a rules-only
            # edit of a SHARED slab CoW-clones (a structural write of an
            # unreachable fresh page), which the allocator covers by
            # running pre_flip after the clone write and strictly before
            # the page-table flip — the same new-planes/old-table
            # pairing the swap path guarantees
            path = self._alloc.load_tenant(
                tenant, tables, hint=hint,
                pre_flip=self._refresh_planes if self._fused_deep else None,
            )
            self._after_mutation()
            self._flow_note(tenant)
            return path
        # fused planes live: a structural install must not let a
        # classify pair the NEW page table with stale planes — route
        # through stage (free page bake, or a content-hash HIT on an
        # already-resident page) -> plane refresh -> flip, the same
        # ordering the swap path guarantees
        try:
            page = self._alloc.stage(tables)
        except jaxpath.ArenaCapacityError:
            # no free page for staging: in-place rewrite with an
            # immediate refresh — a narrow stale window only on a full
            # pool (keep >= 1 free page when serving the fused walk)
            path = self._alloc.load_tenant(
                tenant, tables, hint=hint,
                pre_flip=self._refresh_planes,
            )
            self._after_mutation()
            self._flow_note(tenant)
            return path
        self._refresh_planes()
        self._alloc.activate(tenant, page, tables)
        self._after_mutation()
        self._flow_note(tenant)
        return "rewrite" if had_page else "assign"

    def load_tenant_overlay(self, tenant: int,
                            overlay: Optional[CompiledTables]) -> None:
        """Install/clear one tenant's dense overlay side-slab."""
        if self._ov_alloc is None:
            raise RuntimeError("arena built without an overlay side-pool")
        if overlay is None or overlay.num_entries == 0:
            if self._ov_alloc.page_of(tenant) is not None:
                self._ov_alloc.destroy_tenant(tenant)
        else:
            self._ov_alloc.load_tenant(tenant, overlay)

    def stage_tenant(self, tables: CompiledTables) -> int:
        page = self._alloc.stage(tables)
        # planes refresh at STAGE time, before any flip can land: a
        # classify that pairs new planes with the OLD page table is
        # safe (untouched pages' plane rows are unchanged; staged pages
        # are unreachable until their flip), while old-planes/new-table
        # would walk stale nodes — so the refresh must strictly precede
        # the activation
        if self._fused_deep:
            self._refresh_planes()
        return page

    def activate_tenant(self, tenant: int, page: int,
                        tables: Optional[CompiledTables] = None) -> None:
        if self._fused_deep:
            self._refresh_planes()  # cover externally-staged writes
        self._alloc.activate(tenant, page, tables)
        self._after_mutation()
        self._flow_note(tenant)

    def swap_tenant(self, tenant: int, tables: CompiledTables) -> None:
        page = self.stage_tenant(tables)
        self._alloc.activate(tenant, page, tables)
        self._after_mutation()
        self._flow_note(tenant)

    def destroy_tenant(self, tenant: int) -> None:
        self._alloc.destroy_tenant(tenant)
        if self._ov_alloc is not None and (
            self._ov_alloc.page_of(tenant) is not None
        ):
            self._ov_alloc.destroy_tenant(tenant)
        # destroy mutates the page table / free list too — the
        # invariant hook must cover it like every other boundary
        self._after_mutation()
        self._flow_note(tenant)

    def compact(self) -> int:
        if self._fused_deep:
            # slab moves flip pages one by one inside the allocator —
            # no safe plane pairing exists mid-compaction, so drop to
            # the (always-correct) XLA arena walk for its duration and
            # rebuild the planes after (compaction is rare)
            with self._lock:
                self._planes = None
        moved = self._alloc.compact()
        self._after_mutation()
        if moved and self._flow is not None:
            # slab moves re-steer every moved tenant's flow slab; the
            # pool-wide bump is the conservative invalidation
            for t in self._alloc.tenants():
                self._flow.set_page(t, self._alloc.page_of(t))
            self._flow.bump_all_generations()
        return moved

    def dedup_sweep(self, limit: Optional[int] = None) -> dict:
        """Background content re-merge (the lazy half of the CoW
        arena): re-hash stale pages and flip tenants whose slab content
        re-converged onto one shared page.  Flips only — no slab
        writes, so the fused planes need no refresh; moved tenants'
        flow slabs re-steer and invalidate like any other page move."""
        rep = self._alloc.dedup_sweep(limit)
        if rep["moved"]:
            for t in rep["moved"]:
                self._flow_note(t)
            self._after_mutation()
        return rep

    def _after_mutation(self) -> None:
        if self._fused_deep:
            self._refresh_planes()
        if self._check_invariants:
            from ..analysis import statecheck  # lazy: no import cycle

            viols = statecheck.check_arena(self._alloc)
            if viols:
                raise statecheck.InvariantViolation(
                    "arena invariant contract violated at the slab "
                    "boundary:\n  " + "\n  ".join(viols)
                )

    def _refresh_planes(self) -> None:
        """Bring the paged-walk byte planes up to the node pool: a full
        build only on first touch; afterwards ONLY the written slabs'
        plane rows re-derive and scatter (SN is 128-row aligned, so a
        slab maps 1:1 onto its plane rows) — O(slab) per mutation, not
        O(pool), keeping the hot-swap path flip-sized.  Subtree-plane
        writes (spliced arenas) patch the same way at their pool-row
        bases: O(touched subtrees), never a pool rebuild."""
        gen, pages, rows = self._alloc.consume_dirty_node_pages()
        pblocks = self._alloc.consume_dirty_plane_rows()[1] if hasattr(
            self._alloc, "consume_dirty_plane_rows") else []
        with self._lock:
            if gen == self._planes_gen and self._planes is not None:
                return
            planes = self._planes
            if planes is None or (not pages and not pblocks):
                nodes = self._alloc.host_nodes()
                planes = (
                    None if nodes is None
                    else pallas_walk.build_arena_cwalk_planes(
                        nodes, device=self._device
                    )
                )
            else:
                sn = self._alloc.spec.node_rows
                patches = [
                    (p * sn, rows[p][:sn]) for p in pages
                ] + [(b, blk) for b, blk in pblocks]
                for base, blk in patches:
                    nr = blk.shape[0]
                    slab_planes = pallas_walk._split_cnode_rows(blk)
                    patched = jaxpath._capped_scatter(
                        planes,
                        base + np.arange(nr, dtype=np.int64),
                        slab_planes[:nr],
                        self._device,
                    )
                    if patched is None:  # oversized delta: full rebuild
                        nodes = self._alloc.host_nodes()
                        patched = pallas_walk.build_arena_cwalk_planes(
                            nodes, device=self._device
                        )
                        planes = patched
                        break
                    planes = patched
            self._planes = planes
            self._planes_gen = gen

    def _flow_note(self, tenant: int) -> None:
        """Per-tenant flow bookkeeping after a lifecycle mutation:
        re-steer the tenant's flow slab to its (possibly new) arena
        page and invalidate its resident flow verdicts."""
        if self._flow is None:
            return
        page = self._alloc.page_of(tenant)
        self._flow.set_page(tenant, -1 if page is None else page)
        self._flow.bump_generation(tenant)

    @property
    def flow(self):
        return self._flow

    def flow_counters(self):
        return {} if self._flow is None else self._flow.counter_values()

    def flow_age_tick(self, horizon=None) -> int:
        return 0 if self._flow is None else self._flow.age(horizon)

    def warm_flow_ladder(self, ladder) -> int:
        return 0 if self._flow is None else self._flow.warm(ladder)

    # -- classify ------------------------------------------------------------

    def tenant_ids(self):
        return self._alloc.tenants()

    def classify_async_packed_tenant(
        self, wire_np: np.ndarray, tenant_np: np.ndarray,
        apply_stats: bool = True, tcp_flags: Optional[np.ndarray] = None,
    ) -> PendingClassify:
        """The mixed-tenant packed-wire dispatch: one batch, each
        packet steered to its tenant's slab in-kernel.  ``tenant_np``
        is (B,) int — ids outside the registry classify to UNDEF.
        With the flow tier enabled, established flows serve from their
        tenant's flow slab and only misses walk the arena."""
        if self._flow is not None and wire_np.shape[1] in (4, 7):
            return self._classify_flow_tenant(
                wire_np, tenant_np, apply_stats, tcp_flags
            )
        return self._classify_stateless_tenant(
            wire_np, tenant_np, apply_stats
        )

    def _classify_flow_tenant(
        self, wire_np, tenant_np, apply_stats, tcp_flags
    ) -> PendingClassify:
        from .. import flow as flow_mod

        if self._closed:
            raise RuntimeError("classifier is closed")
        tier = self._flow
        n = wire_np.shape[0]
        kind = (wire_np[:, 0] & 3).astype(np.int32)
        tenant_np = np.ascontiguousarray(tenant_np, np.int32)
        fused, ctx = tier.probe(wire_np, tenant_np=tenant_np,
                                tflags_np=tcp_flags)
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass

        def materialize() -> ClassifyOutput:
            from ..daemon import stats_from_results  # lazy: no import cycle

            res16, hitmask, hits, stale = jaxpath.split_flow_probe_outputs(
                np.asarray(fused), n
            )
            tier.stats.add(hits=hits, misses=n - hits,
                           stale_rejects=stale)
            res16 = res16.copy()
            pl = (
                ((wire_np[:, 1] >> 16) & 0xFFFF)
                | ((wire_np[:, 0] >> 27) << 16)
            ).astype(np.int64)
            stats_delta = stats_from_results(res16.astype(np.uint32), pl)
            miss = np.nonzero(~hitmask)[0]
            if len(miss):
                m = len(miss)
                bucket = flow_mod.flow_miss_bucket(m)
                miss_wire = wire_np[miss]
                miss_tenant = tenant_np[miss]
                if bucket > m:
                    pad = np.zeros(
                        (bucket - m, miss_wire.shape[1]), np.uint32
                    )
                    pad[:, 0] = 3
                    miss_wire = np.concatenate([miss_wire, pad])
                    miss_tenant = np.concatenate(
                        [miss_tenant, np.full(bucket - m, -1, np.int32)]
                    )
                out = self._classify_stateless_tenant(
                    miss_wire, miss_tenant, apply_stats=False,
                    note_tenants=False,
                ).result()
                res16[miss] = (out.results[:m] & 0xFFFF).astype(np.uint16)
                stats_delta += out.stats_delta
                verdicts = np.zeros(miss_wire.shape[0], np.uint32)
                verdicts[:m] = out.results[:m] & 0xFFFF
                mflags = None
                if tcp_flags is not None:
                    mflags = np.zeros(miss_wire.shape[0], np.int32)
                    mflags[:m] = np.asarray(tcp_flags, np.int32)[miss]
                tier.insert(ctx, miss_wire, verdicts,
                            tenant_np=miss_tenant, tflags_np=mflags)
            if apply_stats:
                self._stats.add(stats_delta)
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            self._note_tenants(tenant_np, results)
            return ClassifyOutput(
                results=results, xdp=xdp, stats_delta=stats_delta
            )

        return PendingClassify(materialize)

    def _classify_stateless_tenant(
        self, wire_np: np.ndarray, tenant_np: np.ndarray,
        apply_stats: bool = True, note_tenants: bool = True,
    ) -> PendingClassify:
        """The stateless arena dispatch (the pre-flow classify path and
        the flow tier's miss fall-through)."""
        if self._closed:
            raise RuntimeError("classifier is closed")
        spec = self._alloc.spec
        n = wire_np.shape[0]
        kind = (wire_np[:, 0] & 3).astype(np.int32)
        if wire_np.shape[1] in (4, 7):
            nw = narrow_wire(wire_np)
            if nw is not None:
                wire_np = nw
        put = lambda a: jax.device_put(a, self._device)
        wire = put(wire_np)
        tenant = put(np.ascontiguousarray(tenant_np, np.int32))
        self._note_wire(f"wire{wire_np.shape[1]}", n, wire_np.nbytes)
        # read order matters for the fused path: the ARENA snapshot
        # first, planes after — planes refresh strictly BEFORE flips
        # (stage_tenant), so planes are always at least as new as the
        # page table we pair them with (new-planes/old-table is safe;
        # the reverse would walk stale nodes)
        arena = self._alloc.arena
        ov = None if self._ov_alloc is None else self._ov_alloc.arena
        ov_busy = ov is not None and self._ov_alloc.tenants()
        d_max = spec.d_max if spec.family == "ctrie" else 0
        # spliced arenas key the (cached) factories on the spec so the
        # entry stage resolves splice rows; unspliced callers keep the
        # legacy cache identity by not passing the kwarg at all
        sp = {"spec": spec} if getattr(spec, "spliced", False) else {}
        if (
            self._fused_deep and self._planes is not None and not ov_busy
        ):
            fused = pallas_walk.jitted_classify_arena_cwalk_wire_fused(
                spec.pages, d_max, self._interpret, **sp
            )(arena, self._planes, wire, tenant)
        elif ov_busy:
            fused = jaxpath.jitted_classify_arena_wire_fused(
                spec.family, spec.pages, d_max, self._ov_alloc.spec.pages,
                **sp
            )(arena, ov, wire, tenant)
        else:
            fused = jaxpath.jitted_classify_arena_wire_fused(
                spec.family, spec.pages, d_max, **sp
            )(arena, wire, tenant)
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass

        def materialize() -> ClassifyOutput:
            res16, stats = jaxpath.split_wire_outputs(np.asarray(fused), n)
            stats_delta = jaxpath.merge_stats_host(stats)
            if apply_stats:
                self._stats.add(stats_delta)
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            if note_tenants:
                self._note_tenants(tenant_np, results)
            return ClassifyOutput(
                results=results, xdp=xdp, stats_delta=stats_delta
            )

        return PendingClassify(materialize)

    def classify_tenants(
        self, batch: PacketBatch, tenant_np: np.ndarray,
        apply_stats: bool = True,
    ) -> ClassifyOutput:
        """Batch-object convenience over the packed-tenant dispatch."""
        return self.classify_async_packed_tenant(
            batch.pack_wire(), tenant_np, apply_stats=apply_stats
        ).result()

    def _note_wire(self, fmt: str, n: int, nbytes: int) -> None:
        with self._lock:
            c = self._wire_counts.setdefault(fmt, [0, 0])
            c[0] += n
            c[1] += nbytes

    def wire_stats(self):
        with self._lock:
            return {k: tuple(v) for k, v in self._wire_counts.items()}

    def _note_tenants(self, tenant_np, results) -> None:
        """Per-tenant packets/allow/deny accounting (the tenant_*
        observability satellite): three vectorized bincount passes over
        the batch — this runs on every classify materialize, so a
        per-tenant Python loop would serialize O(tenants x B) work
        under the lock at exactly the mixed-batch scale the arena
        serves."""
        t = np.asarray(tenant_np, np.int64)
        ok = (t >= 0) & (t < self._alloc.spec.max_tenants)
        t = t[ok]
        if len(t) == 0:
            return
        act = (np.asarray(results)[ok]) & 0xFF
        n = int(t.max()) + 1
        pkts = np.bincount(t, minlength=n)
        allow = np.bincount(t[act == ALLOW], minlength=n)
        deny = np.bincount(t[act == DENY], minlength=n)
        with self._lock:
            for tid in np.nonzero(pkts)[0]:
                c = self._tenant_counts.setdefault(int(tid), [0, 0, 0])
                c[0] += int(pkts[tid])
                c[1] += int(allow[tid])
                c[2] += int(deny[tid])

    def tenant_counters(self) -> dict:
        """tenant_* counters for /metrics: allocator slab/swap gauges
        plus per-tenant packet/verdict totals."""
        out = dict(self._alloc.counter_values())
        if self._ov_alloc is not None:
            for k, v in self._ov_alloc.counter_values().items():
                out[f"{k}_overlay"] = v
        with self._lock:
            for tid, (pk, al, dn) in sorted(self._tenant_counts.items()):
                out[f"tenant_{tid}_packets_total"] = pk
                out[f"tenant_{tid}_allow_total"] = al
                out[f"tenant_{tid}_deny_total"] = dn
        return out

    # -- accessors / lifecycle ----------------------------------------------

    @property
    def stats(self) -> StatsAccumulator:
        return self._stats

    def close(self) -> None:
        self._closed = True
