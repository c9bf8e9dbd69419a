"""Multi-chip TPU classifier backend: the mesh serving path.

``MeshTpuClassifier`` serves the same ``classify_async`` /
``prepare_packed`` / ``classify_prepared`` contract as the single-chip
``TpuClassifier``, so the daemon's double-buffered delta-wire ingest and
depth-class steering work unchanged — but the dataplane spans a
``("data", "rules")`` device mesh (parallel.mesh):

- **data axis** (the per-CPU XDP lanes of the reference, PAPER.md §2):
  the packed wire is sharded over "data" at prepare time, so the H2D
  staging of the next chunk runs per chip while the current chunk's
  classify executes; per-shard statistics are combined on device with
  ONE psum and the host reads a single merged ``stats_delta`` instead of
  N per-chip copies.
- **rules axis** (tensor parallelism over targets, the hXDP
  parallel-lane analogue): with ``rules_shards > 1`` the rule table is
  partitioned across chips — dense tables target-sharded, trie tables as
  per-shard tries — and the global longest-prefix winner is selected
  with pmax over match scores.

Kernel parity with the single chip: the replicated configurations
(``rules_shards == 1``) run the SAME kernels under shard_map — the int8
Pallas dense kernel, the XLA trie walk with v4/depth truncation, the
fused Pallas deep walk for the full-depth steering class, and the
replicated overlay combine.  Rule loading on those configurations keeps
the single-chip incremental contract: a 1-key rules edit diff-scatter
patches the mesh-resident arrays (the small patch rows broadcast to
every chip — kilobytes), and a structural CIDR add ships as the
broadcast overlay side-table, the main trie untouched.  A folded edit
TRANSACTION (infw.txn) rides the same machinery: the replicated
NamedSharding stands in for the device in the fused transaction scatter
(jaxpath.txn_scatter), so one flush broadcasts its merged dirty-row
payload to every chip in one staging pass + one launch — the
update-storm path needs no mesh-specific code.  The rules-sharded
configurations re-place per load as always, so a transaction flush
against them costs one re-place, not a broadcast patch.

The rules-sharded configurations rebuild their per-shard partition on
every load (the round-robin entry split renumbers shard membership on
any structural edit) and refuse overlays — the syncer merges into the
main table instead, exactly as it does for the single-chip paths that
cannot honor one.

Wire formats on the mesh: wire / narrow / wire8.  The delta+varint codec
is per-chunk sequential (one varint stream + one inverse permutation per
encode) and does not shard along the data axis, so ``--wire-codec
delta``/``auto`` degrades per chunk down the familiar
delta -> wire8 -> narrow -> full chain starting at wire8 — never
refuses, same contract as an ineligible chunk on one chip.
"""
from __future__ import annotations

import logging
import re
from typing import Optional, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compiler import CompiledTables
from ..constants import KIND_OTHER
from ..kernels import jaxpath
from ..packets import PacketBatch, narrow_wire, wire8
from ..parallel import mesh as meshmod
from .base import ClassifyOutput, PendingClassify, StatsAccumulator
from .tpu import TpuClassifier

log = logging.getLogger("infw.backend.mesh")


def parse_mesh_spec(spec: str) -> Tuple[int, int]:
    """"DATAxRULES" (e.g. "4x2") or a bare device count "8" (rules=1)
    -> (data_shards, rules_shards).  Raises ValueError on junk — the
    daemon CLI surfaces this at launch, not inside the sync loop."""
    m = re.fullmatch(r"\s*(\d+)\s*(?:[xX]\s*(\d+))?\s*", spec or "")
    if not m:
        raise ValueError(
            f"bad mesh spec {spec!r} (expected DATAxRULES, e.g. 4x2, or a "
            "device count)"
        )
    data = int(m.group(1))
    rules = int(m.group(2)) if m.group(2) else 1
    if data < 1 or rules < 1:
        raise ValueError(f"mesh axes must be positive, got {spec!r}")
    return data, rules


def resolve_mesh_spec(spec: str) -> Optional[Mesh]:
    """Build the serving mesh for a --mesh/INFW_MESH spec, or None when
    the daemon should FALL BACK to the single-chip classifier: a 1x1
    spec, or a device pool too small for the requested shape (logged —
    a daemon scheduled onto a single-chip node with a fleet-wide mesh
    setting must come up serving, not crash-loop)."""
    data, rules = parse_mesh_spec(spec)
    if data * rules <= 1:
        return None
    n_avail = len(jax.devices())
    if data * rules > n_avail:
        log.warning(
            "mesh %dx%d needs %d devices but only %d visible; "
            "falling back to the single-chip classifier",
            data, rules, data * rules, n_avail,
        )
        return None
    return meshmod.make_mesh(data * rules, rules_shards=rules)


class MeshTpuClassifier(TpuClassifier):
    """Multi-chip device classifier on a ("data", "rules") mesh.

    With ``rules_shards == 1`` (the default, pure data parallelism) all
    table state is REPLICATED on the mesh — placement, incremental
    patching, overlay broadcast and the fused-walk build all reuse the
    single-chip machinery verbatim, with the replicated NamedSharding
    standing in for the single device — and only the dispatch differs:
    the wire shards over "data" and runs under shard_map with a device-
    side stats psum.  With ``rules_shards > 1`` the table itself is
    partitioned (see module docstring)."""

    def __init__(
        self,
        mesh: Optional[Mesh] = None,
        data_shards: Optional[int] = None,
        rules_shards: int = 1,
        **kw,
    ) -> None:
        if mesh is None:
            n_avail = len(jax.devices())
            data = data_shards or max(n_avail // max(rules_shards, 1), 1)
            mesh = meshmod.make_mesh(
                data * rules_shards, rules_shards=rules_shards
            )
        self._mesh = mesh
        self._data_shards = mesh.shape["data"]
        self._rules_shards = mesh.shape["rules"]
        self._replicated = NamedSharding(mesh, P())
        self._data_sharding = NamedSharding(mesh, P("data", None))
        # The replicated sharding IS the placement: every device_put /
        # scatter-patch / walk-build in the single-chip machinery takes a
        # jax.device_put target, and a NamedSharding broadcasts where a
        # Device pins.
        super().__init__(device=self._replicated, **kw)
        #: the overlay side-table broadcasts in kilobytes on the
        #: replicated configs; the rules-sharded partition cannot honor
        #: one (the syncer merges instead)
        self.supports_overlay = self._rules_shards == 1

    def _make_flow_tier(self, cfg, track_model: bool = False):
        """Place the flow-tier columns by the declared partition rules
        (parallel.mesh.FLOW_PARTITION_RULES): flow slots shard over
        "rules" when the capacity divides the axis, the steering state
        replicates, and the probe/insert dispatches run under the same
        jitted factories as the single chip — GSPMD, no mesh-specific
        flow kernel."""
        from ..flow import FlowTier

        return FlowTier(
            cfg, device=self._replicated,
            shardings=meshmod.flow_shardings(self._mesh, cfg.capacity),
            track_model=track_model,
        )

    @property
    def mesh(self) -> Mesh:
        return self._mesh

    @property
    def data_shards(self) -> int:
        """Width of the "data" axis one dispatched batch spreads over —
        the scheduler's per-chip admission budget multiplies by this
        (a spilled batch costs each chip only batch/data_shards rows)."""
        return self._data_shards

    # -- rule loading -------------------------------------------------------

    def load_tables(self, tables: CompiledTables, dirty_hint=None,
                    overlay: Optional[CompiledTables] = None) -> None:
        if self._rules_shards == 1:
            # Replicated tables: the whole single-chip load path — dense
            # Pallas build, diff-scatter patch, overlay cache, fused-walk
            # build/patch, depth steering — runs against the mesh via the
            # replicated placement.  A 1-key edit ships the same
            # kilobytes as on one chip, broadcast.
            return super().load_tables(
                tables, dirty_hint=dirty_hint, overlay=overlay
            )
        if self._closed:
            raise RuntimeError("classifier is closed")
        if overlay is not None and overlay.num_entries > 0:
            raise ValueError(
                f"overlay not supported on the rules-sharded mesh "
                f"(rules_shards={self._rules_shards}); merge it into the "
                "main table"
            )
        path = self._force_path or (
            "dense" if tables.num_entries <= self._dense_limit else "trie"
        )
        wide_rids = False
        try:
            jaxpath.check_wire_ruleids(tables)
        except ValueError:
            wide_rids = True
        steer_parts = None
        if path == "dense":
            dev = meshmod.shard_tables(tables, self._mesh)
        else:
            # Per-shard tries are a PARTITION of the entry set: any
            # structural change renumbers the round-robin split, so the
            # sharded configuration re-places on every load (the
            # incremental patch story belongs to the replicated config).
            dev = meshmod.shard_tables_trie(tables, self._mesh)
            lut = jaxpath.build_depth_lut(tables)
            classes = jaxpath.tune_depth_classes(tables)
            steer_parts = (
                np.asarray(tables.root_lut, np.int64), lut, classes,
            )
        if self._check_invariants:
            # The sharded partitions are NOT the bucket-padded patch
            # layout (they re-place on every load), so the deep
            # DeviceTables contract doesn't apply; run the minimal
            # sharded consistency pass instead.  The replicated config
            # inherits the full check via super().load_tables.
            from ..analysis import statecheck  # lazy: no import cycle

            viols = statecheck.check_sharded_tables(dev)
            if viols:
                raise statecheck.InvariantViolation(
                    "sharded-table invariant contract violated:\n  "
                    + "\n  ".join(viols)
                )
        with self._lock:
            self._tables = tables
            self._active = (path, dev, None, wide_rids, None, None)
            self._walk_meta = None
            self._last_load = ("full", tables.num_entries)
            self._depth_gen += 1
            self._depth_steer = (
                steer_parts + (self._depth_gen,)
                if steer_parts is not None else None
            )
        if self._flow is not None:
            # the sharded load path bypasses super().load_tables — the
            # flow invalidation chokepoint must still fire here
            self._flow.bump_generation(0)

    # -- dispatch -----------------------------------------------------------

    def _mesh_pad_rows(self, arr: np.ndarray) -> np.ndarray:
        """Pad wire rows to a multiple of 2*data_shards: equal shard
        sizes for the "data" split, EVEN rows per shard so the u16 pair
        packing of the fused output never straddles a shard boundary.
        Pad rows are KIND_OTHER — always PASS, never counted — and the
        host slices them off at materialize."""
        n = arr.shape[0]
        m = 2 * self._data_shards
        npad = (-n) % m
        if npad == 0 and n > 0:
            return arr
        rows = np.zeros((max(npad, m if n == 0 else npad), arr.shape[1]),
                        arr.dtype)
        rows[:, 0] = KIND_OTHER
        return np.concatenate([arr, rows])

    def _plan_wire(
        self, path, dev, block_b, wire_np, v4_only, kind,
        ov_dev=None, depth=None, walk_dev=None,
    ):
        """Mesh format choice + per-shard H2D staging: the chosen payload
        is placed with the "data" sharding, which starts one async copy
        per chip — the staged-plan transfer overlaps whatever every chip
        is running (the double-buffer contract, now per shard)."""
        n = wire_np.shape[0]
        plan = {
            "path": path, "dev": dev, "block_b": block_b, "ov_dev": ov_dev,
            "depth": depth, "walk_dev": walk_dev, "v4_only": v4_only,
            "kind": kind, "n": n,
        }
        put_data = lambda a: jax.device_put(a, self._data_sharding)
        replicated_trie = path == "trie" and self._rules_shards == 1
        if (
            replicated_trie and wire_np.shape[1] == 4 and n
            and self._wire_codec in ("auto", "wire8", "delta")
        ):
            # wire8 is the mesh's compressed format: 8 B/packet, a fixed
            # per-row layout that shards cleanly over "data" (the delta
            # stream is sequential per chunk and does not — see module
            # docstring), with the ifindex dictionary replicated.
            w8 = wire8(wire_np)
            if w8 is not None:
                wire8_np, ifmap = w8
                wire8_np = self._mesh_pad_rows(wire8_np)
                plan.update(
                    fmt="wire8", pkt_len=self._wire4_pkt_len(wire_np),
                    wire=put_data(wire8_np),
                    ifmap=jax.device_put(ifmap, self._replicated),
                )
                self._note_wire("wire8", n, wire8_np.nbytes + ifmap.nbytes)
                return plan
        if wire_np.shape[1] in (4, 7):
            nw = narrow_wire(wire_np)
            if nw is not None:
                wire_np = nw
        wire_np = self._mesh_pad_rows(wire_np)
        plan.update(fmt="wire", wire=put_data(wire_np))
        self._note_wire(f"wire{wire_np.shape[1]}", n, wire_np.nbytes)
        return plan

    def _launch_wire(self, plan, apply_stats: bool) -> PendingClassify:
        if plan["fmt"] == "wire8":
            return self._launch_wire8(plan, apply_stats)
        path, dev, block_b = plan["path"], plan["dev"], plan["block_b"]
        ov_dev, depth, walk_dev = (
            plan["ov_dev"], plan["depth"], plan["walk_dev"]
        )
        v4_only, kind, n = plan["v4_only"], plan["kind"], plan["n"]
        wire = plan["wire"]
        mesh = self._mesh
        if path == "dense":
            if self._rules_shards > 1:
                fn = meshmod.jitted_mesh_wire(mesh, "dense-sharded", dev)
            else:
                self._note_pallas("pallas-dense", n)
                fn = meshmod.jitted_mesh_wire(
                    mesh, "pallas-dense", dev,
                    interpret=self._interpret, block_b=block_b,
                )
            fused = fn(dev, wire)
        elif walk_dev is not None and ov_dev is None:
            # Fused Pallas deep walk per shard — same kernel, same
            # overlay exclusion, as the single-chip dispatch.
            self._note_pallas("pallas-walk", n)
            fn = meshmod.jitted_mesh_wire(
                mesh, "walk", walk_dev, interpret=self._interpret
            )
            fused = fn(walk_dev, wire)
        elif ov_dev is not None:
            fn = meshmod.jitted_mesh_wire(
                mesh, "trie-overlay", dev, v4_only=v4_only, depth=depth,
                overlay=ov_dev,
            )
            fused = fn(dev, ov_dev, wire)
        elif self._rules_shards > 1:
            fn = meshmod.jitted_mesh_wire(
                mesh, "trie-sharded", dev, v4_only=v4_only, depth=depth
            )
            fused = fn(dev, wire)
        else:
            fn = meshmod.jitted_mesh_wire(
                mesh, "trie", dev, v4_only=v4_only, depth=depth
            )
            fused = fn(dev, wire)
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        data_shards = self._data_shards

        def materialize() -> ClassifyOutput:
            res16, stats = meshmod.split_fused_wire_outputs(
                np.asarray(fused), n, data_shards
            )
            stats_delta = jaxpath.merge_stats_host(stats)
            if apply_stats:
                self._stats.add(stats_delta)
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            return ClassifyOutput(
                results=results, xdp=xdp, stats_delta=stats_delta
            )

        return PendingClassify(materialize)

    def _launch_wire8(self, plan, apply_stats: bool) -> PendingClassify:
        dev, ov_dev = plan["dev"], plan["ov_dev"]
        kind, n, pkt_len = plan["kind"], plan["n"], plan["pkt_len"]
        fn = meshmod.jitted_mesh_wire8(self._mesh, dev, overlay=ov_dev)
        if ov_dev is not None:
            fused = fn(dev, ov_dev, plan["wire"], plan["ifmap"])
        else:
            fused = fn(dev, plan["wire"], plan["ifmap"])
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass
        data_shards = self._data_shards

        def materialize() -> ClassifyOutput:
            from ..daemon import stats_from_results  # lazy: no import cycle

            res16, _ = meshmod.split_fused_wire_outputs(
                np.asarray(fused), n, data_shards, with_stats=False
            )
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
        """u32 results path for wide-ruleId tables, on the mesh: the
        DeviceBatch shards over "data", results come back 4B/packet."""
        n = len(batch)
        bp = -(-max(n, 1) // self._data_shards) * self._data_shards
        db = meshmod.shard_batch(batch.pad_to(bp), self._mesh)
        if self._rules_shards > 1:
            # dev is ShardedTrieTables (trie) or mesh DeviceTables (dense)
            if isinstance(dev, meshmod.ShardedTrieTables):
                fn = meshmod.make_sharded_trie_classifier(
                    self._mesh, len(dev.trie_levels)
                )
            else:
                fn = meshmod.make_sharded_classifier(
                    self._mesh, len(dev.trie_levels)
                )
        else:
            fn = meshmod.jitted_mesh_classify(self._mesh, "trie", dev)
        res, xdp, stats = fn(dev, db)
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
                results=np.asarray(res)[:n], xdp=np.asarray(xdp)[:n],
                stats_delta=stats_delta,
            )

        return PendingClassify(materialize)


class MeshArenaClassifier:
    """Multi-tenant paged arena spanning a ("data", "rules") mesh: the
    slab pools are placed ONCE with the per-family partition rules
    (parallel.mesh.ARENA_PARTITION_RULES — pages in whole-slab blocks
    over "rules", page table replicated), tenant lifecycle mutations
    broadcast through the replicated scatter path, and mixed-tenant
    wire batches shard over "data".  Dispatch reuses the SAME jitted
    arena classify factories as the single chip — the pool placement
    engages GSPMD, so there is no mesh-specific kernel to keep in
    parity."""

    supports_overlay = False  # per-tenant overlays: single-chip only v1
    data_shards = 1

    def __init__(self, spec, mesh=None, data_shards=None,
                 rules_shards: int = 1, interpret: bool = True) -> None:
        from ..kernels import jaxpath as _jp

        if mesh is None:
            n = (data_shards or 2) * rules_shards
            mesh = meshmod.make_mesh(n, rules_shards=rules_shards)
        self._mesh = mesh
        self.data_shards = mesh.shape["data"]
        self._interpret = interpret
        self._alloc = _jp.ArenaAllocator(
            spec,
            device=meshmod.arena_replicated(mesh),
            shardings=meshmod.arena_shardings(
                mesh, spec.family, spec.pages,
                spliced=getattr(spec, "spliced", False),
            ),
        )
        self._stats = StatsAccumulator()
        self._closed = False

    @property
    def allocator(self):
        return self._alloc

    @property
    def spec(self):
        return self._alloc.spec

    def load_tenant(self, tenant: int, tables: CompiledTables,
                    hint=None) -> str:
        return self._alloc.load_tenant(tenant, tables, hint=hint)

    def stage_tenant(self, tables: CompiledTables) -> int:
        """Content-addressed staging (hash hit = an already-resident
        shared page, no bake); lifecycle scatters broadcast replicated
        exactly like the single-chip path."""
        return self._alloc.stage(tables)

    def activate_tenant(self, tenant: int, page: int,
                        tables=None) -> None:
        self._alloc.activate(tenant, page, tables)

    def swap_tenant(self, tenant: int, tables: CompiledTables) -> None:
        self._alloc.swap_tenant(tenant, tables)

    def destroy_tenant(self, tenant: int) -> None:
        self._alloc.destroy_tenant(tenant)

    def compact(self) -> int:
        return self._alloc.compact()

    def dedup_sweep(self, limit=None) -> dict:
        """Background content re-merge: page-table row flips broadcast
        through the replicated scatter path — shared pages stay placed
        by the SAME whole-slab partition rules as private ones (a
        refcount is host bookkeeping; GSPMD never sees it)."""
        return self._alloc.dedup_sweep(limit)

    def tenant_counters(self) -> dict:
        return self._alloc.counter_values()

    def classify_async_packed_tenant(
        self, wire_np: np.ndarray, tenant_np: np.ndarray,
        apply_stats: bool = True,
    ) -> PendingClassify:
        """Mixed-tenant mesh dispatch: wire + tenant column sharded
        over "data" (padded to a whole number of shard rows with
        dead lanes), pools as placed — one fused output buffer."""
        if self._closed:
            raise RuntimeError("classifier is closed")
        spec = self._alloc.spec
        n = wire_np.shape[0]
        kind = (wire_np[:, 0] & 3).astype(np.int32)
        data = self.data_shards
        # pad to 2*data rows so the u16 result-pair packing never
        # straddles shards (the MeshTpuClassifier contract)
        pad = (-n) % (2 * data)
        if pad:
            wire_np = np.concatenate(
                [wire_np,
                 np.full((pad, wire_np.shape[1]), KIND_OTHER, np.uint32)],
                axis=0,
            )
            tenant_np = np.concatenate(
                [tenant_np, np.full(pad, -1, tenant_np.dtype)]
            )
        ds = meshmod.arena_data_sharding(self._mesh)
        wire = jax.device_put(wire_np, ds)
        tenant = jax.device_put(
            np.ascontiguousarray(tenant_np, np.int32),
            NamedSharding(self._mesh, P("data")),
        )
        d_max = spec.d_max if spec.family == "ctrie" else 0
        sp = {"spec": spec} if getattr(spec, "spliced", False) else {}
        fused = jaxpath.jitted_classify_arena_wire_fused(
            spec.family, spec.pages, d_max, **sp
        )(self._alloc.arena, wire, tenant)
        try:
            fused.copy_to_host_async()
        except (AttributeError, RuntimeError):
            pass

        def materialize() -> ClassifyOutput:
            res16, stats = jaxpath.split_wire_outputs(
                np.asarray(fused), n + pad
            )
            res16 = res16[:n]
            stats_delta = jaxpath.merge_stats_host(stats)
            if apply_stats:
                self._stats.add(stats_delta)
            results, xdp = jaxpath.host_finalize_wire(res16, kind)
            return ClassifyOutput(
                results=results, xdp=xdp, stats_delta=stats_delta
            )

        return PendingClassify(materialize)

    def classify_tenants(self, batch: PacketBatch, tenant_np: np.ndarray,
                         apply_stats: bool = True) -> ClassifyOutput:
        return self.classify_async_packed_tenant(
            batch.pack_wire(), tenant_np, apply_stats=apply_stats
        ).result()

    @property
    def stats(self) -> StatsAccumulator:
        return self._stats

    def close(self) -> None:
        self._closed = True


class DeviceStripe:
    """Per-device pipeline striping (ISSUE-16): ``width`` single-chip
    ``TpuClassifier`` instances, each PINNED to one device of the pool,
    each running its own donated resident pipeline — and optionally each
    fed by its own shared-memory ingest ring.  Where
    ``MeshTpuClassifier`` shards ONE dispatch over the ("data","rules")
    mesh (scale a single admission), a stripe scales ADMISSION
    THROUGHPUT: the scheduler round-robins whole admissions across the
    stripe (``ContinuousScheduler(stripe=...)``), so k chips run k
    independent overlapped epoch chains — per-device flow state, no
    cross-chip synchronization on the serving path.

    The two compose with the deployment: stripe across chips when flows
    hash-partition cleanly at the NIC edge (per-device flow tables are
    disjoint by construction), mesh-shard when one admission must span
    the pool.
    """

    def __init__(self, devices=None, width: Optional[int] = None,
                 ring_dir: Optional[str] = None,
                 ring_slots: int = 16, ring_slot_packets: int = 4096,
                 **clf_kw) -> None:
        devices = list(jax.devices() if devices is None else devices)
        if width is not None:
            if width > len(devices):
                raise ValueError(
                    f"stripe width {width} exceeds the {len(devices)}-"
                    "device pool"
                )
            devices = devices[:width]
        if not devices:
            raise ValueError("empty device stripe")
        self.classifiers = [
            TpuClassifier(device=d, **clf_kw) for d in devices
        ]
        #: per-device ingest rings (ring_dir/stripe<i>.ring) — one SPSC
        #: ring per chip, so producers hash-partition flows at the edge
        #: and each chip's pipeline drains its own ring cursor
        self.rings = []
        if ring_dir is not None:
            import os as _os

            from ..ring import IngestRing

            for i in range(len(self.classifiers)):
                self.rings.append(IngestRing.create(
                    _os.path.join(ring_dir, f"stripe{i}.ring"),
                    slots=ring_slots, slot_packets=ring_slot_packets,
                ))
        self._inflight = [[] for _ in self.classifiers]
        self._rr = 0

    @property
    def width(self) -> int:
        return len(self.classifiers)

    def next_classifier(self):
        """Round-robin admission target (the scheduler's stripe hook)."""
        clf = self.classifiers[self._rr % len(self.classifiers)]
        self._rr += 1
        return clf

    def load_tables(self, tables, **kw) -> None:
        for clf in self.classifiers:
            clf.load_tables(tables, **kw)

    def mark_resident_warm(self) -> None:
        for clf in self.classifiers:
            if getattr(clf, "resident", None) is not None:
                clf.mark_resident_warm()

    def drain_rings_once(self, budget_per_device: int = 1 << 30) -> int:
        """Pop committed records from every device's ring and dispatch
        each on its OWN classifier, holding up to PIPELINE_SLOTS
        admissions in flight per device before materializing (the same
        overlap discipline as the daemon's single-ring ingest); slots
        release in pop order.  Returns packets processed."""
        from ..resident import ResidentPool

        processed = 0
        for i, (clf, ring) in enumerate(zip(self.classifiers, self.rings)):
            infl = self._inflight[i]
            done = 0
            while done < budget_per_device:
                chunk = ring.pop(timeout=0.0)
                if chunk is None:
                    break
                plan = clf.prepare_packed(
                    chunk.wire, chunk.v4_only, tcp_flags=chunk.tcp_flags,
                )
                pending = clf.classify_prepared(plan, apply_stats=True)
                infl.append((chunk, pending))
                done += chunk.wire.shape[0]
                while len(infl) > ResidentPool.PIPELINE_SLOTS:
                    c, p = infl.pop(0)
                    p.result()
                    c.release()
            processed += done
        for infl in self._inflight:
            while infl:
                c, p = infl.pop(0)
                p.result()
                c.release()
        return processed

    def counter_values(self) -> dict:
        """Aggregated stripe gauges: per-device resident/ring counters
        summed, plus the stripe width."""
        out: dict = {"stripe_width": len(self.classifiers)}
        for clf in self.classifiers:
            for k, v in clf.resident_counters().items():
                out[k] = out.get(k, 0) + v
        for ring in self.rings:
            for k, v in ring.counter_values().items():
                out[k] = out.get(k, 0) + v
        return out

    def close(self) -> None:
        for ring in self.rings:
            ring.close()
        for clf in self.classifiers:
            clf.close()
