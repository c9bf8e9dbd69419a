"""Classification kernels.

- jaxpath: pure JAX/XLA implementations (dense compare-all LPM for
  reference-capacity tables, multibit-trie walk for 100K+ entries).
- pallas_dense: fused Pallas TPU kernel for the dense path (MXU bit-matmul
  LPM + one-hot rule gather + scan + stats).
- pallas_walk: fused Pallas deep-walk kernel for the full-depth v6
  steering class (VMEM-resident extracted deep tail).
- wire_decode: on-device decode of the delta+varint compressed wire
  (parallel XLA varint decode; Pallas prefix-scan for fixed-stride
  plans).

This package also hosts the ENTRYPOINT REGISTRY for the static hot-path
auditor (infw.analysis.jaxcheck / ``tools/infw_lint.py jax``): every
jitted function the production dispatch can launch (classify, wire
decode, fused walk) is enumerated by ``kernel_entrypoints()`` with a
builder that produces the jitted callable plus canonical arguments at a
requested batch size, so the auditor can capture jaxprs on the bench
shape ladder without a device.  New hot-path entrypoints belong here —
an unregistered entrypoint is invisible to ``make static-check``.
"""
from __future__ import annotations

import functools
from typing import Callable, List, NamedTuple, Tuple

import numpy as np


class EntrypointUnavailable(RuntimeError):
    """The entrypoint cannot be built in this environment (e.g. the
    delta encoder declines the canonical corpus); the auditor records it
    as skipped instead of failing."""


class KernelEntrypoint(NamedTuple):
    """One registered jitted hot-path entrypoint.

    ``build(batch_size)`` returns ``(jitted_fn, args)`` ready to trace or
    call; building twice at the same size must return the SAME jitted
    object (the factory-identity half of the recompile lint).

    ``donate`` declares the entrypoint's donated operand positions
    (jax.jit donate_argnums) — the contract the jaxcheck donation lint
    verifies against the compiled program's input_output_alias map:
    every declared donated array leaf must actually alias an output, or
    XLA is silently copying a buffer the serving loop believes it
    reuses in place.  Donating entrypoints consume their operands, so
    the executing lints rebuild args per run.  Every resident-loop
    entrypoint MUST declare its donated operands (registry-level rule,
    also lint-enforced).

    ``bounds`` declares which positional args carry contract-bounded
    table operands: ``(arg_idx, role)`` or ``(arg_idx, role,
    spec_thunk)`` entries, where ``role`` keys
    ``contracts.TENSOR_BOUNDS`` and ``spec_thunk`` lazily supplies the
    geometry spec some resolvers need (arena page counts).  The static
    bounds verifier (analysis.boundscheck) seeds its abstract
    interpretation from these — an unannotated operand is assumed
    attacker-controlled (dtype-top)."""

    name: str
    kind: str  # "xla" | "pallas"
    build: Callable[[int], Tuple[Callable, tuple]]
    donate: Tuple[int, ...] = ()
    bounds: Tuple = ()


# -- canonical fixtures ------------------------------------------------------
#
# Tiny but structurally representative tables: the "deep" variant is
# v6-heavy with /48-/128 masks so the trie compiles its full level
# ladder; the "small" variant sits in the dense-path regime.  Cached per
# process — the auditor traces many entrypoints against the same tables.


@functools.lru_cache(maxsize=None)
def _fixture_tables(deep: bool):
    from .. import testing

    rng = np.random.default_rng(7 if deep else 5)
    if deep:
        return testing.random_tables_fast(
            rng, n_entries=512, width=4, v6_fraction=0.9, ifindexes=(2, 3)
        )
    return testing.random_tables_fast(
        rng, n_entries=64, width=4, v6_fraction=0.3, ifindexes=(2, 3)
    )


@functools.lru_cache(maxsize=None)
def _fixture_device_tables(deep: bool):
    from . import jaxpath

    return jaxpath.device_tables(_fixture_tables(deep))


@functools.lru_cache(maxsize=None)
def _fixture_batch(b: int):
    from .. import testing

    rng = np.random.default_rng(13)
    return testing.random_batch_fast(rng, _fixture_tables(True), n_packets=b)


@functools.lru_cache(maxsize=None)
def _fixture_device_batch(b: int):
    from . import jaxpath

    return jaxpath.device_batch(_fixture_batch(b))


@functools.lru_cache(maxsize=None)
def _fixture_wire(b: int):
    import jax

    return jax.device_put(_fixture_batch(b).pack_wire())


@functools.lru_cache(maxsize=None)
def _fixture_overlay_tables():
    from .. import testing
    from . import jaxpath

    rng = np.random.default_rng(23)
    ov = testing.random_tables_fast(
        rng, n_entries=16, width=4, v6_fraction=0.3, ifindexes=(2, 3)
    )
    return jaxpath.device_tables(ov)


@functools.lru_cache(maxsize=None)
def _fixture_delta(b: int):
    """(enc, payload_dev, dict_dev, ifmap_dev) for the delta-decode
    entrypoint: a v4-compact sorted-friendly corpus the encoder accepts."""
    import jax

    from ..packets import encode_delta_wire
    from . import wire_decode

    batch = _fixture_batch(b)
    idx = np.nonzero(np.asarray(batch.kind) != 2)[0]
    if len(idx) == 0:
        raise EntrypointUnavailable("canonical corpus has no v4 packets")
    v4 = batch.take(idx)
    v4.ip_words[:, 1:] = 0
    wire = v4.pack_wire_v4()
    enc = encode_delta_wire(wire)
    if enc is None:
        raise EntrypointUnavailable(
            "delta encoder declined the canonical corpus"
        )
    return (
        enc,
        jax.device_put(wire_decode.pad_payload(enc.payload)),
        jax.device_put(wire_decode.pad_dict(enc.dict_vals)),
        jax.device_put(enc.ifmap),
    )


# -- builders ----------------------------------------------------------------


def _build_classify(use_trie: bool):
    def build(b: int):
        from . import jaxpath

        fn = jaxpath.jitted_classify(use_trie)
        return fn, (_fixture_device_tables(use_trie), _fixture_device_batch(b))

    return build


def _build_classify_wire_fused(b: int):
    from . import jaxpath

    fn = jaxpath.jitted_classify_wire_fused(True)
    return fn, (_fixture_device_tables(True), _fixture_wire(b))


def _build_classify_wire_overlay(b: int):
    from . import jaxpath

    fn = jaxpath.jitted_classify_wire_overlay_fused(True)
    return fn, (
        _fixture_device_tables(True),
        _fixture_overlay_tables(),
        _fixture_wire(b),
    )


@functools.lru_cache(maxsize=None)
def _fixture_wire8(b: int):
    import jax

    from ..packets import wire8

    batch = _fixture_batch(b)
    idx = np.nonzero(np.asarray(batch.kind) != 2)[0]
    v4 = batch.take(idx)
    v4.ip_words[:, 1:] = 0
    packed = wire8(v4.pack_wire_v4())
    if packed is None:
        raise EntrypointUnavailable(
            "wire8 packer declined the canonical corpus"
        )
    wire8_np, ifmap = packed
    return jax.device_put(wire8_np), jax.device_put(ifmap)


def _build_wire8(b: int):
    from . import jaxpath

    wire, ifmap = _fixture_wire8(b)
    fn = jaxpath.jitted_classify_wire8_fused(False)
    return fn, (_fixture_device_tables(True), wire, ifmap)


def _build_delta_decode(b: int):
    from . import wire_decode

    enc, payload, dictv, ifmap = _fixture_delta(b)
    fn = wire_decode.jitted_classify_delta_fused(
        False, enc.n, enc.dict_mode, enc.fixed_w,
        use_pallas=False, interpret=True,
    )
    return fn, (_fixture_device_tables(True), payload, dictv, ifmap)


def _build_pallas_dense(b: int):
    from . import pallas_dense

    pt = _fixture_pallas_tables()
    fn = pallas_dense.jitted_classify_pallas(True)
    return fn, (pt, _fixture_device_batch(b))


@functools.lru_cache(maxsize=None)
def _fixture_pallas_tables():
    from . import pallas_dense

    return pallas_dense.build_pallas_tables(_fixture_tables(False))


@functools.lru_cache(maxsize=None)
def _fixture_walk_tables():
    from . import pallas_walk

    wt = pallas_walk.build_walk_tables(_fixture_tables(True))
    if wt is None:
        raise EntrypointUnavailable(
            "fused-walk tables failed to build for the canonical fixture"
        )
    return wt


def _build_pallas_walk(b: int):
    from . import pallas_walk

    fn = pallas_walk.jitted_classify_walk(True)
    return fn, (_fixture_walk_tables(), _fixture_device_batch(b))


def _build_pallas_dense_wire(b: int):
    """The dense path's WIRE-fused serving dispatch (backend/tpu.py
    _launch_wire, path == "dense") — the shape the deadline scheduler's
    ladder pre-warm exercises on dense tables; previously only the
    non-wire dense kernel was registered."""
    from . import pallas_dense

    pt = _fixture_pallas_tables()
    block_b = pallas_dense.choose_block_b(pt.mdt.shape[1])
    fn = pallas_dense.jitted_classify_pallas_wire_fused(True, block_b)
    return fn, (pt, _fixture_wire(b))


# -- compressed (ctrie/cwalk) fixtures/builders ------------------------------


@functools.lru_cache(maxsize=None)
def _fixture_ctrie():
    from . import jaxpath

    r = jaxpath.device_ctrie(_fixture_tables(True))
    if r is None:
        raise EntrypointUnavailable(
            "compressed layout ineligible for the canonical fixture"
        )
    return r


@functools.lru_cache(maxsize=None)
def _fixture_cwalk_tables():
    from . import pallas_walk

    built = pallas_walk.build_cwalk_tables_meta(_fixture_tables(True))
    if built is None:
        raise EntrypointUnavailable(
            "fused compressed-walk tables failed to build for the "
            "canonical fixture"
        )
    return built


def _build_ctrie_wire_fused(b: int):
    from . import jaxpath

    cdev, d_max = _fixture_ctrie()
    fn = jaxpath.jitted_classify_ctrie_wire_fused(d_max)
    return fn, (cdev, _fixture_wire(b))


def _build_ctrie_wire_overlay(b: int):
    from . import jaxpath

    cdev, d_max = _fixture_ctrie()
    fn = jaxpath.jitted_classify_ctrie_wire_overlay_fused(d_max)
    return fn, (cdev, _fixture_overlay_tables(), _fixture_wire(b))


def _build_pallas_cwalk(b: int):
    from . import pallas_walk

    wt, meta = _fixture_cwalk_tables()
    fn = pallas_walk.jitted_classify_cwalk(meta["d_max"], True)
    return fn, (wt, _fixture_device_batch(b))


# -- transaction patch (update-storm flush) fixtures/builders ----------------
#
# The batched multi-edit patch path (jaxpath.txn_scatter /
# patch_device_tables hint mode, patch_ctrie rules-only): a flushed edit
# transaction lands as ONE fused dense-group scatter plus the joined
# capped scatter.  Registered so the strict jax audit (transfer guard,
# recompile lint, VMEM estimate) covers the executables the update-storm
# dataplane launches per flush.


@functools.lru_cache(maxsize=None)
def _fixture_padded_tables():
    from . import jaxpath

    return jaxpath.device_tables(_fixture_tables(True), pad=True)


@functools.lru_cache(maxsize=None)
def _fixture_txn_payload(b: int):
    """Device-resident fused-transaction payload over the dense group:
    a ``min(b, budget)``-row dirty set padded to its capped shape —
    exactly what a flushed b-edit rules-only transaction scatters."""
    import jax

    from . import jaxpath

    dev = _fixture_padded_tables()
    arrays = (dev.key_words, dev.mask_words, dev.mask_len, dev.rules)
    nb = arrays[0].shape[0]
    k = max(1, min(int(b), nb // 4))
    idxs = []
    rows = []
    for a in arrays:
        pay = jaxpath._capped_payload(
            np.zeros(k, np.int64),
            np.zeros((k,) + tuple(a.shape[1:]), a.dtype),
            nb,
        )
        if pay is None:
            raise EntrypointUnavailable(
                f"txn payload of {k} rows exceeds the capped budget "
                f"(nb={nb})"
            )
        idxs.append(jax.device_put(pay[0]))
        rows.append(jax.device_put(pay[1]))
    return arrays, tuple(idxs), tuple(rows)


def _build_txn_scatter_dense(b: int):
    from . import jaxpath

    arrays, idxs, rows = _fixture_txn_payload(b)
    fn = jaxpath.jitted_txn_scatter(len(arrays))
    return fn, (arrays, idxs, rows)


@functools.lru_cache(maxsize=None)
def _fixture_ctrie_padded():
    from . import jaxpath

    r = jaxpath.device_ctrie(_fixture_tables(True), pad=True)
    if r is None:
        raise EntrypointUnavailable(
            "compressed layout ineligible for the canonical fixture"
        )
    return r


def _build_ctrie_joined_scatter(b: int):
    """The compressed layout's rules-only transaction flush: the
    per-tidx joined matrix capped scatter (patch_ctrie hot path)."""
    import jax

    from . import jaxpath

    cdev, _d = _fixture_ctrie_padded()
    nb = cdev.joined.shape[0]
    k = max(1, min(int(b), nb // 4))
    pay = jaxpath._capped_payload(
        np.zeros(k, np.int64),
        np.zeros((k, cdev.joined.shape[1]), np.uint16),
        nb,
    )
    if pay is None:
        raise EntrypointUnavailable(
            f"joined payload of {k} rows exceeds the capped budget "
            f"(nb={nb})"
        )
    fn = jaxpath._scatter_rows_jit()
    return fn, (
        cdev.joined, jax.device_put(pay[0]), jax.device_put(pay[1])
    )


# -- multi-tenant paged arena fixtures/builders (ISSUE-10) -------------------


@functools.lru_cache(maxsize=None)
def _fixture_arena(family: str):
    """A 4-page arena holding the two canonical fixture tables as
    tenants 0/1 — the mixed-tenant audit substrate."""
    from .. import testing
    from . import jaxpath

    rng = np.random.default_rng(31)
    t0 = _fixture_tables(False)
    t1 = testing.random_tables_fast(
        rng, n_entries=48, width=4, v6_fraction=0.6, ifindexes=(2, 3)
    )
    spec = jaxpath.arena_spec_for(
        family, (t0, t1), pages=4, max_tenants=8
    )
    alloc = jaxpath.ArenaAllocator(spec)
    alloc.load_tenant(0, t0)
    alloc.load_tenant(1, t1)
    return alloc


@functools.lru_cache(maxsize=None)
def _fixture_arena_wire(b: int):
    """(wire_dev, tenant_dev): the canonical batch round-robined over
    the two fixture tenants."""
    import jax

    tenant = (np.arange(b) % 2).astype(np.int32)
    return _fixture_wire(b), jax.device_put(tenant)


def _build_arena_wire(family: str):
    def build(b: int):
        from . import jaxpath

        alloc = _fixture_arena(family)
        spec = alloc.spec
        d_max = spec.d_max if family == "ctrie" else 0
        fn = jaxpath.jitted_classify_arena_wire_fused(
            family, spec.pages, d_max
        )
        wire, tenant = _fixture_arena_wire(b)
        return fn, (alloc.arena, wire, tenant)

    return build


@functools.lru_cache(maxsize=None)
def _fixture_splice_arena():
    """A subtree-spliced ctrie arena (ISSUE-17) holding the canonical
    fixture table as tenant 0 and a near-copy (one rules edit on a deep
    key) as tenant 1 — trunk + most subtree planes shared, the classify
    entry resolving through the splice indirection."""
    from ..compiler import IncrementalTables
    from .. import testing
    from . import jaxpath

    rng = np.random.default_rng(33)
    t0 = _fixture_tables(False)
    upd = IncrementalTables.from_content(dict(t0.content), rule_width=4)
    deep = sorted(
        (k for k in t0.content if k.prefix_len > 16),
        key=lambda k: (k.ingress_ifindex, k.prefix_len, k.ip_data),
    )
    if deep:
        upd.apply({deep[0]: testing.random_rules(rng, 4)})
    t1 = upd.snapshot()
    spec = jaxpath.arena_spec_for(
        "ctrie", (t0, t1), pages=4, max_tenants=8,
        plane_slots=256, plane_node_rows=16, plane_target_rows=16,
        plane_joined_rows=16, splice_slots=64,
    )
    alloc = jaxpath.ArenaAllocator(spec)
    alloc.load_tenant(0, t0)
    alloc.load_tenant(1, t1)
    return alloc


def _build_arena_splice_wire(b: int):
    from . import jaxpath

    alloc = _fixture_splice_arena()
    if not alloc.distinct_planes():
        raise EntrypointUnavailable(
            "fixture tables decompose to no shared subtree planes"
        )
    spec = alloc.spec
    fn = jaxpath.jitted_classify_arena_wire_fused(
        "ctrie", spec.pages, spec.d_max, spec=spec
    )
    wire, tenant = _fixture_arena_wire(b)
    return fn, (alloc.arena, wire, tenant)


def _build_pallas_arena_walk(b: int):
    from . import pallas_dense, pallas_walk

    alloc = _fixture_arena("ctrie")
    spec = alloc.spec
    planes = pallas_walk.build_arena_cwalk_planes(alloc.host_nodes())
    if planes is None:
        raise EntrypointUnavailable(
            "arena node pool exceeds the paged-walk VMEM budget"
        )
    fn = pallas_walk.jitted_classify_arena_cwalk_wire_fused(
        spec.pages, spec.d_max, pallas_dense.default_interpret()
    )
    wire, tenant = _fixture_arena_wire(b)
    return fn, (alloc.arena, planes, wire, tenant)


# -- stateful flow tier fixtures/builders (ISSUE-11) -------------------------


@functools.lru_cache(maxsize=None)
def _fixture_flow():
    """A small single-slab flow tier primed with one canonical batch,
    so the probe entrypoint traces over a partially-occupied table."""
    from ..flow import FlowConfig, FlowTier

    tier = FlowTier(FlowConfig.make(entries=512))
    wire = np.asarray(_fixture_batch(128).pack_wire())
    _fused, ctx = tier.probe(wire)
    tier.insert(ctx, wire, np.zeros(128, np.uint16))
    return tier


def _build_flow_probe(b: int):
    """The fused flow-probe serving dispatch (jaxpath.jitted_flow_probe
    through backend/tpu.py _launch_flow): cached-verdict serve + in-
    kernel counter/TCP-state updates in one launch."""
    import jax

    from . import jaxpath

    tier = _fixture_flow()
    cfg = tier.config
    fn = jaxpath.jitted_flow_probe(cfg.entries, cfg.ways)
    with tier._lock:
        flow, gens, pages = tier._flow, tier._gens_dev, tier._pages_dev
    wire = _fixture_wire(b)
    zeros = jax.device_put(np.zeros(b, np.int32))
    epoch = jax.device_put(np.int32(tier.epoch + 1))
    return fn, (flow, gens, pages, wire, zeros, zeros, epoch,
                tier._max_age_dev)


def _build_flow_insert(b: int):
    """The flow batch-insert scatter (jaxpath.jitted_flow_insert): miss
    verdicts land in one deduplicated multi-column scatter dispatch."""
    import jax

    from . import jaxpath

    tier = _fixture_flow()
    cfg = tier.config
    fn = jaxpath.jitted_flow_insert(cfg.entries, cfg.ways)
    with tier._lock:
        flow, gens, pages = tier._flow, tier._gens_dev, tier._pages_dev
    wire = _fixture_wire(b)
    zeros = jax.device_put(np.zeros(b, np.int32))
    verdicts = jax.device_put(np.zeros(b, np.uint32))
    epoch = jax.device_put(np.int32(tier.epoch + 1))
    return fn, (flow, gens, pages, wire, zeros, zeros, verdicts, epoch)


# -- resident serving loop fixtures/builders (ISSUE-12) ----------------------
#
# The donated-buffer fused step (jaxpath.jitted_resident_step): wire
# decode + flow probe + stateless classify + merge + stats + miss insert
# in ONE program, flow columns + epoch donated.  Builders return FRESH
# donated operands on every call — execution consumes them (the
# executing lints rebuild per run, keyed off the declared donate tuple).


def _resident_operands(b: int):
    """Fresh flow columns + steering scalars for one resident trace."""
    import jax

    from ..flow import FlowConfig
    from . import jaxpath

    cfg = FlowConfig.make(entries=512)
    C = cfg.capacity
    flow = jaxpath.FlowTable(
        keys=jax.device_put(np.zeros((C, 8), np.uint32)),
        vg=jax.device_put(np.zeros((C, 2), np.int32)),
        se=jax.device_put(np.zeros((C, 2), np.int32)),
        cnt=jax.device_put(np.zeros((C, 3), np.int32)),
    )
    gens = jax.device_put(np.zeros(1, np.int32))
    pages = jax.device_put(np.zeros(1, np.int32))
    epoch = jax.device_put(np.int32(0))
    max_age = jax.device_put(np.int32(cfg.max_age))
    zeros = jax.device_put(np.zeros(b, np.int32))
    return cfg, flow, gens, pages, epoch, max_age, zeros


def _build_resident_fused(b: int):
    """The resident fused serving step over the mixed 7-word wire."""
    from . import jaxpath

    cfg, flow, gens, pages, epoch, max_age, zeros = _resident_operands(b)
    fn = jaxpath.jitted_resident_step(
        cfg.entries, cfg.ways, "trie", False, None, 0, False
    )
    return fn, (flow, gens, pages, epoch, _fixture_device_tables(True),
                _fixture_wire(b), zeros, zeros, max_age)


def _build_resident_ring_fused(b: int):
    """The resident step fed from an ingest-ring slot: the v4-compact
    4-word record is packed IN PLACE into a mapped ring slot and the
    H2D staging device_put reads straight out of the mapping — the
    exact producer->consumer->device path of the --ring daemon mode."""
    import tempfile

    import jax

    from ..ring import IngestRing
    from . import jaxpath

    batch = _fixture_batch(b)
    idx = np.nonzero(np.asarray(batch.kind) != 2)[0]
    if len(idx) == 0:
        raise EntrypointUnavailable("canonical corpus has no v4 packets")
    v4 = batch.take(idx)
    v4.ip_words[:, 1:] = 0
    wire_np = v4.pack_wire_v4()
    n = wire_np.shape[0]
    with tempfile.TemporaryDirectory() as d:
        ring = IngestRing.create(f"{d}/audit.ring", slots=2,
                                 slot_packets=max(n, 8))
        wv, _fl, token = ring.reserve(n, 4)
        np.copyto(wv, wire_np)
        ring.commit(token, v4_only=True)
        chunk = ring.pop(timeout=1.0)
        wire = jax.device_put(np.ascontiguousarray(chunk.wire, np.uint32))
        chunk.release()
        ring.close()
    cfg, flow, gens, pages, epoch, max_age, _z = _resident_operands(b)
    zeros = jax.device_put(np.zeros(n, np.int32))
    fn = jaxpath.jitted_resident_step(
        cfg.entries, cfg.ways, "trie", True, None, 0, False
    )
    return fn, (flow, gens, pages, epoch, _fixture_device_tables(True),
                wire, zeros, zeros, max_age)


@functools.lru_cache(maxsize=None)
def _fixture_wire_stack(b: int, k: int = 2):
    """K stacked (B, 7) wire batches for the superbatch epoch program —
    rows rotated per admission so the K steps don't degenerate into
    identical flow probes."""
    import jax

    w = _fixture_batch(b).pack_wire()
    return jax.device_put(
        np.stack([np.roll(w, j, axis=0) for j in range(k)])
    )


def _build_resident_superbatch_fused(b: int):
    """The device-side epoch program (ISSUE-16): K=2 stacked admissions
    chewed by one while-loop dispatch, flow columns + epoch chained
    through the loop carry.  Donation matches the single step — the
    carry must alias in place through the while loop or every
    superbatch silently copies the whole flow slab K times."""
    import jax

    from . import jaxpath

    cfg, flow, gens, pages, epoch, max_age, _z = _resident_operands(b)
    zeros = jax.device_put(np.zeros((2, b), np.int32))
    fn = jaxpath.jitted_resident_superbatch(
        cfg.entries, cfg.ways, "trie", False, None, 0, False
    )
    return fn, (flow, gens, pages, epoch, _fixture_device_tables(True),
                _fixture_wire_stack(b), zeros, zeros, max_age)


# -- telemetry-plane fixtures/builders (ISSUE-13) ----------------------------
#
# The device-resident sketch update (kernels.sketch): count-min + top-K
# heavy-hitter + per-tenant counter scatters, donated state.  Two forms
# are hot-path: the standalone follow-on launch (multi-dispatch wire
# path) and the resident fused step's in-program composition.  Builders
# return FRESH donated operands per call (the executing lints consume
# them).


def _telemetry_spec():
    from .sketch import SketchSpec

    return SketchSpec.make(depth=3, width=256, topk=32, ways=2)


def _fresh_sketch_state(spec):
    import jax

    from .sketch import SketchState, zero_state_host

    return SketchState(
        *(jax.device_put(a) for a in zero_state_host(spec))
    )


def _build_sketch_update(b: int):
    """The classic telemetry launch: one device program updating the
    whole telemetry plane from (wire, verdicts), state donated, no
    readback."""
    import jax

    from . import sketch as sketch_mod

    spec = _telemetry_spec()
    fn = sketch_mod.jitted_sketch_update(spec)
    zeros = jax.device_put(np.zeros(b, np.int32))
    res = jax.device_put(np.zeros(b, np.uint32))
    return fn, (_fresh_sketch_state(spec), _fixture_wire(b), zeros, zeros,
                res)


def _build_resident_telemetry_fused(b: int):
    """The resident fused step with the telemetry plane riding the same
    program: flow columns + epoch + sketch tensors all donated."""
    from . import jaxpath

    spec = _telemetry_spec()
    cfg, flow, gens, pages, epoch, max_age, zeros = _resident_operands(b)
    fn = jaxpath.jitted_resident_step(
        cfg.entries, cfg.ways, "trie", False, None, 0, False, sketch=spec
    )
    return fn, (flow, gens, pages, epoch, _fresh_sketch_state(spec),
                _fixture_device_tables(True), _fixture_wire(b), zeros,
                zeros, max_age)


def _build_resident_superbatch_telemetry_fused(b: int):
    """The superbatch epoch program with the telemetry plane riding the
    loop carry: sketch tensors donated and chained through the while
    loop alongside the flow columns (ISSUE-16)."""
    import jax

    from . import jaxpath

    spec = _telemetry_spec()
    cfg, flow, gens, pages, epoch, max_age, _z = _resident_operands(b)
    zeros = jax.device_put(np.zeros((2, b), np.int32))
    fn = jaxpath.jitted_resident_superbatch(
        cfg.entries, cfg.ways, "trie", False, None, 0, False, sketch=spec
    )
    return fn, (flow, gens, pages, epoch, _fresh_sketch_state(spec),
                _fixture_device_tables(True), _fixture_wire_stack(b),
                zeros, zeros, max_age)


# -- anomaly-scoring fixtures/builders (ISSUE-14) ----------------------------
#
# The MXU scoring update (kernels.mxu_score): per-source feature
# scatters + the oblivious-forest one-hot matmul + the int8 MLP head +
# the per-tenant policy, donated state.  Two hot-path forms: the
# standalone follow-on launch (multi-dispatch wire path) and the
# resident fused step's in-program composition.  Model value operands
# are persistent, NOT donated.


def _score_spec():
    from .mxu_score import ScoreSpec

    return ScoreSpec.make(trees=4, depth=3, slots=64, ways=2,
                          cms_depth=2, cms_width=128, hidden=4)


def _fresh_score_state(spec):
    import jax

    from .mxu_score import ScoreState, zero_state_host

    return ScoreState(
        *(jax.device_put(a) for a in zero_state_host(spec))
    )


def _score_model_operands(spec):
    import jax

    from .mxu_score import clamp_stress_model, model_device, zero_tparams

    return (model_device(clamp_stress_model(spec)),
            jax.device_put(zero_tparams(spec)))


def _build_score_update(b: int):
    """The classic scoring launch: one device program updating the
    feature state and scoring every lane from (wire, verdicts), state
    donated."""
    import jax

    from . import mxu_score as mxu_score_mod

    spec = _score_spec()
    fn = mxu_score_mod.jitted_score_update(spec)
    model, tparams = _score_model_operands(spec)
    zeros = jax.device_put(np.zeros(b, np.int32))
    res = jax.device_put(np.zeros(b, np.uint32))
    return fn, (_fresh_score_state(spec), model, tparams,
                _fixture_wire(b), zeros, zeros, res)


def _build_resident_mlscore_fused(b: int):
    """The resident fused step with the scoring plane riding the same
    program: flow columns + epoch + score state donated; the model
    value / tparams operands persist across dispatches."""
    from . import jaxpath

    spec = _score_spec()
    cfg, flow, gens, pages, epoch, max_age, zeros = _resident_operands(b)
    model, tparams = _score_model_operands(spec)
    fn = jaxpath.jitted_resident_step(
        cfg.entries, cfg.ways, "trie", False, None, 0, False, score=spec
    )
    return fn, (flow, gens, pages, epoch, _fresh_score_state(spec),
                model, tparams, _fixture_device_tables(True),
                _fixture_wire(b), zeros, zeros, max_age)


# -- payload-matching fixtures/builders (ISSUE-19) ---------------------------
#
# The batched Aho-Corasick match (kernels.acmatch) fused into the
# resident step as the fourth verdict-merge tier.  The automaton
# operands (transition tensor, match bitmap, mode scalar) are
# persistent VALUES, never donated — the strict audit proves engaging
# the payload tier leaves the flow/epoch donation aliasing intact.


@functools.lru_cache(maxsize=None)
def _payload_model():
    from . import acmatch

    return acmatch.compile_patterns(
        [b"/etc/passwd", b"passwd", b"<script>", b"\x90\x90\x90\x90"],
        plen=64,
    )


def _payload_operands(b: int, stacked: bool = False):
    import jax

    from . import acmatch

    model = _payload_model()
    trans, mmap = acmatch.model_device(model)
    pmode = jax.device_put(np.asarray([0], np.int32))
    pay = np.zeros((b, model.spec.plen), np.uint8)
    sig = np.frombuffer(b"/etc/passwd", np.uint8)
    pay[: b // 2, : len(sig)] = sig
    plen = np.full(b, model.spec.plen, np.int32)
    if stacked:
        pay = np.stack([pay, np.roll(pay, 1, axis=0)])
        plen = np.stack([plen, plen])
    return (model.spec, (trans, mmap, pmode),
            jax.device_put(pay), jax.device_put(plen))


@functools.lru_cache(maxsize=None)
def _acmatch_standalone_model():
    """A DELIBERATELY deep pattern set (40 x 8 bytes -> several
    hundred DFA states, bucketed past MATMUL_MAX_STATES) so the
    standalone matcher compiles the dense-delta GATHER path — the
    int32 carried-state regime where a narrowed restage is a provable
    wrap.  The canonical 4-pattern payload fixture stays in the
    matmul regime and cannot exercise that path."""
    from . import acmatch

    pats = [
        bytes(((i * 17 + j * 7) % 251) + 1 for j in range(8))
        for i in range(40)
    ]
    model = acmatch.compile_patterns(pats, plen=64)
    assert not model.spec.matmul, (
        "standalone AC fixture must land in the gather regime"
    )
    return model


def _build_acmatch_standalone(b: int):
    import jax

    from . import acmatch

    model = _acmatch_standalone_model()
    trans, mmap = acmatch.model_device(model)
    pay = np.zeros((b, model.spec.plen), np.uint8)
    sig = np.frombuffer(model.patterns[0], np.uint8)
    pay[: b // 2, : len(sig)] = sig
    plen = np.full(b, model.spec.plen, np.int32)
    fn = acmatch.jitted_acmatch(model.spec)
    return fn, (trans, mmap, jax.device_put(pay), jax.device_put(plen))


def _build_resident_payload_fused(b: int):
    """The resident fused step with the payload-matching tier riding
    the same program: flow columns + epoch donated exactly as the base
    step — the automaton operands are value operands placed after
    every donated position, so the audit's input_output_alias check
    proves the fourth tier never disturbs the aliasing."""
    from . import jaxpath

    cfg, flow, gens, pages, epoch, max_age, zeros = _resident_operands(b)
    spec, pops, pay, plen = _payload_operands(b)
    fn = jaxpath.jitted_resident_step(
        cfg.entries, cfg.ways, "trie", False, None, 0, False,
        payload=spec,
    )
    return fn, (flow, gens, pages, epoch, *pops,
                _fixture_device_tables(True), _fixture_wire(b), pay, plen,
                zeros, zeros, max_age)


def _build_resident_superbatch_payload_fused(b: int):
    """The superbatch epoch program with the payload tier riding the
    device-side scan: stacked (K, B, L) payload columns travel the scan
    xs next to the wire while the automaton operands stay
    loop-invariant (closed over, one HBM copy for all K steps)."""
    import jax

    from . import jaxpath

    cfg, flow, gens, pages, epoch, max_age, _z = _resident_operands(b)
    zeros = jax.device_put(np.zeros((2, b), np.int32))
    spec, pops, pay, plen = _payload_operands(b, stacked=True)
    fn = jaxpath.jitted_resident_superbatch(
        cfg.entries, cfg.ways, "trie", False, None, 0, False,
        payload=spec,
    )
    return fn, (flow, gens, pages, epoch, *pops,
                _fixture_device_tables(True), _fixture_wire_stack(b),
                pay, plen, zeros, zeros, max_age)


# -- mesh (multi-chip serving) fixtures/builders -----------------------------
#
# The MeshTpuClassifier's shard_map'd dispatch (backend/mesh.py,
# parallel/mesh.py jitted_mesh_wire) is hot-path too: register it so the
# strict jax audit (x64 leaks, host callbacks, recompile lint, Pallas
# VMEM budget) covers the multi-chip programs.  The builders need a
# multi-device pool (the audit env forces 8 virtual CPU devices, see
# Makefile entry-check); on a single-device host they report
# EntrypointUnavailable instead of failing.


@functools.lru_cache(maxsize=None)
def _fixture_mesh(rules_shards: int):
    import jax

    from ..parallel import mesh as meshmod

    n = len(jax.devices())
    n -= n % rules_shards
    if n < 2 or n < rules_shards:
        raise EntrypointUnavailable(
            f"mesh entrypoints need >=2 devices (rules_shards="
            f"{rules_shards}); have {len(jax.devices())}"
        )
    return meshmod.make_mesh(n, rules_shards=rules_shards)


def _mesh_data_sharding(mesh):
    from jax.sharding import NamedSharding, PartitionSpec as P

    return NamedSharding(mesh, P("data", None))


@functools.lru_cache(maxsize=None)
def _fixture_mesh_wire(b: int, rules_shards: int):
    import jax

    mesh = _fixture_mesh(rules_shards)
    data = mesh.shape["data"]
    if b % data != 0:
        # An odd device pool (e.g. 6 visible -> data axis 3) may not
        # divide a ladder batch: skip, don't fail the strict audit with
        # a raw sharding ValueError.
        raise EntrypointUnavailable(
            f"ladder batch {b} not divisible over the {data}-wide data "
            "axis of this device pool"
        )
    return jax.device_put(
        _fixture_batch(b).pack_wire(), _mesh_data_sharding(mesh)
    )


@functools.lru_cache(maxsize=None)
def _fixture_mesh_dense_tables():
    from ..parallel import mesh as meshmod

    return meshmod.shard_tables(_fixture_tables(False), _fixture_mesh(2))


@functools.lru_cache(maxsize=None)
def _fixture_mesh_trie_tables():
    from ..parallel import mesh as meshmod

    return meshmod.shard_tables_trie(_fixture_tables(True), _fixture_mesh(2))


@functools.lru_cache(maxsize=None)
def _fixture_mesh_walk_tables():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = _fixture_mesh(1)
    rep = NamedSharding(mesh, P())
    return jax.tree.map(
        lambda a: jax.device_put(a, rep), _fixture_walk_tables()
    )


def _build_mesh_sharded_dense(b: int):
    from ..parallel import mesh as meshmod

    dev = _fixture_mesh_dense_tables()
    fn = meshmod.jitted_mesh_wire(_fixture_mesh(2), "dense-sharded", dev)
    return fn, (dev, _fixture_mesh_wire(b, 2))


def _build_mesh_sharded_trie(b: int):
    from ..parallel import mesh as meshmod

    dev = _fixture_mesh_trie_tables()
    fn = meshmod.jitted_mesh_wire(_fixture_mesh(2), "trie-sharded", dev)
    return fn, (dev, _fixture_mesh_wire(b, 2))


def _build_mesh_walk(b: int):
    from ..parallel import mesh as meshmod
    from . import pallas_dense

    dev = _fixture_mesh_walk_tables()
    fn = meshmod.jitted_mesh_wire(
        _fixture_mesh(1), "walk", dev,
        interpret=pallas_dense.default_interpret(),
    )
    return fn, (dev, _fixture_mesh_wire(b, 1))


@functools.lru_cache(maxsize=None)
def _fixture_mesh_arena():
    """The fixture arena placed on a ("data", "rules") mesh with the
    per-family partition rules — pages in whole-slab blocks over
    "rules", page table replicated (parallel.mesh.ARENA_PARTITION_
    RULES, declared once per slab family)."""
    import jax

    from ..parallel import mesh as meshmod
    from . import jaxpath

    mesh = _fixture_mesh(2)
    t0 = _fixture_tables(False)
    spec = jaxpath.arena_spec_for("ctrie", (t0,), pages=4, max_tenants=8)
    alloc = jaxpath.ArenaAllocator(
        spec,
        device=meshmod.arena_replicated(mesh),
        shardings=meshmod.arena_shardings(mesh, "ctrie", spec.pages),
    )
    alloc.load_tenant(0, t0)
    alloc.load_tenant(1, t0)
    return mesh, alloc


def _build_mesh_arena_trie(b: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from . import jaxpath

    mesh, alloc = _fixture_mesh_arena()
    spec = alloc.spec
    fn = jaxpath.jitted_classify_arena_wire_fused(
        "ctrie", spec.pages, spec.d_max
    )
    wire = _fixture_mesh_wire(b, 2)
    tenant = jax.device_put(
        (np.arange(b) % 2).astype(np.int32),
        NamedSharding(mesh, P("data")),
    )
    return fn, (alloc.arena, wire, tenant)


def kernel_entrypoints() -> List[KernelEntrypoint]:
    """The registered jitted hot-path entrypoints, in dispatch order of
    the TPU backend (backend/tpu.py _launch_wire and friends), then the
    mesh serving programs (backend/mesh.py)."""
    return [
        KernelEntrypoint("classify/xla-dense", "xla", _build_classify(False),
                         bounds=((0, "device-tables"),)),
        KernelEntrypoint("classify/xla-trie", "xla", _build_classify(True),
                         bounds=((0, "device-tables"),)),
        KernelEntrypoint(
            "classify-wire/xla-trie-fused", "xla", _build_classify_wire_fused,
            bounds=((0, "device-tables"),),
        ),
        KernelEntrypoint(
            "classify-wire/xla-overlay-fused", "xla",
            _build_classify_wire_overlay,
            bounds=((0, "device-tables"), (1, "device-tables")),
        ),
        KernelEntrypoint(
            "classify-wire8/xla-fused", "xla", _build_wire8,
            bounds=((0, "device-tables"),),
        ),
        KernelEntrypoint(
            "wire-decode/delta-fused", "xla", _build_delta_decode,
            bounds=((0, "device-tables"),),
        ),
        KernelEntrypoint(
            "classify/pallas-dense", "pallas", _build_pallas_dense
        ),
        KernelEntrypoint(
            "classify-wire/pallas-dense-fused", "pallas",
            _build_pallas_dense_wire,
        ),
        KernelEntrypoint(
            "classify/pallas-walk", "pallas", _build_pallas_walk
        ),
        KernelEntrypoint(
            "classify-wire/xla-ctrie-fused", "xla", _build_ctrie_wire_fused,
            bounds=((0, "ctrie-tables"),),
        ),
        KernelEntrypoint(
            "classify-wire/xla-ctrie-overlay-fused", "xla",
            _build_ctrie_wire_overlay,
            bounds=((0, "ctrie-tables"), (1, "device-tables")),
        ),
        KernelEntrypoint(
            "classify/pallas-cwalk", "pallas", _build_pallas_cwalk,
            bounds=((0, "ctrie-tables"),),
        ),
        KernelEntrypoint(
            "patch/txn-scatter-dense", "xla", _build_txn_scatter_dense
        ),
        KernelEntrypoint(
            "patch/ctrie-joined-scatter", "xla", _build_ctrie_joined_scatter
        ),
        KernelEntrypoint(
            "classify-wire/arena-dense", "xla", _build_arena_wire("dense"),
            bounds=((0, "dense-arena",
                     lambda: _fixture_arena("dense").spec),),
        ),
        KernelEntrypoint(
            "classify-wire/arena-trie", "xla", _build_arena_wire("ctrie"),
            bounds=((0, "ctrie-arena",
                     lambda: _fixture_arena("ctrie").spec),),
        ),
        KernelEntrypoint(
            "classify-wire/arena-splice-trie", "xla",
            _build_arena_splice_wire,
            bounds=((0, "ctrie-arena",
                     lambda: _fixture_splice_arena().spec),),
        ),
        KernelEntrypoint(
            "classify/pallas-arena-walk", "pallas", _build_pallas_arena_walk,
            bounds=((0, "ctrie-arena",
                     lambda: _fixture_arena("ctrie").spec),),
        ),
        KernelEntrypoint(
            "classify-wire/flow-probe", "xla", _build_flow_probe,
            bounds=((2, "flow-page-table",
                     lambda: _fixture_flow().config.pages),),
        ),
        KernelEntrypoint(
            "patch/flow-insert", "xla", _build_flow_insert,
            bounds=((2, "flow-page-table",
                     lambda: _fixture_flow().config.pages),),
        ),
        KernelEntrypoint(
            "classify-wire/resident-fused", "xla", _build_resident_fused,
            donate=(0, 3),
            bounds=((2, "flow-page-table"), (4, "device-tables")),
        ),
        KernelEntrypoint(
            "classify-wire/resident-ring-fused", "xla",
            _build_resident_ring_fused, donate=(0, 3),
            bounds=((2, "flow-page-table"), (4, "device-tables")),
        ),
        KernelEntrypoint(
            "classify-wire/resident-superbatch-fused", "xla",
            _build_resident_superbatch_fused, donate=(0, 3),
            bounds=((2, "flow-page-table"), (4, "device-tables")),
        ),
        KernelEntrypoint(
            "telemetry/sketch-update", "xla", _build_sketch_update,
            donate=(0,),
        ),
        KernelEntrypoint(
            "classify-wire/resident-telemetry-fused", "xla",
            _build_resident_telemetry_fused, donate=(0, 3, 4),
            bounds=((2, "flow-page-table"), (5, "device-tables")),
        ),
        KernelEntrypoint(
            "classify-wire/resident-superbatch-telemetry-fused", "xla",
            _build_resident_superbatch_telemetry_fused, donate=(0, 3, 4),
            bounds=((2, "flow-page-table"), (5, "device-tables")),
        ),
        KernelEntrypoint(
            "mlscore/score-update", "xla", _build_score_update,
            donate=(0,),
        ),
        KernelEntrypoint(
            "classify-wire/resident-mlscore-fused", "xla",
            _build_resident_mlscore_fused, donate=(0, 3, 4),
            bounds=((2, "flow-page-table"), (7, "device-tables")),
        ),
        KernelEntrypoint(
            "classify-wire/resident-payload-fused", "xla",
            _build_resident_payload_fused, donate=(0, 3),
            bounds=((2, "flow-page-table"), (4, "ac-dflat"),
                    (7, "device-tables")),
        ),
        KernelEntrypoint(
            "classify-wire/resident-superbatch-payload-fused", "xla",
            _build_resident_superbatch_payload_fused, donate=(0, 3),
            bounds=((2, "flow-page-table"), (4, "ac-dflat"),
                    (7, "device-tables")),
        ),
        KernelEntrypoint(
            "payload/acmatch-standalone", "xla", _build_acmatch_standalone,
            bounds=((0, "ac-delta"),),
        ),
        KernelEntrypoint(
            "classify-mesh/sharded-dense-wire", "xla",
            _build_mesh_sharded_dense,
            bounds=((0, "device-tables"),),
        ),
        KernelEntrypoint(
            "classify-mesh/sharded-trie-wire", "xla",
            _build_mesh_sharded_trie,
            bounds=((0, "device-tables"),),
        ),
        KernelEntrypoint(
            "classify-mesh/walk-wire", "pallas", _build_mesh_walk
        ),
        KernelEntrypoint(
            "classify-mesh/arena-trie-wire", "xla", _build_mesh_arena_trie,
            bounds=((0, "ctrie-arena",
                     lambda: _fixture_mesh_arena()[1].spec),),
        ),
    ]
