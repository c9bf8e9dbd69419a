"""Zero-copy resident serving loop (ISSUE-12).

Covers: bit-identity of the ONE-fused-program-per-admission dispatch
(decode + flow probe + stateless classify + merge + stats + miss
insert) vs the multi-dispatch flow plan AND the CPU oracle — verdicts,
statistics and all four donated flow columns; donation aliasing
discipline (back-to-back dispatches must not corrupt earlier unread
outputs, incl. under the scheduler's ping-pong staging and on the
8-virtual-device mesh); table-patch staleness (the pool context
refreshes per generation; the injected residentstale defect serves
stale tables and must diverge); the zero-recompile/zero-alloc warm
lifecycle; the ingest ring (wraparound, backpressure, zero-copy views,
loadgen producer subprocess, daemon ring ingest + metrics); the
jaxcheck donation lint both ways; the statecheck resident config; the
native delta-encode parity; and the BENCH_r05 rung-32 pinned-input
regression (compile-free pinned sweep after the ladder prewarm — the
round-5 anomaly was the first-measured shape paying its jit
specialization + per-executable first-dispatch cost inside the timed
loop, not a rung-32 dataplane bug).
"""
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from infw import oracle, resident as resident_mod, testing
from infw.backend.tpu import TpuClassifier
from infw.compiler import IncrementalTables
from infw.flow import FlowConfig
from infw.kernels import jaxpath
from infw.ring import IngestRing

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: ONE flow geometry for the whole module (and the same one the
#: entrypoint fixtures use): jitted_resident_step caches key on the
#: slab geometry, so every test sharing it amortizes the fused-program
#: compiles — the suite cost is dominated by unique (geometry, shape)
#: compiles, not by test count.
ENTRIES = 512


def _tables(seed=3, n=300, width=4, v6=0.4):
    return testing.random_tables_fast(
        np.random.default_rng(seed), n_entries=n, width=width,
        v6_fraction=v6, ifindexes=(2, 3),
    )


def _pair(tabs, entries=ENTRIES, **kw):
    """(resident classifier, multi-dispatch classifier), same tables and
    flow geometry."""
    res = TpuClassifier(
        interpret=True, flow_table=FlowConfig.make(entries=entries),
        resident=True, **kw,
    )
    multi = TpuClassifier(
        interpret=True, flow_table=FlowConfig.make(entries=entries), **kw,
    )
    res.load_tables(tabs)
    multi.load_tables(tabs)
    return res, multi


@pytest.fixture(scope="module")
def shared():
    """Module-shared (tables, resident clf, multi clf), ladder
    pre-warmed once; tests reset the flow tiers instead of rebuilding
    classifiers (each rebuild would re-run the jit warm dispatches)."""
    from infw.scheduler import prewarm_ladder

    tabs = _tables()
    res, multi = _pair(tabs, force_path="trie")
    # identical ladders: every production dispatch bumps the flow epoch
    # exactly once (fused step or classic probe), so equal prewarm
    # sequences keep the two tiers' epoch counters in lockstep — the
    # column bit-identity tests compare se[:, 1] (last-seen epochs) too.
    # Depth-class variants are skipped (one fused compile per class per
    # rung — the tests here never steer); the full-ladder prewarm is
    # exercised by bench_resident and the scheduler suite.
    prewarm_ladder(res, (32, 64, 128), include_depth_classes=False)
    prewarm_ladder(multi, (32, 64, 128), include_depth_classes=False)
    yield tabs, res, multi
    res.close()
    multi.close()


def _flow_cols(clf):
    return clf.flow.flow_columns()


# --- fused-step bit-identity -------------------------------------------------


@pytest.mark.slow
def test_resident_bit_identity_vs_multi_and_oracle(shared):
    """Two passes (populate, then serve-from-cache) over the same batch:
    verdicts, xdp, statistics and every donated flow column must equal
    the multi-dispatch plan and the CPU oracle at each pass."""
    tabs, res, multi = shared
    res.flow.reset()
    multi.flow.reset()
    batch = testing.random_batch_fast(np.random.default_rng(9), tabs, 64)
    ref = oracle.classify(tabs, batch)
    for p in range(2):
        o = res.classify(batch, apply_stats=False)
        om = multi.classify(batch, apply_stats=False)
        assert np.array_equal(o.results, ref.results), f"pass {p}"
        assert np.array_equal(o.xdp, ref.xdp)
        assert np.array_equal(o.stats_delta, om.stats_delta)
        from infw.testing import stats_dict_from_array

        assert stats_dict_from_array(o.stats_delta) == ref.stats
    a, b = _flow_cols(res), _flow_cols(multi)
    for k in a:
        assert np.array_equal(a[k], b[k]), f"flow column {k} diverged"
    assert res.resident_counters()["resident_dispatches_total"] >= 2


@pytest.mark.slow
def test_resident_tcp_flags_and_v4_compact(shared):
    """The SYN/EST/FIN/RST state machine rides the fused step: a flagged
    trace through the resident path matches the multi-dispatch plan
    column-for-column, on the v4-compact 4-word wire."""
    tabs = _tables(v6=0.0)
    res, multi = _pair(tabs, force_path="trie")
    batch, _meta = testing.flow_trace_batch(
        np.random.default_rng(17), tabs, 256, 0.7, chunk_packets=64
    )
    ref = oracle.classify(tabs, batch)
    for lo in range(0, len(batch), 64):
        sub = batch.slice(lo, lo + 64)
        o = res.classify(sub, apply_stats=False)
        om = multi.classify(sub, apply_stats=False)
        assert np.array_equal(o.results, ref.results[lo : lo + 64])
        assert np.array_equal(o.results, om.results)
    a, b = _flow_cols(res), _flow_cols(multi)
    for k in a:
        assert np.array_equal(a[k], b[k]), f"flow column {k} diverged"


@pytest.mark.slow
def test_resident_dense_and_ctrie_paths():
    """The resident program covers all three layout paths: the dense
    path serves from the pool's XLA DeviceTables twin, the compressed
    layout from the ctrie walk — both oracle-identical."""
    for kw, seed in (({}, 5), ({"force_path": "ctrie"}, 7)):
        tabs = _tables(seed=seed)
        res, _m = _pair(tabs, **kw)
        batch = testing.random_batch_fast(
            np.random.default_rng(seed + 1), tabs, 64
        )
        ref = oracle.classify(tabs, batch)
        for _ in range(2):
            o = res.classify(batch, apply_stats=False)
            assert np.array_equal(o.results, ref.results)
        assert res.resident_counters()["resident_fallbacks_total"] == 0
        _m.close()
        res.close()


@pytest.mark.slow
def test_resident_overlay_variants():
    """The overlay side-table combine rides the fused step (trie and
    compressed layouts): overlay-resident keys win by longest prefix,
    oracle-identical across both passes."""
    from infw.compiler import compile_tables_from_content

    tabs = _tables(seed=13, n=200)
    ov_tabs = testing.random_tables_fast(
        np.random.default_rng(14), n_entries=8, width=4, v6_fraction=0.3,
        ifindexes=(2, 3),
    )
    taken = {k.masked_identity() for k in tabs.content}
    ov_content = {
        k: v for k, v in ov_tabs.content.items()
        if k.masked_identity() not in taken
    }
    ov = compile_tables_from_content(ov_content, rule_width=4)
    merged = dict(tabs.content)
    merged.update(ov_content)
    model = compile_tables_from_content(merged, rule_width=4)
    for fp in ("trie", "ctrie"):
        clf = TpuClassifier(
            interpret=True, force_path=fp,
            flow_table=FlowConfig.make(entries=512), resident=True,
        )
        clf.load_tables(tabs, overlay=ov)
        batch = testing.random_batch(np.random.default_rng(15), model, 64)
        ref = oracle.classify(model, batch)
        for p in range(2):
            o = clf.classify(batch, apply_stats=False)
            assert np.array_equal(o.results, ref.results), (fp, p)
        assert clf.resident_counters()["resident_fallbacks_total"] == 0
        clf.close()


@pytest.mark.slow
def test_resident_wide_ruleid_falls_back():
    """Wide-ruleId tables cannot ride the 16-bit resident merge: the
    classifier falls back to the full-batch u32 path, verdicts stay
    oracle-identical (degrade, never refuse)."""
    from infw.constants import IPPROTO_TCP

    content = dict(_tables(n=64).content)
    k = next(iter(content))
    rows = np.zeros((4, 7), np.int32)
    rows[1] = [70001, IPPROTO_TCP, 443, 0, 0, 0, 1]
    content[k] = rows
    from infw.compiler import compile_tables_from_content

    tabs = compile_tables_from_content(content, rule_width=4)
    res = TpuClassifier(
        interpret=True, flow_table=FlowConfig.make(entries=512),
        resident=True, force_path="trie",
    )
    res.load_tables(tabs)
    batch = testing.random_batch(np.random.default_rng(3), tabs, 64)
    ref = oracle.classify(tabs, batch)
    o = res.classify(batch, apply_stats=False)
    assert np.array_equal(o.results, ref.results)
    res.close()


# --- donation / aliasing discipline -----------------------------------------


@pytest.mark.slow
def test_resident_back_to_back_unread_outputs(shared):
    """Double-buffer discipline: dispatch N+1 reusing the donated pool
    must not corrupt dispatch N's unread output — stage several plans
    back-to-back, materialize them afterwards in order and out of
    order."""
    tabs, res, _multi = shared
    res.flow.reset()
    rng = np.random.default_rng(23)
    batches = [testing.random_batch_fast(rng, tabs, 32) for _ in range(6)]
    refs = [oracle.classify(tabs, b) for b in batches]
    plans = []
    for b in batches:
        wire = b.pack_wire()
        plans.append(
            (res.prepare_packed(wire, False), b)
        )
    # materialize out of dispatch order: 3, 0, 5, 1, 4, 2
    for i in (3, 0, 5, 1, 4, 2):
        out = res.classify_prepared(plans[i][0], apply_stats=False).result()
        assert np.array_equal(out.results, refs[i].results), f"plan {i}"


@pytest.mark.slow
def test_resident_scheduler_ping_pong_staging(shared):
    """The continuous scheduler's prepare/launch ping-pong over the
    resident path: staged resident plans chain the donated buffers in
    dispatch order; served verdicts stay oracle-identical."""
    from infw.scheduler import (
        ContinuousScheduler, DeadlinePolicy, ServiceModel,
    )

    tabs, res, _multi = shared
    res.flow.reset()
    batch = testing.random_batch_fast(np.random.default_rng(31), tabs, 600)
    ref = oracle.classify(tabs, batch)
    sched = ContinuousScheduler(
        res, DeadlinePolicy(0.5, 128, service=ServiceModel()),
        pipeline_depth=3, stage_depth=2,
    )
    out = sched.serve(batch, np.zeros(len(batch)))
    assert np.array_equal(out.results, ref.results)


@pytest.mark.slow
def test_resident_mesh_parity():
    """The mesh classifier inherits the resident path via the same
    jitted factories (GSPMD over the replicated placement): parity vs
    the CPU oracle on the 8-virtual-device pool."""
    from infw.backend.mesh import MeshTpuClassifier

    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device pool")
    tabs = _tables()
    clf = MeshTpuClassifier(
        data_shards=4, rules_shards=1, interpret=True, force_path="trie",
        flow_table=FlowConfig.make(entries=512), resident=True,
    )
    clf.load_tables(tabs)
    batch = testing.random_batch_fast(np.random.default_rng(5), tabs, 64)
    ref = oracle.classify(tabs, batch)
    for _ in range(2):
        o = clf.classify(batch, apply_stats=False)
        assert np.array_equal(o.results, ref.results)
    assert clf.resident_counters()["resident_dispatches_total"] >= 2
    clf.close()


# --- staleness: patches refresh the pool context -----------------------------


@pytest.mark.slow
def test_resident_serves_new_tables_after_patch(shared):
    tabs, _r, _m = shared
    res, _m2 = _pair(tabs, force_path="trie")
    _m2.close()
    batch = testing.random_batch_fast(np.random.default_rng(41), tabs, 64)
    res.classify(batch, apply_stats=False)  # populate the cache
    inc = IncrementalTables.from_content(dict(tabs.content), rule_width=4)
    k = next(iter(tabs.content))
    inc.apply({}, [k])
    snap = inc.snapshot()
    res.load_tables(snap, dirty_hint=inc.peek_dirty())
    ref = oracle.classify(snap, batch)
    o = res.classify(batch, apply_stats=False)
    assert np.array_equal(o.results, ref.results), (
        "resident path served stale tables after an incremental patch"
    )
    res.close()


@pytest.mark.slow
def test_resident_stale_defect_diverges(shared):
    """The injected residentstale defect (dropped generation refresh on
    the pool context) must produce oracle divergence after a patch —
    the signal the statecheck acceptance shrinks on."""
    tabs = shared[0]
    batch = testing.random_batch_fast(np.random.default_rng(41), tabs, 64)
    inc = IncrementalTables.from_content(dict(tabs.content), rule_width=4)
    # delete every entry: the post-patch oracle must diverge somewhere
    inc.apply({}, list(tabs.content))
    snap = inc.snapshot()
    resident_mod._INJECT_RESIDENT_STALE_BUG = True
    try:
        res, _m = _pair(tabs, force_path="trie")
        _m.close()
        res.classify(batch, apply_stats=False)
        res.load_tables(snap)
        ref = oracle.classify(snap, batch)
        o = res.classify(batch, apply_stats=False)
        assert not np.array_equal(o.results, ref.results), (
            "injected stale-context defect did not diverge"
        )
        res.close()
    finally:
        resident_mod._INJECT_RESIDENT_STALE_BUG = False


# --- zero-recompile / zero-alloc lifecycle ----------------------------------


@pytest.mark.slow
def test_resident_zero_recompile_zero_alloc_steady_state(shared):
    tabs, res, _multi = shared
    res.flow.reset()
    res.mark_resident_warm()
    cfg = res.flow.config
    fns = [
        jaxpath.jitted_resident_step(cfg.entries, cfg.ways, "trie",
                                     v4, None, 0, False)
        for v4 in (False, True)
    ]
    cache0 = sum(f._cache_size() for f in fns)
    batch = testing.random_batch_fast(np.random.default_rng(51), tabs, 64)
    w7 = batch.pack_wire()
    v4b = batch.take(np.nonzero(np.asarray(batch.kind) != 2)[0])
    v4b.ip_words[:, 1:] = 0
    w4 = v4b.pack_wire_v4()[:32]
    for i in range(50):
        res.classify_prepared(
            res.prepare_packed(w7[:64], False), apply_stats=False
        ).result()
        res.classify_prepared(
            res.prepare_packed(w4, True), apply_stats=False
        ).result()
    grew = sum(f._cache_size() for f in fns) - cache0
    assert grew == 0, f"{grew} resident recompiles on the warm lifecycle"
    assert res.resident.steady_allocs() == 0, (
        f"{res.resident.steady_allocs()} pool allocations on the warmed "
        "serving path"
    )


@pytest.mark.slow
def test_rung32_pinned_input_regression(shared):
    """Small-batch anomaly pin: an old record's 11.77 ms pinned-input
    p50 @batch=32 beside 0.25 ms @batch=128 was a measurement artifact —
    the ladder's FIRST-measured shape (32) paid its jit specialization
    plus its executable's first-dispatch cost inside the timed loop, not
    a rung-32 dataplane bug.  The fix is the full-ladder prewarm before any timed sample;
    this test pins it with the _cache_size lint: after the prewarm, a
    pinned-device-input sweep at 32/64/128 (the r05 shapes, dense wire
    path AND the resident serving path) must perform ZERO compiles, so
    nothing shape-driven can ever land inside a timed rung again."""
    tabs, res, _multi = shared
    res.flow.reset()
    # the bench_wire_latency dense-wire factory (the r05 tier's path)
    dt = jaxpath.device_tables(tabs)
    fn_wire = jaxpath.jitted_classify_wire(False)
    for bs in (32, 64, 128):
        w = jax.device_put(
            testing.random_batch_fast(
                np.random.default_rng(bs), tabs, bs
            ).pack_wire()
        )
        np.asarray(fn_wire(dt, w)[0])
    cfg = res.flow.config
    fns = [fn_wire] + [
        jaxpath.jitted_resident_step(cfg.entries, cfg.ways, "trie",
                                     v4, None, 0, False)
        for v4 in (False, True)
    ]
    cache0 = sum(f._cache_size() for f in fns)
    for bs in (32, 64, 128):
        batch = testing.random_batch_fast(
            np.random.default_rng(100 + bs), tabs, bs
        )
        w_np = batch.pack_wire()
        dw = jax.device_put(w_np)  # pinned device input
        for _ in range(3):
            np.asarray(fn_wire(dt, dw)[0])
            res.classify_prepared(
                res.prepare_packed(w_np, False), apply_stats=False
            ).result()
    grew = sum(f._cache_size() for f in fns) - cache0
    assert grew == 0, (
        f"{grew} compiles during the pinned-input sweep — the BENCH_r05 "
        "anomaly condition (first-dispatch cost inside a timed rung) "
        "has regressed"
    )


# --- ingest ring -------------------------------------------------------------


def test_ring_roundtrip_wraparound_flags(tmp_path):
    p = str(tmp_path / "r.ring")
    ring = IngestRing.create(p, slots=4, slot_packets=64)
    prod = IngestRing.attach(p)
    for i in range(11):
        w = np.full((16, 4 if i % 2 else 7), i, np.uint32)
        fl = np.full(16, i, np.int32) if i % 3 == 0 else None
        prod.push(w, v4_only=(i % 2 == 1), tcp_flags=fl)
        c = ring.pop(timeout=2.0)
        assert c is not None
        assert np.array_equal(c.wire, w)
        assert c.v4_only == (i % 2 == 1)
        assert (c.tcp_flags is None) == (i % 3 != 0)
        if c.tcp_flags is not None:
            assert (c.tcp_flags == i).all()
        c.release()
    assert ring.pop(timeout=0.05) is None
    ring.close()
    prod.close()


def test_ring_backpressure_and_slot_hold(tmp_path):
    """A full ring blocks the producer; a popped-but-unreleased chunk's
    slot is NOT reclaimed (its views double as H2D staging buffers)."""
    import threading
    import time as _t

    p = str(tmp_path / "r.ring")
    ring = IngestRing.create(p, slots=2, slot_packets=16)
    prod = IngestRing.attach(p)
    prod.push(np.full((4, 4), 1, np.uint32))
    prod.push(np.full((4, 4), 2, np.uint32))
    with pytest.raises(TimeoutError):
        prod.push(np.full((4, 4), 3, np.uint32), timeout=0.05)
    held = ring.pop(timeout=1.0)
    # tail has NOT advanced: the producer still blocks
    with pytest.raises(TimeoutError):
        prod.push(np.full((4, 4), 3, np.uint32), timeout=0.05)
    view_before = held.wire.copy()
    t = threading.Thread(
        target=lambda: prod.push(np.full((4, 4), 3, np.uint32),
                                 timeout=2.0)
    )
    t.start()
    _t.sleep(0.05)
    held.release()
    t.join(timeout=2.0)
    assert not t.is_alive()
    # the held view was never overwritten while in flight
    assert np.array_equal(view_before, np.full((4, 4), 1, np.uint32))
    for want in (2, 3):
        c = ring.pop(timeout=1.0)
        assert (c.wire == want).all()
        c.release()
    ring.close()
    prod.close()


def test_ring_flagless_record_at_full_capacity(tmp_path):
    """Review finding: pop()'s sanity bound must use the RECORD's own
    layout — a flag-less record legally holds more packets than a
    flagged one of the same slot size and must not be dropped as
    corrupt."""
    p = str(tmp_path / "r.ring")
    ring = IngestRing.create(p, slots=2, slot_packets=64)
    n = ring.max_packets(4, with_flags=False)
    assert n > ring.max_packets(4, with_flags=True)
    ring.push(np.full((n, 4), 9, np.uint32))
    c = ring.pop(timeout=1.0)
    assert c is not None and c.wire.shape == (n, 4) and (c.wire == 9).all()
    c.release()
    ring.close()


def test_ring_corrupt_record_preserves_inflight_slots(tmp_path):
    """Review finding: a poison (corrupt) record must advance only the
    READ cursor — the tail (producer-visible free boundary) moves past
    it only when the in-order release protocol reaches it, so earlier
    popped-but-unreleased slot views are never overwritten and later
    releases never wedge."""
    p = str(tmp_path / "r.ring")
    ring = IngestRing.create(p, slots=4, slot_packets=16)
    ring.push(np.full((4, 4), 1, np.uint32))
    ring.push(np.full((4, 4), 2, np.uint32))
    ring.push(np.full((4, 4), 3, np.uint32))
    held = ring.pop(timeout=1.0)  # seq 0, unreleased (in-flight H2D)
    # corrupt record 1 in place (impossible width)
    off = ring._slot_off(1)
    np.frombuffer(ring._mm, np.uint32, 4, off + 8)[1] = 99
    with pytest.raises(ValueError):
        ring.pop(timeout=0.1)
    # the tail must NOT have jumped past the in-flight seq-0 slot
    assert ring.tail == 0
    ok = ring.pop(timeout=1.0)  # seq 2 still readable
    assert (ok.wire == 3).all()
    # releases proceed in order and drain through the poison slot
    held.release()
    assert ring.tail == 2  # 0 released, poison 1 drained through
    ok.release()
    assert ring.tail == 3
    ring.close()


def test_loadgen_ring_producer_deterministic(tmp_path):
    """tools/loadgen.py --ring drives a real ring from a subprocess;
    two runs with the same seed produce byte-identical record streams."""
    streams = []
    for run in range(2):
        p = str(tmp_path / f"lg{run}.ring")
        ring = IngestRing.create(p, slots=64, slot_packets=256)
        proc = subprocess.run(
            [sys.executable, os.path.join(REPO, "tools", "loadgen.py"),
             "--ring", p, "--rate", "1e6", "--n", "1024",
             "--file-packets", "256", "--seed", "11", "--ifindex", "2"],
            capture_output=True, text=True, timeout=120, cwd=REPO,
        )
        assert proc.returncode == 0, proc.stderr
        recs = []
        while True:
            c = ring.pop(timeout=0.2)
            if c is None:
                break
            recs.append((c.wire.copy(), c.v4_only))
            c.release()
        assert sum(len(w) for w, _ in recs) == 1024
        streams.append(recs)
        ring.close()
    for (wa, va), (wb, vb) in zip(*streams):
        assert va == vb and np.array_equal(wa, wb)


@pytest.mark.slow
def test_daemon_ring_ingest_resident(tmp_path):
    """Daemon --ring mode: records pushed by a producer are classified
    through the resident path; ring_* and resident_* gauges export on
    /metrics; slots release after materialize."""
    from infw.daemon import Daemon

    ringp = str(tmp_path / "ingest.ring")
    daemon = Daemon(
        state_dir=str(tmp_path), node_name="n1", backend="tpu",
        resident=True, ring=ringp, metrics_port=0, health_port=0,
        file_poll_interval_s=10.0,
        flow_table=FlowConfig.make(entries=ENTRIES),
    )
    try:
        tabs = _tables()
        clf = daemon.syncer._factory()
        clf.load_tables(tabs)
        daemon.syncer._classifier = clf
        assert clf.resident is not None
        prod = IngestRing.attach(ringp)
        batch = testing.random_batch_fast(
            np.random.default_rng(61), tabs, 256
        )
        for lo in range(0, 256, 64):
            w, v4 = batch.pack_wire_subset(
                np.arange(lo, lo + 64, dtype=np.int64)
            )
            prod.push(w, v4_only=v4)
        n = daemon.process_ring_once(budget=10**9)
        assert n == 256
        assert daemon.ingest_ring.tail == daemon.ingest_ring.head
        text = daemon.metrics_registry.render_text()
        assert "ring_popped_total 4" in text
        assert "resident_dispatches_total" in text
        # stats landed exactly once (apply_stats=True on the ring path)
        snap = clf.stats.snapshot()  # (MAX_TARGETS, 4) int64
        ref = oracle.classify(tabs, batch)
        from infw.testing import stats_dict_from_array

        assert stats_dict_from_array(snap) == ref.stats
        prod.close()
    finally:
        daemon.stop()


def test_daemon_resident_flag_validation(tmp_path):
    """Launch validation: --resident on the cpu backend is a usage
    error; --ring into a missing directory is a usage error."""
    from infw.daemon import main as daemon_main

    with pytest.raises(SystemExit) as e:
        daemon_main(["--state-dir", str(tmp_path), "--node-name", "n",
                     "--backend", "cpu", "--resident"])
    assert e.value.code == 2
    with pytest.raises(SystemExit) as e:
        daemon_main(["--state-dir", str(tmp_path), "--node-name", "n",
                     "--backend", "tpu",
                     "--ring", str(tmp_path / "no" / "dir" / "x.ring")])
    assert e.value.code == 2


# --- donation lint -----------------------------------------------------------


@pytest.mark.slow
def test_donation_lint_passes_on_resident_entrypoints():
    from infw.analysis import jaxcheck
    from infw.kernels import kernel_entrypoints

    eps = {e.name: e for e in kernel_entrypoints()}
    assert "classify-wire/resident-fused" in eps
    assert "classify-wire/resident-ring-fused" in eps
    for name in ("classify-wire/resident-fused",
                 "classify-wire/resident-ring-fused"):
        ep = eps[name]
        assert ep.donate == (0, 3)
        findings = jaxcheck._donation_lint(ep, (64,))
        errs = [f for f in findings if f.severity == "error"]
        assert not errs, errs


def test_donation_lint_fails_on_defect_and_undeclared():
    from infw.analysis import jaxcheck
    from infw.kernels import KernelEntrypoint

    ep = jaxcheck.donation_defect_entrypoint()
    findings = jaxcheck._donation_lint(ep, (64,))
    assert any(
        f.check == "donation" and f.severity == "error" for f in findings
    ), "declared-but-unaliasable donation not flagged"
    # a resident-named entrypoint with no donate declaration is an error
    bare = KernelEntrypoint(
        "classify-wire/resident-undeclared", "xla",
        lambda b: (None, ()),
    )
    findings = jaxcheck._donation_lint(bare, (64,))
    assert any(f.severity == "error" for f in findings)


# --- statecheck resident config ---------------------------------------------


def test_statecheck_resident_config_registered():
    """The resident config is registered and resolvable (the full
    equivalence run is tier-gated: `make state-check` and the
    resident-bench gate both execute run_config('resident'); the slow
    tier runs it in-suite too)."""
    from infw.analysis import statecheck

    cfg = statecheck.CONFIGS["resident"]
    assert cfg.resident and cfg.flow > 0


@pytest.mark.slow
def test_statecheck_resident_config_green():
    from infw.analysis import statecheck

    rep = statecheck.run_config("resident", seed=1, n_ops=5,
                                shrink_on_failure=False)
    assert rep["ok"], rep.get("failure")


@pytest.mark.slow
def test_statecheck_residentstale_defect_caught():
    from infw.analysis import statecheck

    resident_mod._INJECT_RESIDENT_STALE_BUG = True
    try:
        rep = statecheck.run_config("resident", seed=0, n_ops=12,
                                    shrink_on_failure=True,
                                    max_shrink_runs=48)
    finally:
        resident_mod._INJECT_RESIDENT_STALE_BUG = False
    assert not rep["ok"], "injected residentstale defect not caught"
    assert rep["shrunk"]["ops"] <= 3


# --- native delta-encode parity ---------------------------------------------


def test_native_delta_encode_parity():
    """The C++ single-pass delta encoder must be byte-identical to the
    NumPy reference across dictionary modes, fixed/varint plans and the
    auto gate (skips when the native library is unavailable)."""
    import infw.packets as pk

    try:
        from infw.backend.cpu_ref import load_library

        load_library()
    except Exception:
        pytest.skip("native library unavailable")

    def numpy_encode(w, cap=None):
        old = pk._native_delta_unavailable
        pk._native_delta_unavailable = True
        try:
            return pk.encode_delta_wire(w, cap)
        finally:
            pk._native_delta_unavailable = old

    from infw.packets import PacketBatch

    checked = 0
    for seed in range(12):
        r = np.random.default_rng(seed)
        n = int(r.integers(1, 400))
        b = PacketBatch(
            kind=np.ones(n, np.int32),
            l4_ok=np.ones(n, np.int32),
            ifindex=r.integers(1, 1 + [1, 3, 15, 16][seed % 4], n).astype(
                np.int32
            ),
            ip_words=np.concatenate(
                [r.integers(0, [100, 1 << 16, 1 << 30][seed % 3],
                            (n, 1)).astype(np.uint32),
                 np.zeros((n, 3), np.uint32)], axis=1,
            ),
            proto=np.asarray([6, 17, 1, 58], np.int32)[
                r.integers(0, 4, n)
            ],
            dst_port=r.integers(0, [1, 40, 70000][seed % 3], n).astype(
                np.int32
            ),
            icmp_type=r.integers(0, 4, n).astype(np.int32),
            icmp_code=r.integers(0, 3, n).astype(np.int32),
            pkt_len=r.integers(60, 1500, n).astype(np.int32),
        )
        w = b.pack_wire_v4()
        for cap in (None, 8.0, 1.0):
            a = pk._encode_delta_native(w, cap)
            ref = numpy_encode(w, cap)
            assert (a is None) == (ref is None), (seed, cap)
            if a is None:
                continue
            for f in ("payload", "dict_vals", "ifmap", "perm"):
                assert np.array_equal(getattr(a, f), getattr(ref, f)), (
                    seed, cap, f,
                )
            assert (a.n, a.dict_mode, a.fixed_w, a.crc) == (
                ref.n, ref.dict_mode, ref.fixed_w, ref.crc,
            )
            checked += 1
    assert checked >= 10


# --- device stats twin -------------------------------------------------------


def test_result_stats_matches_host_stats():
    """jaxpath.result_stats (the in-program stats the fused paths use)
    must merge to exactly daemon.stats_from_results on the same
    verdicts + pkt_len (the wire8/resident readback contract)."""
    from infw.daemon import stats_from_results

    tabs = _tables()
    batch = testing.random_batch_fast(np.random.default_rng(71), tabs, 256)
    ref = oracle.classify(tabs, batch)
    db = jaxpath.device_batch(batch)
    dev = jax.jit(jaxpath.result_stats)(
        jax.device_put(ref.results.astype(np.uint32)), db
    )
    merged = jaxpath.merge_stats_host(np.asarray(dev))
    host = stats_from_results(ref.results, np.asarray(batch.pkt_len))
    assert np.array_equal(merged, host)
