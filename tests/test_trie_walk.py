"""The trie path at a routed table's shape: the blocklist-100k
configuration's prefix-length mix (``benchmark/configs/blocklist-100k.json``)
cut to 6,000 entries, above the dense limit, with nested IPv4 /16-/24 and
IPv6 /29-/48 prefixes.  Covers the resident ring path against the native
CPU reference on records that mix families, the walk's named stages in
the compiled resident step, the resident pool's walk counters and trie
gauges, and the program spans of a NodeState load's phases."""
import glob
import json
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from infw import testing
from infw.backend.cpu_ref import CpuRefClassifier
from infw.daemon import Daemon
from infw.flow import FlowConfig
from infw.interfaces import Interface, InterfaceRegistry
from infw.kernels import jaxpath, pallas_dense
from infw.ring import IngestRing
from test_stages import STRUCTURAL, opcodes, resident_args, unnamed

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "benchmark", "configs", "blocklist-100k.json")
ENTRIES = 6000
WALK_STAGES = {"lpm_root", "lpm_level", "lpm_resolve"}
RECORD = 256


def routed_tables(n: int = ENTRIES):
    """A table of ``n`` entries in the configuration's shape, on eth0."""
    t = json.load(open(CONFIG))["table"]
    return testing.random_tables_fast(
        np.random.default_rng(27), n_entries=n, ifindexes=(2,),
        width=t["rule_width"], v6_fraction=t["v6_fraction"],
        group_size=t["group_size"], v4_prefix_lens=t["v4_prefix_lens"],
        v6_prefix_lens=t["v6_prefix_lens"],
    )


@pytest.fixture(scope="module")
def routed():
    tabs = routed_tables()
    assert tabs.num_entries > pallas_dense.MAX_DENSE_TARGETS
    ml = np.asarray(tabs.mask_len[:tabs.num_entries])
    assert {16, 24, 29, 48} <= set(ml.tolist()) and ml.max() == 48
    return tabs


def _daemon(tmp_path, **kw):
    registry = InterfaceRegistry()
    registry.add(Interface(name="eth0", index=2))
    return Daemon(
        state_dir=str(tmp_path), node_name="n1", backend="tpu",
        metrics_port=0, health_port=0, file_poll_interval_s=10.0,
        registry=registry, **kw,
    )


def test_resident_ring_trie_matches_cpu_reference(tmp_path, routed):
    """Mixed-family records through the daemon's ring and the resident
    step on the trie path: every verdict and the statistics delta equal
    the native CPU reference's."""
    daemon = _daemon(tmp_path, resident=True, ring=str(tmp_path / "ring"),
                     flow_table=FlowConfig.make(entries=512))
    try:
        clf = daemon.syncer._factory()
        clf.load_tables(routed)
        daemon.syncer._classifier = clf
        assert clf.active_path == "trie"
        assert len(clf.resident.context(clf).tdev.trie_levels) == 5
        got = {}
        daemon.ring_verdict_sink = lambda seq, res: got.setdefault(
            int(seq), np.asarray(res).copy())
        batch = testing.random_batch_fast(
            np.random.default_rng(28), routed, 4 * RECORD)
        prod = IngestRing.attach(daemon.ingest_ring.path)
        seqs = []
        for lo in range(0, 4 * RECORD, RECORD):
            idx = np.arange(lo, lo + RECORD, dtype=np.int64)
            kind = batch.kind[idx]
            assert (kind == 1).any() and (kind == 2).any()
            wire, v4 = batch.pack_wire_subset(idx)
            seqs.append(prod.push(wire, v4_only=v4))
        assert daemon.process_ring_once(budget=10**9) == 4 * RECORD
        prod.close()
        ref = CpuRefClassifier()
        ref.load_tables(routed)
        want = ref.classify(batch, apply_stats=False)
        assert np.array_equal(np.concatenate([got[s] for s in seqs]),
                              want.results)
        assert np.array_equal(clf.stats.snapshot(), want.stats_delta)
        ctr = clf.resident_counters()
        assert ctr["resident_path_trie_dispatches_total"] == 4
        assert ctr["resident_trie_lane_levels_walked_total"] == 4 * RECORD * 5
    finally:
        daemon.stop()


def test_walk_counters_add_up(routed):
    """One known record of 40 IPv4, 20 IPv6 and 4 other lanes walks all 5
    levels on every lane and needs 3 on an IPv4 lane and 5 on an IPv6
    one; an IPv4-only record walks 3.  The gauges give the table's trie."""
    from infw.backend.tpu import TpuClassifier

    clf = TpuClassifier(interpret=True, resident=True,
                        flow_table=FlowConfig.make(entries=512))
    try:
        clf.load_tables(routed)
        batch = testing.random_batch_fast(np.random.default_rng(29), routed, 64)
        batch.kind[:] = np.repeat([1, 2, 0, 3], [40, 20, 2, 2]).astype(np.int32)
        wire, _v4 = batch.pack_wire_subset(np.arange(64, dtype=np.int64))
        clf.classify_prepared(clf.prepare_packed(wire, False)).result()
        ctr = clf.resident_counters()
        assert ctr["resident_path_trie_dispatches_total"] == 1
        assert ctr["resident_path_dense_dispatches_total"] == 0
        assert ctr["resident_trie_lane_levels_walked_total"] == 64 * 5
        assert ctr["resident_trie_lane_levels_needed_total"] == 40 * 3 + 20 * 5
        v4 = testing.random_batch_fast(np.random.default_rng(30), routed, 64)
        v4.kind[:] = 1
        wire, _v4 = v4.pack_wire_subset(np.arange(64, dtype=np.int64))
        clf.classify_prepared(clf.prepare_packed(wire, True)).result()
        ctr = clf.resident_counters()
        assert ctr["resident_trie_lane_levels_walked_total"] == 64 * 5 + 64 * 3
        assert ctr["resident_trie_lane_levels_needed_total"] == (
            40 * 3 + 20 * 5 + 64 * 3)
        levels = clf.resident.context(clf).tdev.trie_levels
        assert ctr["resident_trie_levels"] == 5
        assert [ctr[f"resident_trie_level{i}_rows"] for i in range(5)] == [
            int(lv.shape[0]) for lv in levels]
        assert ctr["resident_table_device_bytes"] > sum(
            int(lv.nbytes) for lv in levels)
    finally:
        clf.close()


def _trie_step_text(tabs, b: int = 256) -> str:
    cfg, args = resident_args(b, False)
    args = args[:4] + (jaxpath.device_tables(tabs, pad=True),) + args[5:]
    fn = jaxpath.jitted_resident_step(cfg.entries, cfg.ways, "trie")
    return fn.lower(*args).compile().as_text()


def test_trie_step_maps_walk_to_its_stages(routed):
    """The compiled trie resident step puts the root gather, the level
    gathers and the resolve in lpm_root, lpm_level and lpm_resolve: no
    instruction that does work is left unnamed, and no gather is left in
    the bare lpm stage."""
    text = _trie_step_text(routed)
    assert unnamed(text) == []
    stages = jaxpath.hlo_stage_map(text)
    assert WALK_STAGES <= set(stages.values())
    ops = opcodes(text)
    assert not [n for n, s in stages.items()
                if s == "lpm" and ops.get(n) == "gather"]
    assert "unmapped" not in stages.values()


def test_dense_step_has_no_walk_stage():
    cfg, args = resident_args(256, False)
    fn = jaxpath.jitted_resident_step(cfg.entries, cfg.ways, "dense")
    text = fn.lower(*args).compile().as_text()
    stages = jaxpath.hlo_stage_map(text)
    assert "lpm" in stages.values()
    assert not WALK_STAGES & set(stages.values())
    assert [n for n, s in stages.items() if s == "other"
            and opcodes(text).get(n) not in STRUCTURAL] == []


LOAD_SPANS = ("infw.load.parse", "infw.load.compile", "infw.load.upload",
              "infw.load.walk", "infw.load.checkpoint")


def _nodestate(n: int) -> dict:
    entries = [{
        "sourceCIDRs": [f"10.{i // 256}.{i % 256}.0/24"],
        "rules": [{"order": 1, "protocolConfig": {"protocol": "TCP",
                                                  "tcp": {"ports": 80}},
                   "action": "Deny"}],
    } for i in range(n)]
    return {"metadata": {"name": "n1"},
            "spec": {"interfaceIngressRules": {"eth0": entries}}}


@pytest.mark.parametrize("traced", [True, False], ids=["trace", "no-trace"])
def test_load_phase_spans(tmp_path, traced):
    """A NodeState load under --trace records each of its phases as an
    infw.load.* program span; without --trace it records none."""
    daemon = _daemon(tmp_path, trace=traced)
    try:
        with open(os.path.join(daemon.nodestates_dir, "n1.json"), "w") as f:
            json.dump(_nodestate(32), f)
        trace_dir = str(tmp_path / "trace")
        with jax.profiler.trace(trace_dir):
            daemon.scan_nodestates_once()
        assert daemon.syncer.classifier.tables.num_entries == 32
    finally:
        daemon.stop()
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for ln in plane.lines for ev in ln.events
             if ev.name.startswith("infw.load.")]
    if traced:
        assert set(names) == set(LOAD_SPANS)
        assert names.count("infw.load.parse") == 2
    else:
        assert names == []
