"""Daemon tests: NodeState controller semantics (mock seam + finalizer
dance, the port of ingressnodefirewallnodestate_controller_test.go) and a
file-driven daemon e2e — state dir in, verdicts/metrics/events out (the
role of the reference's functional e2e suite on a single node)."""
import json
import os
import time
import urllib.request

import numpy as np
import pytest

import infw.nodestate_controller as nsc_mod
from infw.constants import IPPROTO_TCP
from infw.daemon import Daemon, read_frames_file, write_frames_file
from infw.interfaces import Interface, InterfaceRegistry
from infw.nodestate_controller import (
    INGRESS_NODE_FIREWALL_FINALIZER,
    NodeStateReconciler,
)
from infw.obs.pcap import build_frame
from infw.spec import (
    ACTION_DENY,
    IngressNodeFirewallNodeState,
    IngressNodeFirewallNodeStateSpec,
    ObjectMeta,
)
from infw.store import InMemoryStore, NotFoundError
from test_syncer import ingress, tcp_rule

NS = "ingress-node-firewall-system"
NODE = "tpu-worker-0"


class MockSyncer:
    """ebpfSingletonMock (ingressnodefirewallnodestate_controller_test.go:22-31):
    captures the last rules map instead of touching the dataplane."""

    def __init__(self):
        self.calls = []

    def sync_interface_ingress_rules(self, rules, is_delete):
        self.calls.append((rules, is_delete))


def node_state(name=NODE, namespace=NS, rules=None):
    return IngressNodeFirewallNodeState(
        metadata=ObjectMeta(name=name, namespace=namespace),
        spec=IngressNodeFirewallNodeStateSpec(
            interface_ingress_rules=rules
            or {"dummy0": [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]}
        ),
    )


@pytest.fixture
def store():
    return InMemoryStore()


@pytest.fixture
def mock_syncer(monkeypatch):
    m = MockSyncer()
    monkeypatch.setattr(nsc_mod, "mock", m)
    yield m
    monkeypatch.setattr(nsc_mod, "mock", None)


def test_nodestate_filters_other_nodes(store, mock_syncer):
    r = NodeStateReconciler(store, syncer=None, node_name=NODE, namespace=NS)
    store.create(node_state(name="other-node"))
    r.reconcile("other-node", NS)      # not our node
    r.reconcile(NODE, "other-ns")      # not our namespace
    assert mock_syncer.calls == []


def test_nodestate_sync_and_finalizer(store, mock_syncer):
    r = NodeStateReconciler(store, syncer=None, node_name=NODE, namespace=NS)
    store.create(node_state())
    r.reconcile(NODE, NS)
    assert len(mock_syncer.calls) >= 1
    rules, is_delete = mock_syncer.calls[-1]
    assert not is_delete and "dummy0" in rules
    obj = store.get(IngressNodeFirewallNodeState.KIND, NODE, NS)
    assert INGRESS_NODE_FIREWALL_FINALIZER in obj.metadata.finalizers


def test_nodestate_deletion_syncs_delete_then_removes_finalizer(store, mock_syncer):
    r = NodeStateReconciler(store, syncer=None, node_name=NODE, namespace=NS)
    store.create(node_state())
    r.reconcile(NODE, NS)
    store.delete(IngressNodeFirewallNodeState.KIND, NODE, NS)  # sets deletion ts
    r.reconcile(NODE, NS)
    assert mock_syncer.calls[-1][1] is True  # is_delete
    with pytest.raises(NotFoundError):  # finalizer removed -> object GC'd
        store.get(IngressNodeFirewallNodeState.KIND, NODE, NS)


def test_nodestate_missing_object_is_noop(store, mock_syncer):
    r = NodeStateReconciler(store, syncer=None, node_name=NODE, namespace=NS)
    r.reconcile(NODE, NS)
    assert mock_syncer.calls == []


def test_nodestate_deletion_retry_after_transient_sync_failure(store, monkeypatch):
    """A transient failure of the is_delete sync must not wedge the object:
    a repeated delete() re-notifies watchers so the finalizer teardown is
    retried (the role controller-runtime's error requeue plays in the
    reference)."""
    from infw.syncer import SyncError as SE

    class FlakySyncer:
        def __init__(self):
            self.fail_next = 1

        def sync_interface_ingress_rules(self, rules, is_delete):
            if is_delete and self.fail_next > 0:
                self.fail_next -= 1
                raise SE("transient")

    flaky = FlakySyncer()
    r = NodeStateReconciler(store, syncer=flaky, node_name=NODE, namespace=NS)
    store.watch(
        IngressNodeFirewallNodeState.KIND,
        lambda ev, obj: _safe_reconcile(r, obj),
    )
    store.create(node_state())
    # first delete: teardown raises, finalizer stays, object wedged-but-alive
    store.delete(IngressNodeFirewallNodeState.KIND, NODE, NS)
    assert store.get(IngressNodeFirewallNodeState.KIND, NODE, NS)
    # retry (manager's next full reconcile deletes stale objects again)
    store.delete(IngressNodeFirewallNodeState.KIND, NODE, NS)
    with pytest.raises(NotFoundError):
        store.get(IngressNodeFirewallNodeState.KIND, NODE, NS)


def _safe_reconcile(r, obj):
    from infw.syncer import SyncError as SE

    try:
        r.reconcile(obj.metadata.name, obj.metadata.namespace)
    except SE:
        pass


# --- frames-file format -------------------------------------------------------

def test_frames_file_roundtrip(tmp_path):
    frames = [
        build_frame("192.0.2.1", "10.0.0.1", IPPROTO_TCP, 1, 80),
        build_frame("192.0.2.2", "10.0.0.1", IPPROTO_TCP, 2, 81),
    ]
    path = str(tmp_path / "x.frames")
    write_frames_file(path, frames, ifindex=[2, 3])
    got_frames, got_idx = read_frames_file(path)
    assert got_frames == frames and got_idx == [2, 3]


# --- daemon e2e ---------------------------------------------------------------

@pytest.fixture
def daemon(tmp_path):
    reg = InterfaceRegistry()
    reg.add(Interface(name="dummy0", index=10))
    d = Daemon(
        state_dir=str(tmp_path / "state"),
        node_name=NODE,
        namespace=NS,
        backend="cpu",
        poll_period_s=0.05,
        debug_lookup=True,
        registry=reg,
        metrics_port=0,
        health_port=0,
        file_poll_interval_s=0.02,
    )
    d.start()
    yield d
    d.stop()


def _wait(cond, timeout=5.0):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return True
        time.sleep(0.02)
    return False


def _http_get(port, path):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}", timeout=5) as r:
        return r.read().decode()


def test_daemon_end_to_end(daemon):
    # 1. apply desired state via the state dir (the "kubectl apply")
    ns_doc = node_state().to_dict()
    path = os.path.join(daemon.nodestates_dir, f"{NODE}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(ns_doc, f)
    os.replace(path + ".tmp", path)
    assert _wait(lambda: daemon.syncer.classifier is not None
                 and daemon.syncer.classifier.tables is not None)
    assert daemon.syncer.attached_interfaces() == {"dummy0"}

    # 2. replay traffic through the ingest dir
    frames = [
        build_frame("10.1.2.3", "203.0.113.1", IPPROTO_TCP, 999, 80),  # deny
        build_frame("10.1.2.3", "203.0.113.1", IPPROTO_TCP, 999, 81),  # pass
    ]
    write_frames_file(os.path.join(daemon.ingest_dir, "t1.frames"), frames, 10)
    verdict_path = os.path.join(daemon.out_dir, "t1.frames.verdicts.json")
    assert _wait(lambda: os.path.exists(verdict_path))
    with open(verdict_path) as f:
        summary = json.load(f)
    assert summary["drop"] == 1 and summary["pass"] == 1

    # 3. metrics endpoint (e2e.go:1143-1356 curls the daemon /metrics)
    port = daemon.actual_metrics_port
    daemon.stats.update_metrics(daemon.syncer.classifier)
    text = _http_get(port, "/metrics")
    assert "ingressnodefirewall_node_packet_deny_total 1" in text
    assert _http_get(port, "/healthz") == "ok"

    # 4. deny events land in the event log (sidecar-stdout equivalent)
    assert _wait(lambda: os.path.exists(daemon.events_path)
                 and "ruleId 1 action Drop" in open(daemon.events_path).read())
    content = open(daemon.events_path).read()
    assert "\tipv4 src addr 10.1.2.3" in content
    assert "\ttcp srcPort 999 dstPort 80" in content

    # 5. debug lookup buffer exposed over HTTP
    keys = json.loads(_http_get(port, "/debug/lookup-keys"))
    assert len(keys) == 2 and keys[0]["ifindex"] == 10

    # 6. state file deletion = CR deletion -> dataplane reset
    os.remove(os.path.join(daemon.nodestates_dir, f"{NODE}.json"))
    assert _wait(lambda: daemon.syncer.classifier is None)


def test_daemon_restart_readopts(tmp_path):
    reg = InterfaceRegistry()
    reg.add(Interface(name="dummy0", index=10))
    state = str(tmp_path / "state")
    kw = dict(state_dir=state, node_name=NODE, namespace=NS, backend="cpu",
              registry=reg, metrics_port=0, health_port=0,
              file_poll_interval_s=0.02, poll_period_s=0.05)
    d = Daemon(**kw)
    d.start()
    with open(os.path.join(d.nodestates_dir, f"{NODE}.json"), "w") as f:
        json.dump(node_state().to_dict(), f)
    assert _wait(lambda: d.syncer.classifier is not None
                 and d.syncer.classifier.tables is not None)
    d.stop()  # SIGTERM: detach but keep checkpoint

    d2 = Daemon(**kw)
    d2.start()
    try:
        # first sync (same file still present) re-adopts from checkpoint
        assert _wait(lambda: d2.syncer.classifier is not None
                     and d2.syncer.classifier.tables is not None)
        assert d2.syncer.attached_interfaces() == {"dummy0"}
    finally:
        d2.stop()


def test_schema_invalid_nodestate_file_rejected_and_isolated(daemon):
    """The state-dir protocol has no API server: the daemon applies the
    schema tier itself, and a persistently bad file must not abort the
    scan or block a *different* file for the same node (ADVICE r1)."""
    bad = node_state().to_dict()
    bad["spec"]["interfaceIngressRules"]["dummy0"][0]["rules"][0][
        "protocolConfig"]["protocol"] = "Tcp"
    # Different filename, same metadata.name -> still targets this node.
    path = os.path.join(daemon.nodestates_dir, "aaa-bad.json")
    with open(path + ".tmp", "w") as f:
        json.dump(bad, f)
    os.replace(path + ".tmp", path)
    time.sleep(0.2)
    # never synced: schema tier rejected it before compile
    assert daemon.syncer.classifier is None or daemon.syncer.classifier.tables is None

    # the bad file stays on disk; a good file later in sort order must
    # still be scanned and synced
    good = node_state().to_dict()
    p2 = os.path.join(daemon.nodestates_dir, f"{NODE}.json")
    with open(p2 + ".tmp", "w") as f:
        json.dump(good, f)
    os.replace(p2 + ".tmp", p2)
    assert _wait(lambda: daemon.syncer.classifier is not None
                 and daemon.syncer.classifier.tables is not None)
    assert os.path.exists(path)


def test_deleting_rejected_file_does_not_reset_dataplane(daemon):
    """A rejected (schema-invalid) file is not desired state; removing it
    must not be treated as CR deletion."""
    good = node_state().to_dict()
    p = os.path.join(daemon.nodestates_dir, f"{NODE}.json")
    with open(p + ".tmp", "w") as f:
        json.dump(good, f)
    os.replace(p + ".tmp", p)
    assert _wait(lambda: daemon.syncer.classifier is not None
                 and daemon.syncer.classifier.tables is not None)

    bad = node_state().to_dict()
    bad["spec"]["interfaceIngressRules"]["dummy0"][0]["rules"][0]["order"] = 0
    pbad = os.path.join(daemon.nodestates_dir, "zzz-bad.json")
    with open(pbad + ".tmp", "w") as f:
        json.dump(bad, f)
    os.replace(pbad + ".tmp", pbad)
    time.sleep(0.2)
    os.remove(pbad)
    time.sleep(0.2)
    assert daemon.syncer.classifier is not None
    assert daemon.syncer.classifier.tables is not None


def test_deny_event_with_large_ifindex(tmp_path):
    """A deny on an interface with ifindex > 65535 must flow through
    process_ingest_once and the event pipeline without the old u16
    EventHdr pack crash (struct.error)."""
    reg = InterfaceRegistry()
    reg.add(Interface(name="big0", index=70000))
    d = Daemon(
        state_dir=str(tmp_path / "state"),
        node_name=NODE, namespace=NS, backend="cpu",
        poll_period_s=0.05, registry=reg, metrics_port=0, health_port=0,
        file_poll_interval_s=0.02,
    )
    d.start()
    try:
        ns = node_state(
            rules={"big0": [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]}
        )
        p = os.path.join(d.nodestates_dir, f"{NODE}.json")
        with open(p + ".tmp", "w") as f:
            json.dump(ns.to_dict(), f)
        os.replace(p + ".tmp", p)
        assert _wait(lambda: d.syncer.classifier is not None
                     and d.syncer.classifier.tables is not None)
        frames = [build_frame("10.1.2.3", "203.0.113.1", IPPROTO_TCP, 999, 80)]
        write_frames_file(os.path.join(d.ingest_dir, "t.frames"), frames, 70000)
        vp = os.path.join(d.out_dir, "t.frames.verdicts.json")
        assert _wait(lambda: os.path.exists(vp))
        with open(vp) as f:
            assert json.load(f)["drop"] == 1
        assert _wait(lambda: os.path.exists(d.events_path)
                     and "ruleId 1 action Drop" in open(d.events_path).read())
        assert "if big0" in open(d.events_path).read()
    finally:
        d.stop()


@pytest.mark.parametrize("mode", ["deferred", "sync"])
def test_ingest_failure_isolated_and_stats_exactly_once(tmp_path, mode):
    """Failure semantics of the cross-file-batched ingest: a TRANSIENT
    fault on a merged job self-heals within the tick (per-file retry
    dispatch), while a PERSISTENT fault attributable to one file poisons
    only that file — it stays on disk for the next tick, job-mates
    complete — and statistics land exactly once across every retry.
    Covered for both failure surfaces: a deferred .result() raise (async
    TPU backend) and a synchronous classify_async raise (eager CPU)."""
    from infw.backend.base import PendingClassify

    reg = InterfaceRegistry()
    reg.add(Interface(name="dummy0", index=10))
    d = Daemon(
        state_dir=str(tmp_path / "state"),
        node_name=NODE, namespace=NS, backend="cpu",
        poll_period_s=0.05, registry=reg, metrics_port=0, health_port=0,
        file_poll_interval_s=60.0,  # drive ticks manually
    )
    try:
        with open(os.path.join(d.nodestates_dir, f"{NODE}.json"), "w") as f:
            json.dump(node_state().to_dict(), f)
        d.scan_nodestates_once()
        clf = d.syncer.classifier
        assert clf is not None and clf.tables is not None

        deny = lambda: build_frame("10.1.2.3", "203.0.113.1", IPPROTO_TCP, 999, 80)
        write_frames_file(os.path.join(d.ingest_dir, "aaa.frames"), [deny()] * 3, 10)
        write_frames_file(os.path.join(d.ingest_dir, "bbb.frames"), [deny()] * 2, 10)

        orig = clf.classify_async
        fail_when = {"pred": lambda batch: True}

        def flaky(batch, apply_stats=True):
            if fail_when["pred"](batch):
                if mode == "sync":
                    raise RuntimeError("device fell over at dispatch")

                def explode():
                    raise RuntimeError("device fell over")

                return PendingClassify(explode)
            return orig(batch, apply_stats=apply_stats)

        clf.classify_async = flaky

        # --- transient fault: fail exactly one dispatch (the merged job);
        # the per-file retries complete everything within the tick ---
        boom = {"left": 1}

        def once(batch):
            if boom["left"]:
                boom["left"] -= 1
                return True
            return False

        fail_when["pred"] = once
        assert d.process_ingest_once() == 2
        assert not os.path.exists(os.path.join(d.ingest_dir, "aaa.frames"))
        snap = clf.stats.snapshot()
        assert snap[1, 2] == 5  # 3 + 2 denies, exactly once despite the retry

        # --- persistent per-file fault: every batch containing aaa2's
        # (content-marked) packets fails — the merged job AND aaa2's
        # per-file retry — so only aaa2 is poisoned; bbb2 completes and
        # is counted once ---
        mark = lambda: build_frame("10.1.2.9", "203.0.113.1", IPPROTO_TCP, 999, 80)
        MARK_W0 = (10 << 24) | (1 << 16) | (2 << 8) | 9
        write_frames_file(os.path.join(d.ingest_dir, "aaa2.frames"), [mark()] * 3, 10)
        write_frames_file(os.path.join(d.ingest_dir, "bbb2.frames"), [deny()] * 2, 10)
        fail_when["pred"] = lambda batch: bool(
            (np.asarray(batch.ip_words)[:, 0] == MARK_W0).any()
        )
        assert d.process_ingest_once() == 1  # only bbb2
        assert os.path.exists(os.path.join(d.ingest_dir, "aaa2.frames"))
        assert not os.path.exists(
            os.path.join(d.out_dir, "aaa2.frames.verdicts.json")
        )
        assert os.path.exists(os.path.join(d.out_dir, "bbb2.frames.verdicts.json"))
        snap = clf.stats.snapshot()
        assert snap[1, 2] == 7  # +bbb2 only

        fail_when["pred"] = lambda batch: False
        assert d.process_ingest_once() == 1  # retry tick consumes aaa2
        assert not os.path.exists(os.path.join(d.ingest_dir, "aaa2.frames"))
        snap = clf.stats.snapshot()
        assert snap[1, 2] == 10  # every deny counted exactly once
    finally:
        d.stop()


def test_pipelined_ingest_multi_chunk(tmp_path):
    """A file larger than ingest_chunk is split into in-flight sub-batches;
    verdict order and stats must match the single-shot path."""
    reg = InterfaceRegistry()
    reg.add(Interface(name="dummy0", index=10))
    d = Daemon(
        state_dir=str(tmp_path / "state"),
        node_name=NODE, namespace=NS, backend="cpu",
        poll_period_s=0.05, registry=reg, metrics_port=0, health_port=0,
        file_poll_interval_s=0.02, ingest_chunk=7, pipeline_depth=3,
    )
    d.start()
    try:
        ns_doc = node_state().to_dict()
        p = os.path.join(d.nodestates_dir, f"{NODE}.json")
        with open(p + ".tmp", "w") as f:
            json.dump(ns_doc, f)
        os.replace(p + ".tmp", p)
        assert _wait(lambda: d.syncer.classifier is not None
                     and d.syncer.classifier.tables is not None)
        # 20 packets -> 3 chunks at chunk=7; alternate deny/pass
        frames = [
            build_frame("10.1.2.3", "203.0.113.1", IPPROTO_TCP, 999,
                        80 if i % 2 == 0 else 81)
            for i in range(20)
        ]
        write_frames_file(os.path.join(d.ingest_dir, "big.frames"), frames, 10)
        vp = os.path.join(d.out_dir, "big.frames.verdicts.json")
        assert _wait(lambda: os.path.exists(vp))
        with open(vp) as f:
            summary = json.load(f)
        assert summary["packets"] == 20
        assert summary["drop"] == 10 and summary["pass"] == 10
        # per-packet verdicts live in the binary sidecar, in file order
        # across chunk boundaries
        rb = np.fromfile(
            os.path.join(d.out_dir, summary["results_file"]), dtype="<u4"
        )
        assert len(rb) == 20
        assert rb[:4].tolist() == [257, 0, 257, 0]
    finally:
        d.stop()


def test_cross_file_batched_ingest_tpu_backend(tmp_path):
    """Multiple frames files in one tick share merged device jobs (packed
    wire path); per-file verdict sidecars, stats and events must be
    identical to processing them alone."""
    reg = InterfaceRegistry()
    reg.add(Interface(name="dummy0", index=10))
    d = Daemon(
        state_dir=str(tmp_path / "state"),
        node_name=NODE, namespace=NS, backend="tpu",
        poll_period_s=0.05, registry=reg, metrics_port=0, health_port=0,
        file_poll_interval_s=60.0, ingest_chunk=64,
    )
    try:
        with open(os.path.join(d.nodestates_dir, f"{NODE}.json"), "w") as f:
            json.dump(node_state().to_dict(), f)
        d.scan_nodestates_once()
        clf = d.syncer.classifier
        assert clf.supports_packed()

        mk = lambda dport: build_frame(
            "10.1.2.3", "203.0.113.1", IPPROTO_TCP, 999, dport
        )
        v6 = build_frame("2001:db8::1", "2001:db8::2", IPPROTO_TCP, 999, 80)
        # three files, mixed families, sizes straddling the chunk size
        write_frames_file(os.path.join(d.ingest_dir, "f0.frames"),
                          [mk(80)] * 40 + [v6] * 10, 10)
        write_frames_file(os.path.join(d.ingest_dir, "f1.frames"),
                          [mk(81)] * 50 + [mk(80)] * 30, 10)
        write_frames_file(os.path.join(d.ingest_dir, "f2.frames"),
                          [v6] * 5, 10)
        assert d.process_ingest_once() == 3
        got = {}
        for fn in ("f0", "f1", "f2"):
            with open(os.path.join(d.out_dir, fn + ".frames.verdicts.json")) as f:
                got[fn] = json.load(f)
        # rule: deny tcp/80 from 10.1.0.0/16 (v4 only), everything else passes
        assert (got["f0"]["drop"], got["f0"]["pass"]) == (40, 10)
        assert (got["f1"]["drop"], got["f1"]["pass"]) == (30, 50)
        assert (got["f2"]["drop"], got["f2"]["pass"]) == (0, 5)
        rb = np.fromfile(
            os.path.join(d.out_dir, got["f1"]["results_file"]), dtype="<u4"
        )
        assert (rb[:50] == 0).all() and (rb[50:] == 257).all()
        snap = clf.stats.snapshot()
        assert snap[1, 2] == 70  # 40 + 30 denies across merged jobs
    finally:
        d.stop()


def test_tpu_backend_refused_where_jax_is_not_on_a_tpu(tmp_path, monkeypatch,
                                                       capsys):
    """--backend tpu on a host where JAX came up on the CPU exits
    non-zero, unless JAX_PLATFORMS=cpu asked for the CPU on purpose (as
    these tests do: see the launch-validation tests elsewhere)."""
    from infw.daemon import main as daemon_main

    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    rc = daemon_main(["--state-dir", str(tmp_path), "--node-name", "n",
                      "--backend", "tpu"])
    assert rc == 1
    assert "JAX_PLATFORMS=cpu" in capsys.readouterr().err
