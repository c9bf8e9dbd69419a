"""Manager runtime tests: admission webhook seam, watch-driven
reconciles, config requeue, NodeState file export, and the full
manager->file->daemon composition (the port of the reference's e2e
operator-deployment flow onto the file protocol)."""
import json
import os
import time

import pytest

from infw.manager import Manager, main as manager_main
from infw.platform import get_platform_info
from infw.spec import (
    ACTION_ALLOW,
    ACTION_DENY,
    IngressNodeFirewall,
    IngressNodeFirewallConfig,
    IngressNodeFirewallConfigSpec,
    IngressNodeFirewallNodeState,
    IngressNodeFirewallSpec,
    ObjectMeta,
)
from infw.store import (
    AdmissionError,
    DaemonSet,
    DaemonSetStatus,
    InMemoryStore,
    Node,
    NotFoundError,
)
from infw.controllers import DEFAULT_CONFIG_NAME
from test_syncer import ingress, tcp_rule, udp_rule

NS = "ingress-node-firewall-system"
WORKER = {"role": "worker"}


def inf(name, selector, ingress_rules, interfaces=("eth0",)):
    return IngressNodeFirewall(
        metadata=ObjectMeta(name=name),
        spec=IngressNodeFirewallSpec(
            node_selector=dict(selector),
            ingress=list(ingress_rules),
            interfaces=list(interfaces),
        ),
    )


@pytest.fixture
def mgr(tmp_path):
    m = Manager(namespace=NS, export_dir=str(tmp_path / "export"))
    yield m
    m.stop()


# --- admission webhook seam ---------------------------------------------------

def test_admission_rejects_invalid_interface(mgr):
    bad = inf("fw", WORKER, [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])],
              interfaces=("3eth",))
    with pytest.raises(AdmissionError, match="can't start with a number"):
        mgr.store.create(bad)


def test_admission_rejects_failsafe_conflict(mgr):
    # TCP 6443 (kube API) is failsafe: a Deny rule covering it is rejected
    # (webhook.go:199-243).
    bad = inf("fw", WORKER, [ingress(["10.0.0.0/8"], [tcp_rule(1, "6000-7000", ACTION_DENY)])])
    with pytest.raises(AdmissionError, match="conflict with access"):
        mgr.store.create(bad)
    # Allow over the same range is fine (webhook.go:219-223).
    ok = inf("fw", WORKER, [ingress(["10.0.0.0/8"], [tcp_rule(1, "6000-7000", ACTION_ALLOW)])])
    mgr.store.create(ok)


def test_admission_rejects_cross_inf_order_overlap(mgr):
    mgr.store.create(inf("fw1", WORKER, [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]))
    with pytest.raises(AdmissionError, match="conflicts with IngressNodeFirewall"):
        mgr.store.create(
            inf("fw2", WORKER, [ingress(["10.0.0.0/8"], [udp_rule(1, 53, ACTION_DENY)])])
        )
    # distinct orders are admitted
    mgr.store.create(
        inf("fw2", WORKER, [ingress(["10.0.0.0/8"], [udp_rule(2, 53, ACTION_DENY)])])
    )


def test_admission_self_update_allowed(mgr):
    fw = inf("fw1", WORKER, [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])])
    mgr.store.create(fw)
    fw.spec.ingress[0].rules[0].protocol_config.tcp.ports = 81
    mgr.store.update(fw)  # must not conflict with itself


# --- watch-driven reconciles + export ----------------------------------------

def test_watch_driven_fanout_and_export(mgr, tmp_path):
    mgr.store.create(Node(metadata=ObjectMeta(name="w0", labels=WORKER)))
    mgr.store.create(inf("fw1", WORKER, [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]))
    mgr.drain()
    ns_obj = mgr.store.get(IngressNodeFirewallNodeState.KIND, "w0", NS)
    assert "eth0" in ns_obj.spec.interface_ingress_rules

    export = tmp_path / "export" / "nodestates" / "w0.json"
    assert export.exists()
    doc = json.loads(export.read_text())
    assert doc["metadata"]["name"] == "w0"

    # INF deletion -> NodeState deleted -> export file removed
    mgr.store.delete(IngressNodeFirewall.KIND, "fw1")
    mgr.drain()
    assert not export.exists()


def test_out_of_band_nodestate_deletion_repaired(mgr):
    """Owns(&NodeState) semantics: deleting a NodeState out-of-band while
    its INF still selects the node must recreate it on the next drain."""
    mgr.store.create(Node(metadata=ObjectMeta(name="w0", labels=WORKER)))
    mgr.store.create(inf("fw1", WORKER, [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]))
    mgr.drain()
    mgr.store.delete(IngressNodeFirewallNodeState.KIND, "w0", NS)
    mgr.drain()
    assert mgr.store.get(IngressNodeFirewallNodeState.KIND, "w0", NS)


def test_stopped_manager_cancels_watches(tmp_path):
    store = InMemoryStore()
    m = Manager(store=store, namespace=NS, export_dir=str(tmp_path / "e"))
    m.stop()
    store.create(Node(metadata=ObjectMeta(name="w0", labels=WORKER)))
    store.create(inf("fw1", WORKER, [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]))
    assert m._queue.qsize() == 0  # no events land on the dead queue


def test_config_reconcile_conditions(mgr):
    mgr.store.create(
        IngressNodeFirewallConfig(
            metadata=ObjectMeta(name=DEFAULT_CONFIG_NAME, namespace=NS),
            spec=IngressNodeFirewallConfigSpec(),
        )
    )
    mgr.drain()
    ds = mgr.store.get(DaemonSet.KIND, "ingress-node-firewall-daemon", NS)
    ds.status = DaemonSetStatus(desired_number_scheduled=1, number_ready=1)
    mgr.store.update_status(ds)
    mgr.enqueue_config(DEFAULT_CONFIG_NAME)
    mgr.drain()
    cfg = mgr.store.get(IngressNodeFirewallConfig.KIND, DEFAULT_CONFIG_NAME, NS)
    assert {c.type: c.status for c in cfg.status.conditions}["Available"] == "True"


# --- full manager -> file -> daemon composition -------------------------------

def test_manager_daemon_file_composition(tmp_path):
    from infw.daemon import Daemon
    from infw.interfaces import Interface, InterfaceRegistry
    from infw.obs.pcap import build_frame
    from infw.daemon import write_frames_file

    shared = str(tmp_path / "shared")
    mgr = Manager(namespace=NS, export_dir=shared)
    reg = InterfaceRegistry()
    reg.add(Interface(name="eth0", index=2))
    daemon = Daemon(
        state_dir=shared, node_name="w0", namespace=NS, backend="cpu",
        registry=reg, metrics_port=0, health_port=0, file_poll_interval_s=0.02,
        poll_period_s=0.05,
    )
    daemon.start()
    try:
        mgr.store.create(Node(metadata=ObjectMeta(name="w0", labels=WORKER)))
        mgr.store.create(
            inf("fw1", WORKER, [ingress(["0.0.0.0/0"], [tcp_rule(1, 8080, ACTION_DENY)])])
        )
        mgr.drain()

        deadline = time.time() + 5
        while time.time() < deadline and (
            daemon.syncer.classifier is None or daemon.syncer.classifier.tables is None
        ):
            time.sleep(0.02)
        assert daemon.syncer.classifier is not None

        frames = [build_frame("1.2.3.4", "10.0.0.1", 6, 1, 8080),
                  build_frame("1.2.3.4", "10.0.0.1", 6, 1, 8081)]
        write_frames_file(os.path.join(daemon.ingest_dir, "x.frames"), frames, 2)
        vp = os.path.join(daemon.out_dir, "x.frames.verdicts.json")
        while time.time() < deadline and not os.path.exists(vp):
            time.sleep(0.02)
        with open(vp) as f:
            summary = json.load(f)
        assert summary["drop"] == 1 and summary["pass"] == 1
    finally:
        daemon.stop()
        mgr.stop()


# --- CLI env contract ---------------------------------------------------------

def test_main_requires_env(monkeypatch, capsys):
    monkeypatch.delenv("DAEMONSET_IMAGE", raising=False)
    monkeypatch.delenv("DAEMONSET_NAMESPACE", raising=False)
    with pytest.raises(SystemExit):
        manager_main([])
    assert "DAEMONSET_IMAGE" in capsys.readouterr().err


def test_platform_info():
    info = get_platform_info()
    assert info.backend  # cpu in tests
    assert info.num_devices >= 1
    assert isinstance(info.is_tpu, bool)


# --- apply-dir (kubectl-apply seam) ------------------------------------------

def _write_cr(path, doc):
    tmp = str(path) + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f)
    os.replace(tmp, path)


def test_apply_dir_create_update_delete(tmp_path):
    m = Manager(namespace=NS, export_dir=str(tmp_path / "export"),
                apply_dir=str(tmp_path / "apply"))
    try:
        m.store.create(Node(metadata=ObjectMeta(name="w0", labels=WORKER)))
        doc = inf("fw1", WORKER,
                  [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]).to_dict()
        _write_cr(tmp_path / "apply" / "fw1.json", doc)
        m.scan_apply_dir_once()
        m.drain()
        got = m.store.get(IngressNodeFirewall.KIND, "fw1")
        assert got is not None
        with open(tmp_path / "apply" / "fw1.status.json") as f:
            assert json.load(f) == {"applied": True, "errors": []}
        # fan-out produced the exported NodeState
        assert os.path.exists(tmp_path / "export" / "nodestates" / "w0.json")

        # update: edit the file -> rules change flows through (content
        # hash, so no mtime-granularity games needed)
        doc["spec"]["ingress"][0]["rules"][0]["action"] = "Allow"
        _write_cr(tmp_path / "apply" / "fw1.json", doc)
        m.scan_apply_dir_once()
        got = m.store.get(IngressNodeFirewall.KIND, "fw1")
        assert got.spec.ingress[0].rules[0].action == ACTION_ALLOW

        # rename within the file: old CR must not be orphaned
        doc["metadata"]["name"] = "fw1b"
        _write_cr(tmp_path / "apply" / "fw1.json", doc)
        m.scan_apply_dir_once()
        with pytest.raises(NotFoundError):
            m.store.get(IngressNodeFirewall.KIND, "fw1")
        assert m.store.get(IngressNodeFirewall.KIND, "fw1b") is not None

        # break the file, then remove it: the live CR (from the last good
        # apply) must still be deleted — a rejected edit does not orphan it
        with open(tmp_path / "apply" / "fw1.json", "w") as f:
            f.write("{nope")
        m.scan_apply_dir_once()
        assert m.store.get(IngressNodeFirewall.KIND, "fw1b") is not None
        os.remove(tmp_path / "apply" / "fw1.json")
        m.scan_apply_dir_once()
        m.drain()
        with pytest.raises(NotFoundError):
            m.store.get(IngressNodeFirewall.KIND, "fw1b")
        assert not os.path.exists(tmp_path / "apply" / "fw1.status.json")
    finally:
        m.stop()


def test_apply_dir_rename_to_rejected_keeps_old_cr(tmp_path):
    """A file edit that renames its CR to something admission rejects must
    NOT fail open: the previously-enforcing object stays (the reference
    webhook rejects atomically, leaving the old object intact) — round-3
    advisor medium finding."""
    m = Manager(namespace=NS, apply_dir=str(tmp_path / "apply"))
    try:
        doc = inf("fw1", WORKER,
                  [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]).to_dict()
        _write_cr(tmp_path / "apply" / "fw.json", doc)
        m.scan_apply_dir_once()
        assert m.store.get(IngressNodeFirewall.KIND, "fw1") is not None

        # rename AND break it (deny on failsafe port 22 is rejected)
        doc["metadata"]["name"] = "fw2"
        doc["spec"]["ingress"][0]["rules"][0]["protocolConfig"]["tcp"]["ports"] = "22"
        _write_cr(tmp_path / "apply" / "fw.json", doc)
        m.scan_apply_dir_once()
        # old object still enforcing, successor rejected
        assert m.store.get(IngressNodeFirewall.KIND, "fw1") is not None
        with pytest.raises(NotFoundError):
            m.store.get(IngressNodeFirewall.KIND, "fw2")
        with open(tmp_path / "apply" / "fw.status.json") as f:
            assert json.load(f)["applied"] is False

        # removing the file still deletes the live (old) CR — the mapping
        # survived the rejected rename
        os.remove(tmp_path / "apply" / "fw.json")
        m.scan_apply_dir_once()
        with pytest.raises(NotFoundError):
            m.store.get(IngressNodeFirewall.KIND, "fw1")
    finally:
        m.stop()


def test_apply_dir_rename_conflicting_with_self_succeeds(tmp_path):
    """A rename whose successor order-conflicts only with its own
    predecessor (identical spec, new name) must land: the scan retries
    with the predecessor removed, and restores it only if the successor
    still fails on its own."""
    m = Manager(namespace=NS, apply_dir=str(tmp_path / "apply"))
    try:
        doc = inf("fwa", WORKER,
                  [ingress(["10.0.0.0/8"], [tcp_rule(1, 80, ACTION_DENY)])]).to_dict()
        _write_cr(tmp_path / "apply" / "fw.json", doc)
        m.scan_apply_dir_once()
        doc["metadata"]["name"] = "fwb"  # same spec: overlaps fwa's orders
        _write_cr(tmp_path / "apply" / "fw.json", doc)
        m.scan_apply_dir_once()
        assert m.store.get(IngressNodeFirewall.KIND, "fwb") is not None
        with pytest.raises(NotFoundError):
            m.store.get(IngressNodeFirewall.KIND, "fwa")
        with open(tmp_path / "apply" / "fw.status.json") as f:
            assert json.load(f)["applied"] is True
    finally:
        m.stop()


def test_apply_dir_rejection_writes_status(tmp_path):
    m = Manager(namespace=NS, apply_dir=str(tmp_path / "apply"))
    try:
        bad = inf("fw-bad", WORKER,
                  [ingress(["10.0.0.0/8"], [tcp_rule(1, 22, ACTION_DENY)])]).to_dict()
        _write_cr(tmp_path / "apply" / "fw-bad.json", bad)  # failsafe port 22
        m.scan_apply_dir_once()
        with pytest.raises(NotFoundError):
            m.store.get(IngressNodeFirewall.KIND, "fw-bad")
        with open(tmp_path / "apply" / "fw-bad.status.json") as f:
            st = json.load(f)
        assert st["applied"] is False and st["errors"]
        # garbage file: rejected, not fatal
        with open(tmp_path / "apply" / "junk.json", "w") as f:
            f.write("{nope")
        m.scan_apply_dir_once()
        with open(tmp_path / "apply" / "junk.status.json") as f:
            assert json.load(f)["applied"] is False
    finally:
        m.stop()


def test_full_compose_stack_cr_to_sidecar_event(tmp_path):
    """The reference's whole e2e flow as REAL processes, brought up FROM
    THE BUNDLE: deploy/launch.py reads deploy/bundle/manifest.json and
    spawns sidecar + manager(--apply-dir) + daemon (the OLM-install role,
    /root/reference/bundle/).  An IngressNodeFirewall CR dropped in the
    apply dir must travel admission -> fan-out -> NodeState export ->
    daemon sync -> classify, and the deny event must come out of the
    SIDECAR's log in the reference's line format (cmd/syslog +
    test/e2e/events regex flow)."""
    import re
    import subprocess
    import sys as _sys

    state = tmp_path / "state"
    sock = str(tmp_path / "events.sock")
    env = dict(os.environ, NODE_NAME="composed-node",
               DAEMONSET_IMAGE="infw:latest", DAEMONSET_NAMESPACE=NS,
               JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    logs = {n: state / f"{n}.log"
            for n in ("events-sidecar", "manager", "daemon")}
    launcher = subprocess.Popen(
        [_sys.executable, os.path.join(repo, "deploy", "launch.py"),
         "--state-dir", str(state), "--backend", "cpu",
         "--node-name", "composed-node", "--events-socket", sock,
         "--ephemeral-ports"],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, env=env,
    )
    procs = {"launcher": launcher}
    try:
        # generous: under a loaded machine (the INFW_BIG_TESTS run
        # allocates GBs right before this test) process spawn + jax
        # import can exceed 30s
        deadline = time.time() + 120
        while time.time() < deadline and not (state / "apply").is_dir():
            time.sleep(0.1)
        assert (state / "apply").is_dir(), (
            "launcher stack did not come up; launcher output:\n"
            + (launcher.stdout.read().decode(errors="replace")
               if launcher.poll() is not None else "(still starting)")
        )

        # a CR that trips the failsafe webhook: rejected with the verdict
        # in its status file (the API-call error of webhook.go, as a file)
        bad = inf("fw-bad", WORKER,
                  [ingress(["10.0.0.0/8"], [tcp_rule(1, 22, ACTION_DENY)])]).to_dict()
        _write_cr(state / "apply" / "fw-bad.json", bad)
        stp = state / "apply" / "fw-bad.status.json"
        while time.time() < deadline and not stp.exists():
            time.sleep(0.1)
        with open(stp) as f:
            st = json.load(f)
        assert st["applied"] is False
        assert any("conflict" in e for e in st["errors"]), st  # failsafe SSH

        # the REAL path: a valid CR (empty selector = all nodes) travels
        # admission -> fan-out against the self-registered Node ->
        # NodeState export -> daemon sync.  No manual NodeState anywhere.
        good = inf("fw-good", {},
                   [ingress(["10.1.0.0/16"], [tcp_rule(1, 80, ACTION_DENY)])],
                   interfaces=("eth0",)).to_dict()
        _write_cr(state / "apply" / "fw-good.json", good)
        nsp = state / "nodestates" / "composed-node.json"
        deadline = time.time() + 30  # fresh budget: startup consumed the first
        while time.time() < deadline and not nsp.exists():
            time.sleep(0.1)
        assert nsp.exists(), logs["manager"].read_text()[-2000:]

        from infw.daemon import write_frames_file_v2
        from infw.obs.pcap import FramesBuf, build_frame

        fb = FramesBuf.from_frames(
            [build_frame("10.1.2.3", "9.9.9.9", 6, 1234, 80)], 2
        )
        vp = state / "out" / "e.frames.verdicts.json"
        deadline = time.time() + 60
        wrote = False
        while time.time() < deadline and not vp.exists():
            if not wrote and (state / "ingest").is_dir():
                write_frames_file_v2(str(state / "ingest" / "e.frames"), fb)
                wrote = True
            time.sleep(0.2)
        assert vp.exists(), logs["daemon"].read_text()[-2000:]
        with open(vp) as f:
            assert json.load(f)["drop"] == 1

        # the deny event must surface on the SIDECAR's log
        pat = re.compile(r"ruleId 1 action Drop len \d+ if ")
        sidecar_log = logs["events-sidecar"]
        while time.time() < deadline:
            if sidecar_log.exists() and pat.search(
                sidecar_log.read_text(errors="replace")
            ):
                break
            time.sleep(0.2)
        assert pat.search(sidecar_log.read_text(errors="replace")), (
            sidecar_log.read_text(errors="replace")[-2000:]
        )
    finally:
        for p in procs.values():
            p.terminate()
        for p in procs.values():
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait(timeout=15)


def test_apply_dir_config_kind_and_unsupported(tmp_path):
    """The apply seam routes by kind: an IngressNodeFirewallConfig drives
    the config reconciler (daemonset render); unsupported kinds are
    rejected with the reason in the status file."""
    m = Manager(namespace=NS, apply_dir=str(tmp_path / "apply"))
    try:
        _write_cr(tmp_path / "apply" / "config.json", {
            "apiVersion": "ingressnodefirewall.openshift.io/v1alpha1",
            "kind": "IngressNodeFirewallConfig",
            "metadata": {"name": DEFAULT_CONFIG_NAME},
            "spec": {"nodeSelector": {}, "debug": True},
        })
        m.scan_apply_dir_once()
        m.drain()
        cfg = m.store.get(
            IngressNodeFirewallConfig.KIND, DEFAULT_CONFIG_NAME, NS
        )
        assert cfg.spec.debug is True  # namespace defaulted to the manager's
        ds = m.store.get(DaemonSet.KIND, "ingress-node-firewall-daemon", NS)
        assert ds is not None  # config reconcile rendered the daemonset

        _write_cr(tmp_path / "apply" / "node.json",
                  {"kind": "Node", "metadata": {"name": "n0"}})
        m.scan_apply_dir_once()
        with open(tmp_path / "apply" / "node.status.json") as f:
            st = json.load(f)
        assert st["applied"] is False
        assert any("unsupported kind" in e for e in st["errors"])
    finally:
        m.stop()


def test_manager_process_never_imports_jax(tmp_path):
    """`python -m infw.manager` leaves the chip to the daemon started
    beside it: JAX is made unimportable in the child, and the manager
    must still come up, serve and exit cleanly on SIGTERM."""
    import signal
    import subprocess
    import sys

    fake = tmp_path / "fakejax" / "jax"
    fake.mkdir(parents=True)
    marker = tmp_path / "jax-imported"
    (fake / "__init__.py").write_text(
        f"open({str(marker)!r}, 'w').close()\n"
        "raise ImportError('the manager must not import jax')\n"
    )
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [str(fake.parent), repo]))
    proc = subprocess.Popen(
        [sys.executable, "-m", "infw.manager",
         "--export-dir", str(tmp_path / "state"), "--lease-file", "none",
         "--namespace", "ns", "--daemon-image", "img",
         "--metrics-port", "0", "--health-port", "0"],
        env=env, stderr=subprocess.PIPE, text=True,
    )
    try:
        deadline = time.time() + 30
        while (time.time() < deadline and proc.poll() is None
               and not (tmp_path / "state" / "nodestates").exists()):
            time.sleep(0.1)
        time.sleep(1.0)
        assert proc.poll() is None, proc.stderr.read()
        proc.send_signal(signal.SIGTERM)
        _out, err = proc.communicate(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
    assert proc.returncode == 0, err
    assert "manager started" in err
    assert not marker.exists()


def test_platform_probe_raises_when_jax_has_no_backend(monkeypatch):
    """A failed probe must not read as a CPU host."""
    import jax

    def no_backend():
        raise RuntimeError("Unable to initialize backend 'tpu'")

    monkeypatch.setattr(jax, "devices", no_backend)
    with pytest.raises(RuntimeError):
        get_platform_info()
