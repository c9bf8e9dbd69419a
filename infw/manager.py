"""The cluster manager: controller runtime wiring both reconcilers, the
admission webhook, and the NodeState export path.

Equivalent of the reference's manager binary (/root/reference/main.go):
env contract DAEMONSET_IMAGE / DAEMONSET_NAMESPACE (:87-99), webhook
registration behind a toggle (:142-147), controller setup with watches
(:132-140, 155-164), healthz endpoint and blocking run loop (:101-126,
177).

The watch->workqueue->reconcile shape mirrors controller-runtime: events
coalesce in a debounced queue, reconciles run on a worker thread, and a
config reconcile returning requeue_after is rescheduled (the 5s
requeue-while-progressing, ingressnodefirewallconfig_controller.go:94-107).

NodeState export: when an ``export_dir`` is configured, every NodeState
write/delete is mirrored to ``<export_dir>/nodestates/<node>.json`` — the
file protocol the daemon watches — so manager and daemons compose across
process boundaries the way the reference's manager and DaemonSet compose
through the k8s API.

The manager never starts a JAX backend: on a TPU host the process that
initializes JAX holds the chip, and the daemon started beside it must
get it.  The daemon backend it renders comes from ``INFW_BACKEND`` (the
variable the launcher and the daemon read), default ``tpu``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import logging
import os
import queue
import signal
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import List, Optional

from ._threads import spawn
from . import validate
from .controllers import (
    DEFAULT_CONFIG_NAME,
    IngressNodeFirewallConfigReconciler,
    IngressNodeFirewallReconciler,
)
from .spec import (
    IngressNodeFirewall,
    IngressNodeFirewallConfig,
    IngressNodeFirewallNodeState,
    ObjectMeta,
)
from .apply import apply_object
from .store import (
    DELETED,
    AdmissionError,
    AlreadyExistsError,
    InMemoryStore,
    Node,
    NotFoundError,
    StoreError,
)

log = logging.getLogger("infw.manager")

DEFAULT_METRICS_PORT = 39201  # main.go:63
DEFAULT_HEALTH_PORT = 8081    # main.go:65


def inf_admission(obj: IngressNodeFirewall, store: InMemoryStore) -> List[str]:
    """The validating webhook hooked into the store's admission seam
    (webhook.go ValidateCreate/Update: validate against all *other*
    existing IngressNodeFirewalls)."""
    existing = [
        o
        for o in store.list(IngressNodeFirewall.KIND)
        if o.metadata.name != obj.metadata.name
    ]
    return validate.validate_ingress_node_firewall(obj, existing)


class Manager:
    def __init__(
        self,
        store: Optional[InMemoryStore] = None,
        namespace: str = "ingress-node-firewall-system",
        daemon_image: str = "infw-daemon:latest",
        enable_webhook: bool = True,
        export_dir: Optional[str] = None,
        apply_dir: Optional[str] = None,
        apply_poll_interval_s: float = 0.5,
        register_nodes: Optional[List[str]] = None,
        metrics_port: int = DEFAULT_METRICS_PORT,
        health_port: int = DEFAULT_HEALTH_PORT,
        lease=None,
        lease_holder: Optional[str] = None,
    ) -> None:
        self.store = store if store is not None else InMemoryStore()
        self.namespace = namespace
        self.backend = os.environ.get("INFW_BACKEND") or "tpu"
        self.fanout = IngressNodeFirewallReconciler(self.store, namespace=namespace)
        self.config = IngressNodeFirewallConfigReconciler(
            self.store, namespace=namespace, daemon_image=daemon_image,
            backend=self.backend,
        )
        self.metrics_port = metrics_port
        self.health_port = health_port
        self.reconcile_counts = {"fanout": 0, "config": 0}
        self.apply_counts = {"applied": 0, "rejected": 0, "deleted": 0}

        if enable_webhook:
            self.store.set_admission(IngressNodeFirewall.KIND, inf_admission)

        self.export_dir: Optional[str] = None
        if export_dir:
            self.export_dir = os.path.join(export_dir, "nodestates")
            os.makedirs(self.export_dir, exist_ok=True)

        # kubectl-apply analogue: a watched directory of IngressNodeFirewall
        # CR JSONs (see scan_apply_dir_once) — the file seam that lets an
        # operator drive a RUNNING manager process the way `kubectl apply`
        # drives the reference's API server.
        self.apply_dir: Optional[str] = None
        if apply_dir:
            self.apply_dir = apply_dir
            os.makedirs(self.apply_dir, exist_ok=True)
        self.apply_poll_interval_s = apply_poll_interval_s
        # filename -> ((kind, name, namespace) | None, content hash)
        self._applied: dict = {}

        # Self-registered Node inventory for API-server-less deployments
        # (the compose stack): the reference's fan-out matches CRs against
        # cluster Nodes; a single-node composition registers its own host
        # the way a kubelet joins the cluster.
        for node_name in register_nodes or []:
            try:
                self.store.create(Node(metadata=ObjectMeta(name=node_name)))
            except AlreadyExistsError:
                pass

        # Single-writer lease (leader election, main.go:76-85): start()
        # blocks in standby until the lease is acquired; a renewal
        # failure (another instance stole an expired lease) stops this
        # manager — the controller-runtime leader-loss-is-fatal contract.
        self.lease = lease
        self.lease_holder = lease_holder or f"mgr-{os.getpid()}-{id(self):x}"
        self.is_leader = lease is None  # leaderless single-writer default
        self.lease_lost = False

        self._queue: "queue.Queue" = queue.Queue()
        self._stop = threading.Event()
        self._threads: List[threading.Thread] = []
        self._servers: List[ThreadingHTTPServer] = []
        self._requeue_timers: dict = {}  # config name -> outstanding Timer
        self._watch_cancels: List = []

        # Watches (SetupWithManager): the fan-out controller reconciles on
        # INF + Node + owned NodeState events
        # (ingressnodefirewall_controller.go:239-249); the config controller
        # on Config events.
        for kind in (IngressNodeFirewall.KIND, Node.KIND):
            self._watch_cancels.append(
                self.store.watch(kind, lambda e, o: self.enqueue_fanout())
            )
        self._watch_cancels.append(
            self.store.watch(IngressNodeFirewallNodeState.KIND, self._on_nodestate_event)
        )
        self._watch_cancels.append(
            self.store.watch(
                IngressNodeFirewallConfig.KIND,
                lambda e, o: self.enqueue_config(o.metadata.name),
            )
        )

    # -- work queue ----------------------------------------------------------

    def enqueue_fanout(self) -> None:
        # Standby instances (lease not yet acquired) must not act OR
        # accumulate an unbounded queue; the post-acquisition full resync
        # in start() covers anything that happened while standing by.
        if not self.is_leader:
            return
        self._queue.put(("fanout", None))

    def enqueue_config(self, name: str) -> None:
        if not self.is_leader:
            return
        self._queue.put(("config", name))

    def _on_nodestate_event(self, event: str, obj) -> None:
        # Only the leader mirrors exports — a standby writing the same
        # export tmp files would race the leader's os.replace protocol.
        if not self.is_leader:
            return
        if self.export_dir is not None:
            path = os.path.join(self.export_dir, f"{obj.metadata.name}.json")
            if event == DELETED:
                try:
                    os.remove(path)
                except FileNotFoundError:
                    pass
            else:
                tmp = path + ".tmp"
                with open(tmp, "w") as f:
                    json.dump(obj.to_dict(), f)
                os.replace(tmp, path)
        # Owned-object watch: NodeState drift — including out-of-band
        # deletion — triggers the owner's reconcile (Owns(&NodeState),
        # :247); self-initiated deletes converge in one no-write pass since
        # the store suppresses no-op writes.
        self.enqueue_fanout()

    def process_one(self, block: bool = True, timeout: Optional[float] = None) -> bool:
        """Run one queued reconcile; returns False when the queue is empty
        (non-blocking mode) or the stop flag is set."""
        try:
            item = self._queue.get(block=block, timeout=timeout)
        except queue.Empty:
            return False
        kind, arg = item
        # Debounce: collapse consecutive duplicate requests.
        try:
            while True:
                nxt = self._queue.get_nowait()
                if nxt != item:
                    self._queue.put(nxt)
                    break
        except queue.Empty:
            pass
        try:
            if kind == "fanout":
                self.fanout.reconcile()
                self.reconcile_counts["fanout"] += 1
            elif kind == "config":
                result = self.config.reconcile(arg)
                self.reconcile_counts["config"] += 1
                if result.requeue_after is not None and not self._stop.is_set():
                    # One outstanding requeue per config: cancel-and-replace
                    # so a progressing deployment never accumulates timers.
                    old = self._requeue_timers.pop(arg, None)
                    if old is not None:
                        old.cancel()
                    t = threading.Timer(
                        result.requeue_after, lambda: self.enqueue_config(arg)
                    )
                    t.daemon = True
                    t.start()
                    self._requeue_timers[arg] = t
        except Exception as e:  # reconcile errors are logged, never fatal
            log.error("%s reconcile failed: %s", kind, e)
        return True

    def drain(self) -> None:
        """Process queued work until empty (test helper — the equivalent of
        envtest's Eventually())."""
        while self.process_one(block=False):
            pass

    # -- apply dir (kubectl-apply seam) --------------------------------------

    # CR kinds the apply seam accepts (NodeStates are manager-owned
    # output, never operator input — exactly the reference's RBAC shape)
    APPLY_KINDS = {
        IngressNodeFirewall.KIND: IngressNodeFirewall,
        IngressNodeFirewallConfig.KIND: IngressNodeFirewallConfig,
    }

    def scan_apply_dir_once(self) -> None:
        """Reconcile the apply directory against the store: each
        ``<name>.json`` is a CR (IngressNodeFirewall or
        IngressNodeFirewallConfig, discriminated by ``kind``) applied
        through the admission seam (create-or-update); file deletion
        deletes the CR.  The admission verdict lands in
        ``<name>.status.json`` — the file protocol's version of the
        webhook response the reference returns on the API call
        (webhook.go ValidateCreate/Update)."""
        if not self.apply_dir:
            return
        seen = set()
        for fn in sorted(os.listdir(self.apply_dir)):
            if (
                not fn.endswith(".json")
                or fn.endswith(".status.json")
                or fn.endswith(".tmp")
            ):
                continue
            path = os.path.join(self.apply_dir, fn)
            try:
                with open(path, "rb") as f:
                    raw = f.read()
            except FileNotFoundError:
                continue
            seen.add(fn)
            # Content hash, not (mtime, size): same-length rewrites within
            # one mtime tick must not be silently skipped.
            sig = hashlib.sha1(raw).hexdigest()
            prev = self._applied.get(fn)  # (ident-or-None, sig)
            if prev is not None and prev[1] == sig:
                continue
            errors: List[str] = []
            obj = None
            try:
                doc = json.loads(raw)
            except ValueError as e:
                doc = None
                errors = [f"unparseable CR document: {e}"]
            if doc is not None:
                cls = self.APPLY_KINDS.get(
                    doc.get("kind") if isinstance(doc, dict) else None
                )
                if cls is None:
                    kind = doc.get("kind") if isinstance(doc, dict) else doc
                    errors = [
                        f"unsupported kind {kind!r} "
                        f"(expected one of {sorted(self.APPLY_KINDS)})"
                    ]
                else:
                    try:
                        obj = cls.from_dict(doc)
                    except Exception as e:
                        errors = [f"invalid {cls.KIND} document: {e}"]
                    if obj is not None and (
                        cls is IngressNodeFirewallConfig
                        and not obj.metadata.namespace
                    ):
                        # the config reconciler looks in the manager namespace
                        obj.metadata.namespace = self.namespace
            if obj is not None:
                ident = (obj.KIND, obj.metadata.name, obj.metadata.namespace)
                old_ident = (
                    prev[0]
                    if prev is not None and prev[0] not in (None, ident)
                    else None
                )
                errors = self._try_apply(obj)
                if old_ident is not None:
                    # The file renamed (or re-kinded) its CR.  The
                    # replacement is validated FIRST (above) so a bad edit
                    # never fails open: the webhook analogue rejects
                    # atomically, leaving the old object enforcing.  Only
                    # when the rejection is the successor conflicting with
                    # its own predecessor (cross-INF order overlap names
                    # the conflicting INF, validate.py:266-270) is the
                    # predecessor removed for ONE retry — any other
                    # rejection must not touch the enforcing object (a
                    # delete/recreate cycle would briefly fail open for
                    # watchers and can lose the CR if the restore races).
                    conflict_tag = (
                        f"conflicts with IngressNodeFirewall "
                        f"{old_ident[1]!r}"
                    )
                    # EVERY error must be a self-conflict: any other error
                    # survives the predecessor's removal, so the retry
                    # could not succeed and the churn would be pure risk.
                    self_conflict = (
                        old_ident[0] == obj.KIND
                        and bool(errors)
                        and all(conflict_tag in e for e in errors)
                    )
                    if not errors:
                        self._delete_cr(old_ident, fn + " (renamed)")
                    elif self_conflict:
                        old_obj = self._get_cr(old_ident)
                        self._delete_cr(old_ident, fn + " (renamed)")
                        errors = self._try_apply(obj)
                        if errors and old_obj is not None:
                            try:
                                self.store.create(old_obj)
                                log.warning(
                                    "apply %s: replacement rejected; "
                                    "restored %s/%s", fn, old_ident[0],
                                    old_ident[1],
                                )
                            except StoreError as e:
                                log.error(
                                    "apply %s: could not restore %s/%s "
                                    "after rejected replacement: %s",
                                    fn, old_ident[0], old_ident[1], e,
                                )
            self._write_apply_status(fn, errors)
            if errors:
                self.apply_counts["rejected"] += 1
                log.warning("apply %s rejected: %s", fn, "; ".join(errors))
                # Remember the rejected signature so an unchanged file is
                # not re-applied (and re-logged) every poll — but KEEP the
                # previously applied CR mapping: the live object must still
                # be deletable when the file goes away.
                old = prev if prev is not None else (None, None)
                self._applied[fn] = (old[0], sig)
            else:
                self.apply_counts["applied"] += 1
                log.info("applied %s -> %s/%s", fn, obj.KIND, obj.metadata.name)
                self._applied[fn] = (ident, sig)
        for fn in [f for f in self._applied if f not in seen]:
            ident, _sig = self._applied.pop(fn)
            try:
                os.remove(os.path.join(self.apply_dir, fn[:-5] + ".status.json"))
            except OSError:
                pass
            if ident is None:
                continue  # a rejected file never reached the store
            self._delete_cr(ident, fn + " removed")

    def _try_apply(self, obj) -> List[str]:
        """Apply through the admission seam; returns the rejection errors
        ([] on success)."""
        try:
            apply_object(self.store, obj)
        except AdmissionError as e:
            return list(e.errors)
        except StoreError as e:
            return [str(e)]
        return []

    def _get_cr(self, ident):
        kind, name, namespace = ident
        try:
            return self.store.get(kind, name, namespace or "")
        except NotFoundError:
            return None

    def _delete_cr(self, ident, why: str) -> None:
        kind, name, namespace = ident
        try:
            self.store.delete(kind, name, namespace or "")
            self.apply_counts["deleted"] += 1
            log.info("deleted %s/%s (%s)", kind, name, why)
        except NotFoundError:
            pass

    def _write_apply_status(self, fn: str, errors: List[str]) -> None:
        status_path = os.path.join(
            self.apply_dir, fn[:-5] + ".status.json"
        )
        tmp = status_path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"applied": not errors, "errors": errors}, f)
        os.replace(tmp, status_path)

    def _apply_loop(self) -> None:
        while not self._stop.is_set():
            try:
                self.scan_apply_dir_once()
            except Exception as e:  # never let a scan error kill the loop
                log.error("apply-dir scan failed: %s", e)
            self._stop.wait(self.apply_poll_interval_s)

    # -- lifecycle -----------------------------------------------------------

    def _worker(self) -> None:
        while not self._stop.is_set():
            self.process_one(block=True, timeout=0.2)

    def _make_handler(mgr):
        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _send(self, code, body):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", "text/plain; charset=utf-8")
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                if self.path in ("/healthz", "/readyz"):
                    self._send(200, "ok")
                elif self.path == "/metrics":
                    lines = [
                        "# TYPE ingressnodefirewall_manager_reconcile_total counter"
                    ]
                    for k, v in mgr.reconcile_counts.items():
                        lines.append(
                            f'ingressnodefirewall_manager_reconcile_total{{controller="{k}"}} {v}'
                        )
                    lines.append(
                        "# TYPE ingressnodefirewall_manager_apply_total counter"
                    )
                    for k, v in mgr.apply_counts.items():
                        lines.append(
                            f'ingressnodefirewall_manager_apply_total{{outcome="{k}"}} {v}'
                        )
                    self._send(200, "\n".join(lines) + "\n")
                else:
                    self._send(404, "not found")

        return Handler

    def _await_lease(self, timeout: Optional[float]) -> bool:
        """Standby loop: poll try_acquire until leadership or timeout/stop.
        Returns True when this instance is the leader."""
        deadline = None if timeout is None else time.time() + timeout
        poll = max(0.05, self.lease.duration_s / 10.0)
        while not self._stop.is_set():
            if self.lease.try_acquire(self.lease_holder):
                self.is_leader = True
                log.info("lease acquired holder=%s", self.lease_holder)
                return True
            if deadline is not None and time.time() >= deadline:
                return False
            time.sleep(poll)
        return False

    def _renew_loop(self) -> None:
        interval = self.lease.duration_s / 3.0
        while not self._stop.wait(interval):
            if not self.lease.renew(self.lease_holder):
                # Another instance took over an expired lease: stop acting
                # as leader immediately (fatal, like controller-runtime's
                # leader-election loss).
                self.lease_lost = True
                self.is_leader = False
                log.error(
                    "lease lost holder=%s (stolen after expiry); stopping",
                    self.lease_holder,
                )
                spawn(self.stop, name="infw-mgr-stop")
                return

    def start(self, lease_timeout: Optional[float] = None) -> bool:
        """Bring the manager up.  With a lease configured this blocks in
        standby until leadership is acquired (pass ``lease_timeout`` to
        bound the wait; returns False if it expires un-acquired — the
        instance stays standby and can be start()ed again)."""
        if self.lease is not None and not self.is_leader:
            if not self._await_lease(lease_timeout):
                return False
            t = spawn(self._renew_loop, name="infw-lease-renew")
            self._threads.append(t)
        handler = self._make_handler()
        for port in {self.metrics_port, self.health_port}:
            srv = ThreadingHTTPServer(("127.0.0.1", port), handler)
            self._servers.append(srv)
            t = spawn(srv.serve_forever, name="infw-mgr-http")
            self._threads.append(t)
        t = spawn(self._worker, name="infw-mgr-worker")
        self._threads.append(t)
        if self.apply_dir:
            t = spawn(self._apply_loop, name="infw-mgr-apply")
            self._threads.append(t)
        # Initial full reconciles (the List-driven state resync on start).
        self.enqueue_fanout()
        self.enqueue_config(DEFAULT_CONFIG_NAME)
        log.info(
            "manager started namespace=%s daemon-backend=%s leader=%s",
            self.namespace, self.backend, self.is_leader,
        )
        return True

    def stop(self) -> None:
        self._stop.set()
        for cancel in self._watch_cancels:
            cancel()
        for t in self._requeue_timers.values():
            t.cancel()
        self._requeue_timers.clear()
        for srv in self._servers:
            srv.shutdown()
            srv.server_close()
        if self.lease is not None and self.is_leader:
            self.is_leader = False
            self.lease.release(self.lease_holder)


def main(argv: Optional[List[str]] = None) -> int:
    """CLI entry enforcing the env contract (main.go:87-99)."""
    p = argparse.ArgumentParser(prog="infw-manager")
    p.add_argument("--export-dir", default=None,
                   help="mirror NodeStates to <dir>/nodestates for file-driven daemons")
    p.add_argument("--apply-dir", default=None,
                   help="watch <dir> for CR JSONs (IngressNodeFirewall or "
                        "IngressNodeFirewallConfig, by kind) — the "
                        "kubectl-apply seam; <name>.status.json carries "
                        "the admission verdict)")
    p.add_argument("--register-node", action="append", default=None,
                   metavar="NAME",
                   help="register a Node in the manager's inventory "
                        "(repeatable; API-server-less compose runs where "
                        "no kubelet joins nodes)")
    p.add_argument("--namespace", default=os.environ.get(
        "DAEMONSET_NAMESPACE", ""))
    p.add_argument("--daemon-image", default=os.environ.get("DAEMONSET_IMAGE", ""))
    p.add_argument("--enable-webhook", action="store_true", default=True)
    p.add_argument("--disable-webhook", dest="enable_webhook", action="store_false")
    p.add_argument("--metrics-port", type=int, default=DEFAULT_METRICS_PORT)
    p.add_argument("--health-port", type=int, default=DEFAULT_HEALTH_PORT)
    p.add_argument("--lease-file", default=None,
                   help="single-writer lease file (leader election, "
                        "main.go:76-85); default <export-dir>/manager.lease "
                        "when --export-dir is set, 'none' disables")
    p.add_argument("--lease-duration", type=float, default=15.0,
                   help="lease duration in seconds; a crashed leader is "
                        "taken over after at most this long")
    args = p.parse_args(argv)

    # Mirrors the hard env guards at main.go:87-99.
    if not args.daemon_image:
        p.error("DAEMONSET_IMAGE environment variable or --daemon-image must be set")
    if not args.namespace:
        p.error("DAEMONSET_NAMESPACE environment variable or --namespace must be set")

    logging.basicConfig(
        level=logging.INFO, format="%(asctime)s %(name)s %(levelname)s %(message)s"
    )
    lease = None
    lease_file = args.lease_file
    if lease_file is None and args.export_dir:
        lease_file = os.path.join(args.export_dir, "manager.lease")
    if lease_file and lease_file != "none":
        from .lease import FileLease

        os.makedirs(os.path.dirname(os.path.abspath(lease_file)),
                    exist_ok=True)
        lease = FileLease(lease_file, duration_s=args.lease_duration)
        log.info("single-writer lease at %s (duration %.0fs)",
                 lease_file, args.lease_duration)

    mgr = Manager(
        namespace=args.namespace,
        daemon_image=args.daemon_image,
        enable_webhook=args.enable_webhook,
        export_dir=args.export_dir,
        apply_dir=args.apply_dir,
        register_nodes=args.register_node,
        metrics_port=args.metrics_port,
        health_port=args.health_port,
        lease=lease,
    )
    stop = threading.Event()

    def on_signal(*_a):
        stop.set()
        mgr._stop.set()  # unblocks a standby _await_lease wait too

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGINT, on_signal)
    if not mgr.start():  # blocks in standby until the lease is acquired
        log.info("exiting before leadership (signalled in standby)")
        return 0
    try:
        # a lease loss stop()s the manager from its renew thread; exit
        # the process then (controller-runtime semantics) so a supervisor
        # can restart us into standby
        while not stop.wait(0.5):
            if mgr.lease_lost:
                log.error("exiting after lease loss")
                return 1
    finally:
        mgr.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
