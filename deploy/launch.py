#!/usr/bin/env python
"""Bundle-driven composition launcher.

The OLM bundle analogue made operational: where the reference's bundle/
ClusterServiceVersion tells OLM how to install and run the operator
(/root/reference/bundle/manifests/ingress-node-firewall.clusterserviceversion.yaml
declares the deployments, env contract and RBAC), this launcher READS
``deploy/bundle/manifest.json`` and brings up the declared composition —
events sidecar, manager (fan-out + apply dir), daemon (dataplane) — as
supervised processes wired through a shared state dir and events socket,
exactly like the reference daemonset wires its three containers
(bindata/manifests/daemon/daemonset.yaml:25-113).

Usage:
    python deploy/launch.py --state-dir /var/lib/infw [--backend tpu|cpu]
        [--node-name NAME] [--dry-run]

The component commands, their order, and the env contract all come from
the bundle; nothing here hand-rolls a run line.  Required env vars that
have well-known deployment defaults (DAEMONSET_IMAGE etc.) are defaulted
the way the kustomize overlays default them; any remaining missing
required var is a launch error naming the component and variable.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import shlex
import signal
import subprocess
import sys
import time

BUNDLE_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "bundle", "manifest.json")
REPO_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: deployment defaults for required env (the kustomize overlay role);
#: anything already in the environment wins
ENV_DEFAULTS = {
    "DAEMONSET_IMAGE": "infw:latest",
    "DAEMONSET_NAMESPACE": "ingress-node-firewall-system",
}


def load_bundle(path: str = BUNDLE_PATH) -> dict:
    with open(path) as f:
        bundle = json.load(f)
    if bundle.get("schema") != "infw.bundle/v1":
        raise SystemExit(f"{path}: unsupported bundle schema "
                         f"{bundle.get('schema')!r}")
    return bundle


def build_plan(bundle: dict, subs: dict, extra_args: dict | None = None,
               only: str | None = None, flag_env: dict | None = None,
               include: list | None = None):
    """[(name, argv, env)] in bundle launch order.  ``subs`` fills the
    run templates' <placeholders>; ``extra_args`` appends per-component
    argv (e.g. ephemeral ports for tests).  ``only`` selects a single
    component by name (the way a DaemonSet pod runs one declared
    container) — required for components marked ``standalone`` (e.g.
    daemon-multihost), which never join the default composition.
    ``include`` appends explicitly requested standalone components to
    the default order (--with-metrics-proxy): an explicit request is the
    same consent --component gives, so the standalone guard exempts
    them.  ``flag_env`` maps launcher flag names to values; a
    component's ``envFromFlags`` contract routes them into its
    environment."""
    components = bundle["components"]
    include = list(include or [])
    if only is not None:
        if only not in components:
            raise SystemExit(
                f"unknown component {only!r}; bundle declares "
                f"{sorted(components)}"
            )
        order = [only]
    else:
        order = list(bundle.get("launchOrder", sorted(components)))
        order += [n for n in include if n not in order]
    unknown = [n for n in order if n not in components]
    if unknown:
        raise SystemExit(f"bundle launchOrder names unknown components: {unknown}")
    # standalone components (daemon-multihost) carry an env contract the
    # default composition cannot satisfy — launching one there would hang
    # a distributed job on a rank that never joins; they are reachable
    # only through an explicit --component selection (or an explicit
    # ``include`` request).
    standalone_in_order = [
        n for n in order
        if components[n].get("standalone") and n != only and n not in include
    ]
    if standalone_in_order:
        raise SystemExit(
            f"standalone components {standalone_in_order} cannot join the "
            "default composition; launch them with --component"
        )
    # Conversely: multihost flags with no component consuming them would
    # silently launch a single-host plan while the coordinator waits for
    # this rank forever.
    if flag_env:
        consumed = {
            f for n in order
            for f in components[n].get("envFromFlags", {}).values()
        }
        dropped = sorted(
            f for f, v in flag_env.items()
            if v is not None and f not in consumed
        )
        if dropped:
            raise SystemExit(
                f"flags --{' --'.join(dropped)} are not consumed by any "
                "launched component (did you mean --component "
                "daemon-multihost?)"
            )
    plan = []
    for name in order:
        comp = components[name]
        # Split FIRST, substitute per token: a state dir or node name
        # containing spaces/quotes must stay one argv element (the shell
        # script this replaces quoted \"$STATE_DIR\" at every use).
        argv = []
        for tok in shlex.split(comp["run"]):
            # Detect placeholders on the TEMPLATE token, before
            # substitution: a substituted value that itself contains
            # angle brackets (a path, a node name) must not trip a
            # false "unfilled placeholder" error.
            unfilled = [
                m for m in re.findall(r"<([A-Za-z][A-Za-z0-9_-]*)>", tok)
                if m not in subs
            ]
            if unfilled:
                raise SystemExit(
                    f"component {name}: unfilled placeholder "
                    f"{', '.join(f'<{m}>' for m in unfilled)} in run "
                    f"token: {tok}"
                )
            for key, val in subs.items():
                tok = tok.replace(f"<{key}>", str(val))
            argv.append(tok)
        argv[0] = sys.executable  # the bundle says "python"; use ours
        env = dict(os.environ)
        # Override, don't setdefault: --node-name must name the WHOLE
        # composition — a stray exported NODE_NAME would otherwise split
        # it (manager registers --node-name while the daemon reads env
        # and polls for a NodeState that never appears).
        if subs.get("node-name"):
            env["NODE_NAME"] = str(subs["node-name"])
        # --backend names the whole composition too: the manager renders
        # the daemon backend from INFW_BACKEND without probing a device
        if subs.get("backend"):
            env["INFW_BACKEND"] = str(subs["backend"])
        for var, default in ENV_DEFAULTS.items():
            env.setdefault(var, default)
        # The bundle's envFromFlags contract: launcher flags become the
        # component's env (the daemonset fieldRef/env-injection role) —
        # an explicit flag beats an inherited environment variable.
        for var, flag in comp.get("envFromFlags", {}).items():
            val = (flag_env or {}).get(flag)
            if val is not None:
                env[var] = str(val)
        missing = [
            var for var in comp.get("env", {}).get("required", [])
            if not env.get(var)
        ]
        if missing:
            raise SystemExit(
                f"component {name}: missing required env {missing} "
                "(bundle env contract)"
            )
        argv += (extra_args or {}).get(name, [])
        plan.append((name, argv, env))
    return plan


def launch(plan, state_dir: str) -> int:
    """Spawn the plan in order; supervise until ANY component exits (the
    pod restart-policy model: the composition lives and dies as a unit,
    and an external supervisor restarts the whole thing) or a signal
    arrives, then tear everything down in reverse order."""
    os.makedirs(state_dir, exist_ok=True)
    procs = []

    def teardown(*_a):
        for name, p in reversed(procs):
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 15
        for name, p in reversed(procs):
            try:
                p.wait(timeout=max(0.1, deadline - time.time()))
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    signal.signal(signal.SIGTERM, lambda *_a: sys.exit(143))
    try:
        for name, argv, env in plan:
            log_path = os.path.join(state_dir, f"{name}.log")
            with open(log_path, "ab") as lf:
                p = subprocess.Popen(
                    argv, env=env, cwd=REPO_DIR,
                    stdout=lf, stderr=subprocess.STDOUT,
                )
            procs.append((name, p))
            print(f"launch: {name} pid={p.pid} log={log_path}", flush=True)
        # supervise: if ANY component dies, bring the composition down
        # (the pod restart-policy role; an external supervisor restarts us)
        while True:
            for name, p in procs:
                rc = p.poll()
                if rc is not None:
                    print(f"launch: {name} exited rc={rc}; tearing down",
                          flush=True)
                    return rc
            time.sleep(0.3)
    except (KeyboardInterrupt, SystemExit) as e:
        return int(getattr(e, "code", 130) or 0)
    finally:
        teardown()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--state-dir", default="/var/lib/infw")
    ap.add_argument("--backend", default=os.environ.get("INFW_BACKEND", "tpu"),
                    choices=("tpu", "cpu"))
    ap.add_argument("--node-name",
                    default=os.environ.get("NODE_NAME") or os.uname().nodename)
    ap.add_argument("--events-socket", default=None,
                    help="default: <state-dir>/events.sock")
    ap.add_argument("--bundle", default=BUNDLE_PATH)
    ap.add_argument("--ephemeral-ports", action="store_true",
                    help="bind daemon metrics/health to ephemeral ports "
                         "(tests / multiple compositions per host)")
    ap.add_argument("--component", default=None,
                    help="launch ONLY this bundle component (required for "
                         "standalone components, e.g. daemon-multihost)")
    ap.add_argument("--with-metrics-proxy", action="store_true",
                    help="add the authenticated metrics proxy to the "
                         "composition (TLS on by default — a self-signed "
                         "pair is minted under <state-dir>/tls when no "
                         "operator pair exists)")
    ap.add_argument("--insecure-metrics", action="store_true",
                    # a security knob must not misparse common spellings
                    # (False/NO/off) in the insecure direction:
                    # case-insensitive, "off" included
                    default=os.environ.get("INFW_INSECURE_METRICS", "")
                    .strip().lower()
                    not in ("", "0", "false", "no", "off"),
                    help="serve the metrics proxy over PLAINTEXT (the "
                         "bearer token then travels in the clear) — an "
                         "explicit opt-out of the default-on TLS; also "
                         "via INFW_INSECURE_METRICS=1")
    ap.add_argument("--coordinator", default=None,
                    help="multihost: coordinator host:port "
                         "(bundle envFromFlags -> INFW_COORDINATOR)")
    ap.add_argument("--num-processes", default=None,
                    help="multihost: total process count "
                         "(-> INFW_NUM_PROCESSES)")
    ap.add_argument("--process-id", default=None,
                    help="multihost: this host's rank (-> INFW_PROCESS_ID)")
    ap.add_argument("--dry-run", action="store_true",
                    help="print the launch plan and exit")
    args = ap.parse_args(argv)

    bundle = load_bundle(args.bundle)
    state_dir = os.path.abspath(args.state_dir)
    subs = {
        "state-dir": state_dir,
        "backend": args.backend,
        "node-name": args.node_name,
        "events-socket": args.events_socket
        or os.path.join(state_dir, "events.sock"),
    }
    # ephemeral ports for every component that declares the
    # metrics/health port pair in the bundle (daemon, daemon-multihost,
    # manager — components with OTHER ports, e.g. metrics-proxy, do not
    # accept these flags)
    extra = (
        {
            name: ["--metrics-port", "0", "--health-port", "0"]
            for name, comp in bundle["components"].items()
            if "metrics" in comp.get("ports", {})
        }
        if args.ephemeral_ports else {}
    )
    flag_env = {
        "coordinator": args.coordinator,
        "num-processes": args.num_processes,
        "process-id": args.process_id,
    }
    if args.with_metrics_proxy and args.component not in (None, "metrics-proxy"):
        # silently dropping the proxy would leave the operator believing
        # off-node metrics are TLS-fronted while nothing is listening
        raise SystemExit(
            "--with-metrics-proxy joins the DEFAULT composition; with "
            f"--component {args.component} nothing would launch the proxy "
            "— run a second launcher with --component metrics-proxy"
        )
    include = ["metrics-proxy"] if (
        args.with_metrics_proxy and args.component is None
        and "metrics-proxy" in bundle["components"]
    ) else []
    proxy_in_plan = args.component == "metrics-proxy" or bool(include)
    if proxy_in_plan:
        # DEFAULT-ON TLS (satellite of the reference posture: the
        # kube-rbac-proxy sidecar always terminates TLS): mint a
        # self-signed pair under the state dir unless the operator
        # explicitly opted into plaintext.  The bearer-token file the
        # run template points at is bootstrapped alongside so a fresh
        # state dir comes up authenticated, never open.
        proxy_args = []
        if not args.insecure_metrics:
            crt = os.path.join(state_dir, "tls", "metrics-tls.crt")
            key = os.path.join(state_dir, "tls", "metrics-tls.key")
            if not args.dry_run:
                if REPO_DIR not in sys.path:  # invoked by absolute path
                    sys.path.insert(0, REPO_DIR)
                from infw.obs.metricsproxy import ensure_self_signed

                crt, key = ensure_self_signed(os.path.join(state_dir, "tls"))
            proxy_args += ["--certfile", crt, "--keyfile", key]
        if not args.dry_run:
            token_path = os.path.join(state_dir, "metrics-token")
            if not os.path.exists(token_path):
                import secrets

                os.makedirs(state_dir, exist_ok=True)
                fd = os.open(token_path + ".tmp",
                             os.O_WRONLY | os.O_CREAT | os.O_TRUNC, 0o600)
                with os.fdopen(fd, "w") as f:
                    f.write(secrets.token_hex(32))
                os.replace(token_path + ".tmp", token_path)
        extra = dict(extra)
        extra["metrics-proxy"] = extra.get("metrics-proxy", []) + proxy_args
    plan = build_plan(bundle, subs, extra, only=args.component,
                      flag_env=flag_env, include=include)
    print(f"launch: bundle {bundle['name']} v{bundle['version']} "
          f"({len(plan)} components)", flush=True)
    if args.dry_run:
        for name, argv_, env in plan:
            print(f"  {name}: {' '.join(shlex.quote(a) for a in argv_)}")
            # envFromFlags routing is part of the plan — print it so a
            # dry run (and the tests) can verify the injected contract
            injected = bundle["components"][name].get("envFromFlags", {})
            for var in injected:
                if var in env:
                    print(f"    env {var}={env[var]}")
        return 0
    return launch(plan, state_dir)


if __name__ == "__main__":
    sys.exit(main())
