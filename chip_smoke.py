#!/usr/bin/env python
"""Smoke run of the node daemon's serving paths on one TPU chip.

Drives ``infw.daemon.Daemon(backend="tpu")`` — the object ``python -m
infw.daemon`` builds — through the calls its loop makes: a NodeState
JSON dropped into ``nodestates/`` and ``scan_nodestates_once()``,
frames files dropped into ``ingest/`` and ``process_ingest_once()``, the
``out/*.verdicts.bin`` sidecars read back.  Tables and packets come from
``infw.testing`` with fixed seeds, and every phase compares every
packet's verdict and the statistics delta with the native C++ reference
classifier (``CpuRefClassifier``, no JAX).

Phases (one chip):
  A  reference capacity: 1000 mixed v4/v6 CIDRs x 100-wide ordered rule
     lists over 3 interfaces (the reference's map limits, BASELINE
     config 2), 2^20 packets; the dense Pallas kernel must serve, not in
     interpret mode.
  B  scale: 100K CIDRs, a v4 bulk with 1000 v6 prefixes down to /128
     (BASELINE config 3), 2^20 packets; the trie walk and the fused
     Pallas deep walk must both serve.
  C  ring path: B's table served through the ingest ring with the
     resident fused admission program; a producer thread packs records
     as ``tools/loadgen.py --ring`` does, 256 admissions; no resident
     fallbacks and no compiles after the warm-up.

``--chips 4`` runs only the multi-chip phase: B's table served on a
``4x1`` (data-parallel) and a ``2x2`` (rules-sharded) mesh.

Each phase prints one line; the last line of stdout is
``{"ok": true, "device": {...}}``.  Any failure exits non-zero without
it.  The script refuses to run unless JAX's backend is a TPU.

    python chip_smoke.py [--chips 4]
"""
from __future__ import annotations

import argparse
import ipaddress
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

from infw import testing
from infw.backend.cpu_ref import CpuRefClassifier
from infw.compiler import compile_tables_from_content
from infw.daemon import Daemon, write_frames_file_v2
from infw.interfaces import Interface, InterfaceRegistry
from infw.obs.pcap import build_frames_bulk, parse_frames_buf
from infw.packets import concat

NODE = "smoke-node"
#: ifindex -> interface name of the three interfaces every phase serves
IFACES = {2: "eth0", 3: "eth1", 4: "eth2"}
_PROTO_NAMES = {6: "TCP", 17: "UDP", 132: "SCTP", 1: "ICMP", 58: "ICMPv6",
                0: ""}


class SmokeFailure(AssertionError):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Counts the XLA executables built (or loaded from the persistent
    cache) while it is open, and the seconds spent on them."""

    def __init__(self) -> None:
        self.count = 0
        self.seconds = 0.0

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.count += 1
            self.seconds += duration

    def __enter__(self) -> "CompileMeter":
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *_exc) -> None:
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on_event)


# -- rule tables as a NodeState ----------------------------------------------


def _cidr(key) -> str:
    mask = key.prefix_len - 32
    data = bytes(key.ip_data)
    if mask <= 32 and not any(data[4:]):
        return f"{ipaddress.IPv4Address(data[:4])}/{mask}"
    return f"{ipaddress.IPv6Address(data)}/{mask}"


def _rule(row) -> dict:
    order, proto, p_start, p_end, itype, icode, action = (int(v) for v in row)
    name = _PROTO_NAMES[proto]
    pc = {"protocol": name}
    if name in ("TCP", "UDP", "SCTP"):
        pc[name.lower()] = {
            "ports": f"{p_start}-{p_end}" if p_end else p_start
        }
    elif name in ("ICMP", "ICMPv6"):
        pc[name.lower()] = {"icmpType": itype, "icmpCode": icode}
    return {"order": order, "protocolConfig": pc,
            "action": "Deny" if action == 1 else "Allow"}


def nodestate_doc(tables) -> dict:
    """The NodeState CR that compiles back to ``tables``: one
    sourceCIDR entry per LPM key, its rule rows as ordered rules."""
    per_iface = {name: [] for name in IFACES.values()}
    for key, rows in tables.content.items():
        per_iface[IFACES[key.ingress_ifindex]].append({
            "sourceCIDRs": [_cidr(key)],
            "rules": [_rule(r) for r in rows if r[0] != 0],
        })
    return {
        "metadata": {"name": NODE},
        "spec": {"interfaceIngressRules": per_iface},
    }


def registry() -> InterfaceRegistry:
    reg = InterfaceRegistry()
    for index, name in IFACES.items():
        reg.add(Interface(name=name, index=index))
    return reg


def make_tables(seed: int, n_entries: int, width: int, v6_fraction: float):
    return testing.random_tables_fast(
        np.random.default_rng(seed), n_entries=n_entries, width=width,
        v6_fraction=v6_fraction, ifindexes=tuple(IFACES),
    )


# -- daemon driving ------------------------------------------------------------


def start_daemon(work: str, tag: str, tables, **kw) -> Daemon:
    """A fresh daemon on its own state dir with ``tables`` synced in
    through the NodeState file protocol."""
    state = os.path.join(work, tag)
    os.makedirs(state)
    daemon = Daemon(state_dir=state, node_name=NODE, backend="tpu",
                    registry=registry(), metrics_port=0, health_port=0,
                    **kw)
    path = os.path.join(daemon.nodestates_dir, f"{NODE}.json")
    with open(path + ".tmp", "w") as f:
        json.dump(nodestate_doc(tables), f)
    os.replace(path + ".tmp", path)
    daemon.scan_nodestates_once()
    clf = daemon.syncer.classifier
    check(clf is not None and clf.tables is not None,
          f"{tag}: the NodeState did not load (see the daemon log)")
    check(clf.tables.num_entries == tables.num_entries,
          f"{tag}: {clf.tables.num_entries} entries loaded, "
          f"{tables.num_entries} sent")
    return daemon


def frames_for(batch):
    fb = build_frames_bulk(batch.kind, batch.ip_words, batch.proto,
                           batch.dst_port, batch.icmp_type, batch.icmp_code,
                           l4_ok=batch.l4_ok)
    fb.ifindex = np.asarray(batch.ifindex, np.uint32)
    return fb


def serve_files(daemon: Daemon, batch, n_files: int, max_ticks: int = 64):
    """Drop ``batch`` as ``n_files`` frames files, tick the ingest loop
    until they are consumed, and return (the packets as the daemon
    parsed them, their verdicts from the out/ sidecars)."""
    bounds = np.linspace(0, len(batch), n_files + 1).astype(np.int64)
    names, parsed = [], []
    for i in range(n_files):
        sub = batch.take(np.arange(bounds[i], bounds[i + 1]))
        fb = frames_for(sub)
        parsed.append(parse_frames_buf(fb))
        name = f"f{i:04d}.frames"
        write_frames_file_v2(os.path.join(daemon.ingest_dir, name), fb)
        names.append(name)
    for _ in range(max_ticks):
        if not os.listdir(daemon.ingest_dir):
            break
        daemon.process_ingest_once()
    left = os.listdir(daemon.ingest_dir)
    check(not left, f"ingest files left unconsumed: {sorted(left)[:4]}")
    verdicts = [
        np.fromfile(os.path.join(daemon.out_dir, n + ".verdicts.bin"), "<u4")
        for n in names
    ]
    return concat(parsed), np.concatenate(verdicts)


def reference(tables, batch):
    """(packed verdicts, stats delta) of the native reference classifier.
    Its LPM is a linear scan, so the batch is split over the host's cores
    (the native call releases the GIL)."""
    from concurrent.futures import ThreadPoolExecutor

    ref = CpuRefClassifier()
    ref.load_tables(tables)
    bounds = np.linspace(0, len(batch), (os.cpu_count() or 1) * 4 + 1)
    parts = [np.arange(lo, hi) for lo, hi in
             zip(bounds[:-1].astype(np.int64), bounds[1:].astype(np.int64))]
    with ThreadPoolExecutor(os.cpu_count()) as pool:
        outs = list(pool.map(
            lambda idx: ref.classify(batch.take(idx), apply_stats=False),
            parts,
        ))
    ref.close()
    return (np.concatenate([o.results for o in outs]).astype(np.uint32),
            sum(o.stats_delta for o in outs))


def compare(tables, batch, verdicts, stats_delta) -> dict:
    """Mismatch counts against the native reference classifier."""
    check(len(verdicts) == len(batch),
          f"{len(verdicts)} verdicts for {len(batch)} packets")
    want, want_stats = reference(tables, batch)
    return {
        "verdict_mismatches": int(
            (np.asarray(verdicts, np.uint32) != want).sum()
        ),
        "stats_mismatches": int((stats_delta != want_stats).sum()),
    }


def observe(clf) -> dict:
    """What served: read while the daemon still holds its classifier."""
    devices = getattr(clf, "mesh", None)
    return {
        "path": clf.active_path,
        "wire": sorted(clf.wire_stats()),
        "pallas": clf.pallas_stats(),
        "interpret": clf.interpret,
        "classifier": type(clf).__name__,
        "devices": (sorted({f"{d.platform}:{d.id}" for d in devices.devices.flat})
                    if devices is not None else []),
    }


def report(phase: str, tables, n_packets: int, result: dict, facts: dict,
           meter: CompileMeter, t0: float, **extra) -> dict:
    line = {
        "phase": phase,
        "entries": int(tables.num_entries),
        "packets": int(n_packets),
        **result,
        **{k: facts[k] for k in ("path", "wire", "pallas")},
        "compiles": meter.count,
        "compile_s": round(meter.seconds, 3),
        "wall_s": round(time.perf_counter() - t0, 3),
        **extra,
    }
    print(f"phase {phase} " + json.dumps(line), flush=True)
    check(result["verdict_mismatches"] == 0 and result["stats_mismatches"] == 0,
          f"phase {phase}: verdicts or statistics differ from the reference")
    return line


def file_phase(phase: str, work: str, tables, n_packets: int, seed: int,
               n_files: int, **daemon_kw) -> dict:
    """Shared body of the file-ingest phases: returns what served, with
    the printed report line under "line"."""
    t0 = time.perf_counter()
    batch = testing.random_batch_fast(
        np.random.default_rng(seed), tables, n_packets
    )
    with CompileMeter() as meter:
        daemon = start_daemon(work, phase, tables, **daemon_kw)
        try:
            clf = daemon.syncer.classifier
            before = clf.stats.snapshot()
            parsed, verdicts = serve_files(daemon, batch, n_files)
            delta = clf.stats.snapshot() - before
            facts = observe(clf)
        finally:
            daemon.stop()
    result = compare(tables, parsed, verdicts, delta)
    facts["line"] = report(phase, tables, n_packets, result, facts, meter, t0)
    return facts


# -- phases --------------------------------------------------------------------


def phase_a(work: str, n_entries: int = 1000, width: int = 100,
            n_packets: int = 1 << 20, interpret: bool = False,
            n_files: int = 16) -> dict:
    """Reference capacity through the dense Pallas kernel."""
    tables = make_tables(101, n_entries, width, v6_fraction=0.5)
    f = file_phase("A", work, tables, n_packets, 102, n_files)
    check(f["path"] == "dense", f"phase A served {f['path']}")
    check(f["interpret"] == interpret,
          f"phase A: Pallas interpret mode is {f['interpret']}")
    check(f["pallas"].get("pallas-dense", 0) >= n_packets,
          "phase A: the dense Pallas kernel did not serve every packet")
    return f["line"]


def phase_b_tables(n_entries: int = 100_000, width: int = 8,
                   n_deep_v6: int = 1000):
    """BASELINE config 3: a v4 bulk plus ``n_deep_v6`` v6 prefixes of
    /0../128.  The v6 share is what the fused deep walk's VMEM budget
    holds: its deep class is every slot past the second-deepest steering
    threshold, and at 1000 deep v6 prefixes that closure takes 5.8 MB of
    the 10 MB budget (a 30% v6 share needs ~190 MB, and the walk then
    never serves)."""
    bulk = make_tables(201, n_entries - n_deep_v6, width, v6_fraction=0.0)
    deep = make_tables(202, n_deep_v6, width, v6_fraction=1.0)
    content = dict(bulk.content)
    content.update(deep.content)
    return compile_tables_from_content(content, rule_width=width)


def phase_b(work: str, n_entries: int = 100_000, width: int = 8,
            n_deep_v6: int = 1000, n_packets: int = 1 << 20,
            n_files: int = 16) -> dict:
    """Trie scale: the XLA walk and the fused Pallas deep walk."""
    tables = phase_b_tables(n_entries, width, n_deep_v6)
    f = file_phase("B", work, tables, n_packets, 202, n_files,
                   fused_deep=True)
    walked = f["pallas"].get("pallas-walk", 0)
    check(f["path"] == "trie", f"phase B served {f['path']}")
    check(walked > 0, "phase B: no chunk went to the fused deep walk")
    check(walked < n_packets, "phase B: the XLA trie walk served nothing")
    return f["line"]


def phase_c(work: str, n_entries: int = 100_000, width: int = 8,
            n_deep_v6: int = 1000, admissions: int = 256,
            record_packets: int = 4096, warmup: int = 2) -> dict:
    """Ring -> resident fused admission program, fed by a producer
    thread; no fallbacks, and nothing compiles after the warm-up."""
    from infw.ring import IngestRing, push_batch_slice

    tables = phase_b_tables(n_entries, width, n_deep_v6)
    n_packets = admissions * record_packets
    # through a frames round trip, as in the file phases: the packets as
    # a parser delivers them (byte-wide fields wrap like on the wire)
    batch = parse_frames_buf(frames_for(testing.random_batch_fast(
        np.random.default_rng(302), tables, n_packets
    )))
    t0 = time.perf_counter()
    ring_file = os.path.join(work, "C.ring")
    got = {}
    errors = []

    def produce() -> None:
        ring = IngestRing.attach(ring_file)
        try:
            for i in range(admissions):
                lo = i * record_packets
                push_batch_slice(ring, batch, lo, lo + record_packets,
                                 timeout=120.0)
        except Exception as e:  # reported by the consumer
            errors.append(e)
        finally:
            ring.close()

    with CompileMeter() as meter:
        daemon = start_daemon(
            work, "C", tables, ring=ring_file, resident=True,
            max_tick_packets=record_packets, pipeline_depth=4,
        )
        daemon.ring_verdict_sink = lambda seq, res: got.__setitem__(
            seq, np.array(res, np.uint32))
        producer = threading.Thread(target=produce, name="smoke-producer")
        try:
            clf = daemon.syncer.classifier
            check(clf.resident is not None, "phase C: resident path is off")
            before = clf.stats.snapshot()
            producer.start()
            warm = None
            deadline = time.monotonic() + 600
            while len(got) < admissions and time.monotonic() < deadline:
                if warm is None and len(got) >= warmup:
                    warm = meter.count
                if daemon.process_ring_once() == 0:
                    if not producer.is_alive() and not len(daemon.ingest_ring):
                        break
                    time.sleep(0.001)
            delta = clf.stats.snapshot() - before
            fallbacks = int(clf.resident_counters().get(
                "resident_fallbacks_total", 0))
            facts = observe(clf)
        finally:
            if producer.ident is not None:
                producer.join(timeout=150)
            daemon.stop()
    check(not producer.is_alive(), "phase C: the producer did not finish")
    check(not errors, f"phase C: the producer failed: {errors[:1]}")
    check(len(got) == admissions,
          f"phase C: {len(got)} of {admissions} admissions served")
    late = meter.count - (warm if warm is not None else 0)
    verdicts = np.concatenate([got[s] for s in sorted(got)])
    result = compare(tables, batch, verdicts, delta)
    line = report("C", tables, n_packets, result, facts, meter, t0,
                  admissions=admissions, resident_fallbacks=fallbacks,
                  compiles_after_warmup=late)
    check(fallbacks == 0, f"phase C: {fallbacks} resident fallbacks")
    check(late == 0, f"phase C: {late} compiles after the warm-up")
    return line


def phase_mesh(work: str, n_entries: int = 100_000, width: int = 8,
               n_deep_v6: int = 1000, n_packets: int = 1 << 18,
               platform: str = "tpu", n_files: int = 4) -> list:
    """B's table on a 4x1 (data-parallel) and a 2x2 (rules-sharded)
    mesh of four chips."""
    tables = phase_b_tables(n_entries, width, n_deep_v6)
    lines = []
    for spec in ("4x1", "2x2"):
        f = file_phase(f"mesh-{spec}", work, tables, n_packets, 402,
                       n_files, mesh=spec, fused_deep=True)
        check(f["classifier"] == "MeshTpuClassifier",
              f"mesh {spec}: served by {f['classifier']}")
        check(len(f["devices"]) == 4
              and all(d.startswith(platform + ":") for d in f["devices"]),
              f"mesh {spec}: devices {f['devices']}")
        lines.append(f["line"])
    return lines


def device_line(expect_count: int) -> dict:
    from infw.platform import get_platform_info

    info = get_platform_info()
    check(info.num_devices >= expect_count,
          f"{expect_count} chips needed, {info.num_devices} visible")
    return {"platform": info.device_platforms[0], "kind": info.device_kind,
            "count": info.num_devices}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="1: phases A-C on one chip; 4: the mesh phase only")
    args = p.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(f"chip_smoke: JAX came up on {jax.default_backend()!r}, "
              "not a TPU", file=sys.stderr)
        return 2
    from infw.platform import enable_compile_cache

    enable_compile_cache()
    device = device_line(args.chips)
    with tempfile.TemporaryDirectory(prefix="infw-smoke-") as work:
        if args.chips == 4:
            phase_mesh(work)
        else:
            phase_a(work)
            phase_b(work)
            phase_c(work)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
