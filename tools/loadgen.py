#!/usr/bin/env python
"""Open-loop Poisson/burst load generator for the daemon's ingest dir.

Drives a RUNNING daemon the way the SLO tier (bench_slo) drives the
in-process scheduler: packets are scheduled by an arrival process
(``infw.testing.poisson_arrivals`` / ``burst_arrivals``) at a fixed
offered load, grouped into frames files of ``--file-packets`` packets,
and each file is dropped into ``<state-dir>/ingest/`` at its FIRST
packet's scheduled arrival time.

Open-loop discipline (the coordinated-omission rule): the drop schedule
is computed up front against one anchor timestamp and each write sleeps
until its ABSOLUTE scheduled time — never "write, then sleep the
interval" — so a slow consumer (or a slow writer) makes the generator
fall visibly behind schedule (reported at exit) instead of silently
stretching the offered load.  A closed-loop generator that paces off its
own completions would hide exactly the queueing a latency SLO exists to
measure.

The packet mix is synthetic (uniform random v4/v6 addresses and
protocols — deny rate depends on the loaded ruleset); determinism per
``--seed`` covers addresses, ports AND arrival times, so two runs offer
byte-identical streams on identical schedules.

Usage:
    python tools/loadgen.py --out <state-dir>/ingest --rate 100000 \\
        --n 1000000 [--burst 256] [--file-packets 4096] [--seed 7] \\
        [--ifindex 10] [--v6-fraction 0.3] [--dry-run]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

from _common import setup_repo_path

setup_repo_path()

from infw import testing  # noqa: E402
from infw.daemon import write_frames_file_v2  # noqa: E402
from infw.obs.pcap import FramesBuf, build_frames_bulk  # noqa: E402


def synth_columns(rng: np.random.Generator, n: int, v6_fraction: float,
                  established_fraction: float = 0.0,
                  file_packets: int = 4096):
    """Uniform synthetic packet columns (no table bias — loadgen does
    not know the daemon's ruleset), flow-pool expanded.

    ``established_fraction`` > 0 switches on flow locality: the columns
    draw from a flow pool via the chunk-aware assignment
    (infw.testing.flow_locality_fids, chunked at ``file_packets`` so
    one dropped frames file / ring record is the cache's insert
    granularity) — the hit-rate-ladder workload for a daemon running
    --flow-table.  Byte-deterministic per (seed, arguments)."""
    if established_fraction > 0.0:
        fid, _fresh, n_flows = testing.flow_locality_fids(
            rng, n, established_fraction, chunk_packets=file_packets
        )
    else:
        fid = np.arange(n)
        n_flows = n
    kind = np.where(
        rng.random(n_flows) < v6_fraction, 2, 1
    ).astype(np.int32)
    ip = rng.integers(0, 256, (n_flows, 16), dtype=np.uint8)
    ip[kind == 1, 4:] = 0
    ip_words = np.ascontiguousarray(ip).view(">u4").astype(np.uint32)
    ip_words = ip_words.reshape(n_flows, 4)
    proto = np.asarray([6, 17, 132, 1, 58], np.int32)[
        rng.integers(0, 5, n_flows)
    ]
    dst_port = rng.integers(0, 65536, n_flows).astype(np.int32)
    icmp_type = rng.integers(0, 256, n_flows).astype(np.int32)
    icmp_code = rng.integers(0, 3, n_flows).astype(np.int32)
    return {
        "kind": kind[fid], "ip_words": ip_words[fid], "proto": proto[fid],
        "dst_port": dst_port[fid], "icmp_type": icmp_type[fid],
        "icmp_code": icmp_code[fid],
    }, n_flows


def synth_payload(rng: np.random.Generator, n: int, shape: str,
                  plen: int, pattern_seed: int, n_patterns: int,
                  attack_fraction: float, file_packets: int):
    """Payload-prefix columns for the --ring producer (ISSUE-19):
    ``http`` is the benign HTTP-ish request mix
    (infw.payload.benign_payloads); ``attack-mix`` overwrites a seeded
    fraction of lanes with signature-bearing prefixes
    (infw.payload.attack_payloads) drawn from the SAME deterministic
    pattern set a daemon gets from ``--payload <n_patterns>`` at the
    same seed — so the measuring side knows exactly which lanes must
    match.  Returns (pay (n, plen) uint8, plens (n,) int32, meta);
    the meta carries per-record ground-truth label bitmaps in the
    attack-label encoding (decode_attack_labels) plus the pattern-set
    coordinates.  Byte-deterministic per (seeded rng, arguments);
    the header stream is untouched (payload rides beside the wire)."""
    from infw.payload import (
        attack_payloads,
        benign_payloads,
        signature_patterns,
    )

    pay, plens = benign_payloads(rng, n, plen=plen)
    meta = {
        "payload_shape": shape,
        "payload_prefix_bytes": int(plen),
        # ring-record cost per lane: the uint8 prefix column plus the
        # int32 valid-byte count word
        "payload_bytes_per_packet": int(plen) + 4,
        "payload_pattern_seed": int(pattern_seed),
        "payload_patterns": int(n_patterns),
    }
    if shape == "attack-mix":
        pats = signature_patterns(
            np.random.default_rng(pattern_seed), n_patterns, plen=plen
        )
        mask = rng.random(n) < float(attack_fraction)
        k = int(mask.sum())
        if k:
            apay, alens = attack_payloads(rng, k, pats, plen=plen)
            pay[mask] = apay
            plens[mask] = alens
        meta["payload_signature_packets"] = k
        # same per-record hex-bitmap label encoding as the header
        # attacks: which lanes the generator planted signatures in
        # (decode_attack_labels recovers the mask).  NOTE ~15% of the
        # planted lanes deliberately straddle the truncation boundary
        # and must NOT match — the label marks "signature-bearing",
        # not "must match"; exact match truth is the host oracle
        # (infw.backend.cpu_ref.payload_match_ref) over these columns.
        meta["payload_labels"] = {
            "record_bitmaps_hex": encode_attack_labels(
                mask, file_packets
            ),
        }
    return pay, np.asarray(plens, np.int32), meta


def encode_attack_labels(mask: np.ndarray, file_packets: int) -> list:
    """Per-record ground-truth label bitmaps: the (n,) bool attack-lane
    mask packed little-bit-first per record window and hex-encoded —
    one string per dropped file / ring record, so a measuring consumer
    scores precision/recall against EXACTLY the lanes the generator
    overwrote (not just "packets from attacker IPs", which undercounts
    when an attacker address collides with background traffic).
    Byte-deterministic: same mask -> same strings."""
    mask = np.asarray(mask, bool)
    fp = max(int(file_packets), 1)
    out = []
    for lo in range(0, len(mask), fp):
        out.append(np.packbits(
            mask[lo : lo + fp], bitorder="little"
        ).tobytes().hex())
    return out


def decode_attack_labels(hex_bitmaps: list, n: int,
                         file_packets: int) -> np.ndarray:
    """Inverse of encode_attack_labels -> the (n,) bool mask."""
    fp = max(int(file_packets), 1)
    mask = np.zeros(n, bool)
    for i, h in enumerate(hex_bitmaps):
        lo = i * fp
        hi = min(lo + fp, n)
        bits = np.unpackbits(
            np.frombuffer(bytes.fromhex(h), np.uint8), bitorder="little"
        )[: hi - lo]
        mask[lo:hi] = bits.astype(bool)
    return mask


def attack_lane_src_ids(mask: np.ndarray, n_src: int) -> np.ndarray:
    """(n,) int32 attacker id per lane (-1 = background): attack lanes
    take source index (position in the attack sequence) % n_src — the
    deterministic assignment inject_attack uses, exposed so consumers
    can attribute each labeled lane to its attacker address without
    re-running the generator."""
    mask = np.asarray(mask, bool)
    ids = np.full(len(mask), -1, np.int32)
    idx = np.nonzero(mask)[0]
    ids[idx] = (np.arange(len(idx)) % max(int(n_src), 1)).astype(np.int32)
    return ids


def inject_attack(rng: np.random.Generator, c: dict, n: int, mode: str,
                  attack_fraction: float, attack_start: float,
                  n_attackers: int, file_packets: int):
    """Overwrite a seeded subset of the synthetic lanes with an attack
    (infw.testing.attack_trace_batch's modes, tables-free form): the
    attack begins at ``attack_start`` of the stream rounded down to a
    file/record boundary and claims ``attack_fraction`` of the lanes
    from then on.  Returns (tcp_flags, meta); byte-deterministic per
    (seeded rng, arguments).  Note deny verdicts depend on the DAEMON's
    loaded ruleset — the tables-free generator guarantees the top-talker
    / SYN-rate surfaces, and `--attack denystorm` aims every attack lane
    at one (src, dst_port) pair so a single deny rule covers it."""
    from infw.kernels.jaxpath import TCP_ACK, TCP_SYN

    cp = max(int(file_packets), 1)
    start = (int(n * float(attack_start)) // cp) * cp
    mask = (np.arange(n) >= start) & (
        rng.random(n) < float(attack_fraction)
    )
    k = int(mask.sum())
    n_src = 1 if mode == "portscan" else max(1, int(n_attackers))
    srcs = np.zeros((n_src, 4), np.uint32)
    srcs[:, 0] = rng.integers(1, 1 << 32, n_src, dtype=np.uint64)
    lane_src = np.arange(k) % n_src
    c["kind"][mask] = 1
    c["ip_words"][mask] = srcs[lane_src]
    c["proto"][mask] = 6
    c["icmp_type"][mask] = 0
    c["icmp_code"][mask] = 0
    flags = np.where(c["proto"] == 6, TCP_ACK, 0).astype(np.int32)
    if mode == "synflood":
        c["dst_port"][mask] = 443
        flags[mask] = TCP_SYN
    elif mode == "portscan":
        c["dst_port"][mask] = np.arange(k) % 65536
    else:  # denystorm: one (src, port) pair per attacker — rule-sized
        c["dst_port"][mask] = 80
    meta = {
        "attack": mode, "attack_start_packet": int(start),
        "attack_packets": k,
        "attackers": [
            ".".join(str(b) for b in int(s[0]).to_bytes(4, "big"))
            for s in srcs
        ],
        # per-RECORD ground-truth labels (ISSUE-14): honest
        # precision/recall needs per-lane truth, not just attacker IPs
        # — the onset record index, one hex bitmap of attack lanes per
        # dropped file / ring record (decode_attack_labels), and the
        # lane->attacker assignment stride (attack_lane_src_ids).
        # Features must never read these (label discipline); they
        # exist for the measuring consumer only.
        "labels": {
            "onset_record": int(start) // cp,
            "attack_src_stride": int(n_src),
            "record_bitmaps_hex": encode_attack_labels(mask, cp),
        },
    }
    return flags, meta


def synth_batch(rng: np.random.Generator, n: int, v6_fraction: float,
                ifindex: int, established_fraction: float = 0.0,
                file_packets: int = 4096, attack=None):
    """Synthetic columns -> frames buffer (the file-drop producer)."""
    c, n_flows = synth_columns(rng, n, v6_fraction,
                               established_fraction, file_packets)
    meta = {}
    if attack is not None:
        _flags, meta = inject_attack(
            rng, c, n, attack["mode"], attack["fraction"],
            attack["start"], attack["attackers"], file_packets,
        )
        # frames carry no TCP flag bytes (parse_frames_buf degrades
        # flags to 0) — SYN-rate telemetry needs the --ring producer;
        # the top-talker / deny-storm surfaces work on either path
    fb = build_frames_bulk(c["kind"], c["ip_words"], c["proto"],
                           c["dst_port"], c["icmp_type"], c["icmp_code"])
    fb.ifindex = np.full(n, int(ifindex), np.uint32)
    return fb, n_flows, meta


def synth_wire_batch(rng: np.random.Generator, n: int, v6_fraction: float,
                     ifindex: int, established_fraction: float = 0.0,
                     file_packets: int = 4096, attack=None):
    """Synthetic columns -> PacketBatch (the --ring producer: packed
    wire records, no frames round-trip).  pkt_len is synthesized
    deterministically; every synthetic proto is l4-parseable.  With
    ``attack``, the batch carries the injected TCP flags column (the
    ring record format ships it, so pure-SYN floods reach the daemon's
    flow/telemetry tiers intact)."""
    from infw.packets import PacketBatch

    c, n_flows = synth_columns(rng, n, v6_fraction,
                               established_fraction, file_packets)
    meta = {}
    flags = None
    if attack is not None:
        flags, meta = inject_attack(
            rng, c, n, attack["mode"], attack["fraction"],
            attack["start"], attack["attackers"], file_packets,
        )
    batch = PacketBatch(
        kind=c["kind"],
        l4_ok=np.ones(n, np.int32),
        ifindex=np.full(n, int(ifindex), np.int32),
        ip_words=np.ascontiguousarray(c["ip_words"], np.uint32),
        proto=c["proto"],
        dst_port=c["dst_port"],
        icmp_type=c["icmp_type"],
        icmp_code=c["icmp_code"],
        pkt_len=rng.integers(60, 1500, n).astype(np.int32),
    )
    if flags is not None:
        batch.tcp_flags = flags
    return batch, n_flows, meta


def _ring_main(args, rng, offs) -> int:
    """Ring producer: one packed-wire record per --file-packets window,
    written IN PLACE into the daemon's shared-memory ingest ring at its
    first packet's scheduled arrival time (open-loop; a full ring blocks
    and the stall is reported as schedule lag, never silently absorbed
    into a stretched offered load)."""
    from infw.ring import IngestRing, push_batch_slice

    batch, n_flows, attack_meta = synth_wire_batch(
        rng, args.n, args.v6_fraction, args.ifindex,
        established_fraction=args.established_fraction,
        file_packets=args.file_packets, attack=_attack_dict(args),
    )
    pay = plens = None
    payload_meta = {}
    if args.payload != "none":
        # a CHILD rng keyed off --seed: the header stream stays
        # byte-identical to the same run with --payload none (payload
        # rides beside the wire, never perturbs it)
        pay, plens, payload_meta = synth_payload(
            np.random.default_rng([args.seed, 0x7061796C]), args.n,
            args.payload, args.payload_plen, args.payload_seed,
            args.payload_patterns, args.payload_attack_fraction,
            args.file_packets,
        )
    fp = int(args.file_packets)
    n_rec = -(-args.n // fp)
    rec_starts = offs[::fp][:n_rec]
    summary = {
        "n": int(args.n), "rate_pps": float(args.rate),
        "process": f"burst:{args.burst}" if args.burst > 0 else "poisson",
        "mode": "ring", "records": int(n_rec), "file_packets": fp,
        "duration_s": float(offs[-1]), "seed": int(args.seed),
        "established_fraction": float(args.established_fraction),
        "n_flows": int(n_flows), **attack_meta, **payload_meta,
    }
    print(json.dumps(summary), flush=True)
    if args.dry_run:
        return 0
    ring = IngestRing.attach(args.ring)
    t0 = time.monotonic()
    worst_lag = 0.0
    # two DIFFERENT failure signals an open-loop producer must not
    # conflate: time reserve() spent blocked on a full ring is CONSUMER
    # backpressure (the daemon fell behind; by design the ring blocks
    # rather than drops), while lag beyond that is the PRODUCER falling
    # behind its own schedule (pack/copy too slow for --rate).  The
    # ring's blocked_us counter (this process's attach) provides the
    # split.
    worst_producer_lag = 0.0
    blocked_s = 0.0
    for i in range(n_rec):
        target = t0 + float(rec_starts[i])
        lag = time.monotonic() - target
        if lag < 0:
            time.sleep(-lag)
        else:
            worst_lag = max(worst_lag, lag)
            worst_producer_lag = max(worst_producer_lag, lag - blocked_s)
        lo, hi = i * fp, min((i + 1) * fp, args.n)
        push_batch_slice(ring, batch, lo, hi, payload=pay,
                         payload_len=plens, timeout=30.0)
        blocked_s = ring.counter_values()["ring_blocked_us_total"] / 1e6
    done = time.monotonic() - t0
    print(json.dumps({
        "offered_duration_s": float(offs[-1]),
        "actual_duration_s": done,
        "worst_schedule_lag_s": worst_lag,
        "worst_producer_lag_s": worst_producer_lag,
        "ring_blocked_s": blocked_s,
        "ring_backpressured": blocked_s > 0.01,
        "fell_behind": worst_producer_lag > 0.01,
        **{k: int(v) for k, v in ring.counter_values().items()},
    }), flush=True)
    if blocked_s > 0.01:
        print("loadgen: WARNING ring backpressure blocked the producer "
              f"for {blocked_s*1e3:.1f} ms total (consumer fell behind) "
              "— offered load was lower than requested",
              file=sys.stderr)
    if worst_producer_lag > 0.01:
        print("loadgen: WARNING fell behind its open-loop schedule by "
              f"{worst_producer_lag*1e3:.1f} ms net of ring blocking "
              "(slow producer) — offered load was lower than requested",
              file=sys.stderr)
    return 0


def _attack_dict(args):
    if args.attack is None:
        return None
    return {"mode": args.attack, "fraction": args.attack_fraction,
            "start": args.attack_start, "attackers": args.attackers}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="infw-loadgen", description=__doc__)
    p.add_argument("--out", default=None,
                   help="ingest directory of the target daemon "
                        "(file-drop mode; exactly one of --out/--ring)")
    p.add_argument("--rate", type=float, required=True,
                   help="offered load, packets/second")
    p.add_argument("--n", type=int, required=True, help="total packets")
    p.add_argument("--burst", type=int, default=0,
                   help="burst size: >0 switches the arrival process "
                        "from Poisson to back-to-back bursts at the "
                        "same mean rate (testing.burst_arrivals)")
    p.add_argument("--file-packets", type=int, default=4096,
                   help="packets per dropped frames file")
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--ifindex", type=int, default=10)
    p.add_argument("--v6-fraction", type=float, default=0.3)
    p.add_argument("--established-fraction", type=float, default=0.0,
                   help="flow locality: fraction of packets repeating a "
                        "flow from an earlier frames file (chunk-aware, "
                        "infw.testing.flow_locality_fids) — drive a "
                        "--flow-table daemon at a controlled hit rate")
    p.add_argument("--established-ladder", action="store_true",
                   help="emit the 0/50/90/99%% established-flow ladder: "
                        "four sub-directories <out>/ef00|ef50|ef90|ef99, "
                        "each a full manifest-disciplined drop schedule "
                        "at its rung's flow locality (byte-deterministic "
                        "per --seed)")
    p.add_argument("--ring", default=None,
                   help="RING PRODUCER MODE: instead of dropping frames "
                        "files, attach to a daemon's shared-memory "
                        "ingest ring (--ring on the daemon side, which "
                        "creates it) and write one PACKED WIRE record "
                        "per --file-packets window IN PLACE at its "
                        "scheduled time — no per-chunk file syscalls, "
                        "no per-chunk buffer allocation; a full ring "
                        "blocks (backpressure) and counts as schedule "
                        "lag.  Record format: see README 'Resident "
                        "serving'")
    p.add_argument("--attack", choices=("synflood", "portscan", "denystorm"),
                   default=None,
                   help="inject a seeded adversarial traffic mix (the "
                        "telemetry tier's workload, "
                        "infw.testing.attack_trace_batch modes): a "
                        "deterministic subset of lanes after "
                        "--attack-start becomes the attack.  synflood = "
                        "pure-SYN TCP from --attackers sources (SYN "
                        "flags ship in --ring mode; frames files carry "
                        "no flag bytes); portscan = one source sweeping "
                        "dst ports; denystorm = one (src, port 80) pair "
                        "per attacker, sized for a single deny rule on "
                        "the daemon side.  Manifest records mode, start "
                        "and attacker addresses")
    p.add_argument("--attack-fraction", type=float, default=0.4,
                   help="fraction of post-start lanes the attack claims "
                        "(default 0.4)")
    p.add_argument("--attack-start", type=float, default=0.25,
                   help="where the attack begins, as a fraction of the "
                        "stream, rounded down to a file/record boundary "
                        "(default 0.25)")
    p.add_argument("--attackers", type=int, default=2,
                   help="distinct attack sources (portscan always uses "
                        "1; default 2)")
    p.add_argument("--payload", choices=("none", "http", "attack-mix"),
                   default="none",
                   help="payload-prefix traffic shape (--ring mode "
                        "only; frames files carry no payload bytes): "
                        "http = benign HTTP-ish request prefixes "
                        "(infw.payload.benign_payloads); attack-mix = "
                        "the same plus a seeded "
                        "--payload-attack-fraction of lanes bearing "
                        "signatures from the deterministic pattern set "
                        "(--payload-seed/--payload-patterns — the same "
                        "set a daemon loads with --payload N at that "
                        "seed), labels in the manifest.  The target "
                        "daemon must run --payload so its ring slots "
                        "carry the column")
    p.add_argument("--payload-plen", type=int, default=64,
                   help="payload prefix bytes per packet (a "
                        "PAYLOAD_PREFIX_WIDTHS bucket: 64 or 128; "
                        "default 64); the manifest records the "
                        "resulting payload-column bytes/packet")
    p.add_argument("--payload-patterns", type=int, default=32,
                   help="signature pattern-set size for attack-mix "
                        "(default 32)")
    p.add_argument("--payload-seed", type=int, default=0,
                   help="pattern-set seed for attack-mix (default 0 — "
                        "matches the daemon's --payload default set)")
    p.add_argument("--payload-attack-fraction", type=float, default=0.1,
                   help="fraction of lanes carrying a planted "
                        "signature in attack-mix (default 0.1)")
    p.add_argument("--dry-run", action="store_true",
                   help="print the schedule summary without writing or "
                        "sleeping")
    args = p.parse_args(argv)
    if not 0.0 <= args.attack_fraction <= 1.0:
        p.error("--attack-fraction must be in [0, 1]")
    if not 0.0 <= args.attack_start < 1.0:
        p.error("--attack-start must be in [0, 1)")
    if args.attackers < 1:
        p.error("--attackers must be >= 1")
    if args.rate <= 0 or args.n <= 0 or args.file_packets <= 0:
        p.error("--rate, --n and --file-packets must be positive")
    if not 0.0 <= args.established_fraction < 1.0:
        p.error("--established-fraction must be in [0, 1)")
    if (args.out is None) == (args.ring is None):
        p.error("exactly one of --out (file drops) or --ring (ring "
                "producer) is required")
    if args.payload != "none":
        if args.ring is None:
            p.error("--payload requires --ring (frames files carry no "
                    "payload bytes)")
        from infw.kernels.wire_decode import PAYLOAD_PREFIX_WIDTHS
        if args.payload_plen not in PAYLOAD_PREFIX_WIDTHS:
            p.error(f"--payload-plen must be one of "
                    f"{PAYLOAD_PREFIX_WIDTHS}")
        if args.payload_patterns < 1:
            p.error("--payload-patterns must be >= 1")
        if not 0.0 <= args.payload_attack_fraction <= 1.0:
            p.error("--payload-attack-fraction must be in [0, 1]")
    if args.ring and args.established_ladder:
        p.error("--established-ladder emits file-drop sub-runs; use "
                "--established-fraction with --ring")

    if args.established_ladder:
        # the hit-rate ladder: one full run per rung, each into its own
        # sub-directory with its own manifest (the measuring consumer
        # points a --flow-table daemon at one rung at a time)
        rc = 0
        base = list(argv) if argv is not None else sys.argv[1:]
        base = [a for i, a in enumerate(base)
                if a != "--established-ladder"
                and not (a == "--out" or (i > 0 and base[i - 1] == "--out"))
                and not a.startswith("--established-fraction")
                and not (i > 0 and base[i - 1] == "--established-fraction")]
        for ef in (0.0, 0.5, 0.9, 0.99):
            sub = os.path.join(args.out, f"ef{int(ef * 100):02d}")
            rc |= main(base + ["--out", sub,
                               "--established-fraction", str(ef)])
        return rc

    rng = np.random.default_rng(args.seed)
    if args.burst > 0:
        offs = testing.burst_arrivals(rng, args.rate, args.n,
                                      burst=args.burst)
    else:
        offs = testing.poisson_arrivals(rng, args.rate, args.n)
    if args.ring:
        return _ring_main(args, rng, offs)
    fb, n_flows, attack_meta = synth_batch(
        rng, args.n, args.v6_fraction, args.ifindex,
        established_fraction=args.established_fraction,
        file_packets=args.file_packets, attack=_attack_dict(args),
    )

    fp = int(args.file_packets)
    n_files = -(-args.n // fp)
    # each file drops at its FIRST packet's scheduled arrival; the
    # sidecar manifest records per-packet offsets so a measuring
    # consumer can reconstruct scheduled arrival times
    file_starts = offs[::fp][:n_files]
    summary = {
        "n": int(args.n), "rate_pps": float(args.rate),
        "process": f"burst:{args.burst}" if args.burst > 0 else "poisson",
        "files": int(n_files), "file_packets": fp,
        "duration_s": float(offs[-1]), "seed": int(args.seed),
        "established_fraction": float(args.established_fraction),
        "n_flows": int(n_flows), **attack_meta,
    }
    print(json.dumps(summary), flush=True)
    if args.dry_run:
        return 0

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "loadgen-manifest.json"), "w") as f:
        json.dump({**summary,
                   "file_start_offsets_s": [float(x) for x in file_starts]},
                  f)
    t0 = time.monotonic()
    worst_lag = 0.0
    for i in range(n_files):
        target = t0 + float(file_starts[i])
        lag = time.monotonic() - target
        if lag < 0:
            time.sleep(-lag)
        else:
            worst_lag = max(worst_lag, lag)
        # slice this file's window out of the contiguous frames buffer
        # (three array slices, no per-frame Python)
        lo = i * fp
        hi = min(lo + fp, args.n)
        start = int(fb.offsets[lo])
        end = int(fb.offsets[hi]) if hi < len(fb) else len(fb.buf)
        sub = FramesBuf.from_lengths(
            np.asarray(fb.buf[start:end]),
            np.asarray(fb.lengths[lo:hi]),
            np.asarray(fb.ifindex[lo:hi]),
        )
        write_frames_file_v2(
            os.path.join(args.out, f"load{i:06d}.frames"), sub
        )
    done = time.monotonic() - t0
    print(json.dumps({
        "offered_duration_s": float(offs[-1]),
        "actual_duration_s": done,
        "worst_schedule_lag_s": worst_lag,
        "fell_behind": worst_lag > 0.01,
    }), flush=True)
    if worst_lag > 0.01:
        print("loadgen: WARNING fell behind its open-loop schedule by "
              f"{worst_lag*1e3:.1f} ms — offered load was lower than "
              "requested; measured latencies must use the manifest's "
              "scheduled offsets, not file mtimes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
