"""Test fixtures: adversarial ruleset and packet generators.

Replaces the reference's veth+netcat traffic harness
(/root/reference/pkg/ebpfsyncer/ebpfsyncer_test.go:1236-1318) with synthetic
rule tables and packet tensors; the reachability tables of that suite become
golden verdict vectors checked against the NumPy oracle.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from .compiler import (
    CompiledTables,
    LpmKey,
    TableColumns,
    compile_tables_from_columns,
    compile_tables_from_content,
)
from .constants import (
    IPPROTO_ICMP,
    IPPROTO_ICMPV6,
    IPPROTO_SCTP,
    IPPROTO_TCP,
    IPPROTO_UDP,
)
from .packets import PacketBatch

_PROTOS = [IPPROTO_TCP, IPPROTO_UDP, IPPROTO_SCTP, IPPROTO_ICMP, IPPROTO_ICMPV6, 0]


def random_rules(
    rng: np.random.Generator, width: int, max_rules: Optional[int] = None
) -> np.ndarray:
    """Random packed rule rows (width, 7) with the loader's invariants:
    index == order == ruleId, index 0 empty."""
    rows = np.zeros((width, 7), np.int32)
    n = rng.integers(0, max_rules if max_rules is not None else width - 1, endpoint=True)
    orders = rng.choice(np.arange(1, width), size=min(n, width - 1), replace=False)
    for order in orders:
        proto = _PROTOS[rng.integers(0, len(_PROTOS))]
        rows[order, 0] = order
        rows[order, 1] = proto
        if proto in (IPPROTO_TCP, IPPROTO_UDP, IPPROTO_SCTP):
            if rng.random() < 0.5:
                start = int(rng.integers(1, 65000))
                rows[order, 2] = start
                rows[order, 3] = int(rng.integers(start + 1, 65536))
            else:
                rows[order, 2] = int(rng.integers(1, 65536))
                rows[order, 3] = 0
        elif proto in (IPPROTO_ICMP, IPPROTO_ICMPV6):
            rows[order, 4] = int(rng.integers(0, 256))
            rows[order, 5] = int(rng.integers(0, 3))
        rows[order, 6] = int(rng.integers(1, 3))  # DENY or ALLOW
    return rows


def random_tables(
    rng: np.random.Generator,
    n_entries: int,
    ifindexes: Tuple[int, ...] = (2, 3),
    width: int = 16,
    v6_fraction: float = 0.3,
    overlap_fraction: float = 0.3,
) -> CompiledTables:
    """Random LPM content with deliberately overlapping prefixes (nested
    CIDRs of different lengths over shared bases) to stress longest-match
    tie-breaks."""
    content: Dict[LpmKey, np.ndarray] = {}
    bases: List[Tuple[bytes, bool]] = []
    while len(content) < n_entries:
        is_v6 = rng.random() < v6_fraction
        if bases and rng.random() < overlap_fraction:
            base, is_v6 = bases[rng.integers(0, len(bases))]
            data = bytearray(base)
            # perturb tail bytes to create nested/sibling prefixes
            pos = rng.integers(1, 16)
            data[pos] = rng.integers(0, 256)
            data = bytes(data)
        else:
            if is_v6:
                data = bytes(rng.integers(0, 256, 16, dtype=np.uint8))
            else:
                data = bytes(rng.integers(0, 256, 4, dtype=np.uint8)) + bytes(12)
            bases.append((data, is_v6))
        if is_v6:
            mask_len = int(rng.choice([0, 8, 13, 24, 32, 48, 64, 96, 128]))
        else:
            mask_len = int(rng.choice([0, 1, 8, 13, 16, 24, 30, 31, 32]))
            data = data[:4] + bytes(12)
        ifindex = int(ifindexes[rng.integers(0, len(ifindexes))])
        key = LpmKey(prefix_len=mask_len + 32, ingress_ifindex=ifindex, ip_data=data)
        content[key] = random_rules(rng, width)
    return compile_tables_from_content(content, rule_width=width)


def random_batch(
    rng: np.random.Generator,
    tables: CompiledTables,
    n_packets: int,
    ifindexes: Tuple[int, ...] = (2, 3, 9),
    hit_fraction: float = 0.7,
) -> PacketBatch:
    """Random packets biased toward table hits and match boundaries."""
    keys = list(tables.content.keys())
    b = n_packets
    kind = rng.choice([0, 1, 2, 3], size=b, p=[0.02, 0.55, 0.4, 0.03]).astype(np.int32)
    l4_ok = (rng.random(b) > 0.05).astype(np.int32)
    ifindex = np.array([ifindexes[i] for i in rng.integers(0, len(ifindexes), b)], np.int32)
    ip = np.zeros((b, 16), np.uint8)
    proto = np.zeros(b, np.int32)
    dst_port = np.zeros(b, np.int32)
    icmp_type = np.zeros(b, np.int32)
    icmp_code = np.zeros(b, np.int32)

    for i in range(b):
        if keys and rng.random() < hit_fraction:
            key = keys[rng.integers(0, len(keys))]
            data = bytearray(key.ip_data)
            if rng.random() < 0.5:
                # flip bits beyond the mask: should still match
                m = key.mask_len
                if m < 128:
                    bit = rng.integers(m, 128)
                    data[bit // 8] ^= 0x80 >> (bit % 8)
            else:
                # sometimes flip a bit inside the mask: should not match
                if key.mask_len > 0 and rng.random() < 0.3:
                    bit = rng.integers(0, key.mask_len)
                    data[bit // 8] ^= 0x80 >> (bit % 8)
            ip[i] = np.frombuffer(bytes(data), np.uint8)
            ifindex[i] = key.ingress_ifindex if rng.random() < 0.9 else ifindex[i]
            is_v4_key = all(d == 0 for d in data[4:]) and key.mask_len <= 32
            kind[i] = 1 if (is_v4_key and rng.random() < 0.8) else (2 if rng.random() < 0.8 else kind[i])
            # bias protocol/port toward a rule in that entry
            rows = tables.content[key]
            nz = np.nonzero(rows[:, 0])[0]
            if len(nz) and rng.random() < 0.8:
                r = rows[nz[rng.integers(0, len(nz))]]
                proto[i] = r[1] if r[1] != 0 else rng.integers(0, 255)
                if r[1] in (IPPROTO_TCP, IPPROTO_UDP, IPPROTO_SCTP):
                    if r[3] == 0:
                        dst_port[i] = r[2] + rng.integers(-1, 2)
                    else:
                        dst_port[i] = int(
                            rng.choice([r[2] - 1, r[2], r[3] - 1, r[3], r[3] + 1])
                        )
                    dst_port[i] = int(np.clip(dst_port[i], 0, 65535))
                elif r[1] in (IPPROTO_ICMP, IPPROTO_ICMPV6):
                    icmp_type[i] = r[4] + rng.integers(0, 2)
                    icmp_code[i] = r[5]
                continue
        # fully random packet
        ip[i] = rng.integers(0, 256, 16, dtype=np.uint8)
        if kind[i] == 1:
            ip[i, 4:] = 0
        proto[i] = int(rng.choice([6, 17, 132, 1, 58, 47, 0]))
        dst_port[i] = int(rng.integers(0, 65536))
        icmp_type[i] = int(rng.integers(0, 256))
        icmp_code[i] = int(rng.integers(0, 3))

    words = np.zeros((b, 4), np.uint32)
    for w in range(4):
        words[:, w] = (
            ip[:, 4 * w].astype(np.uint32) << 24
            | ip[:, 4 * w + 1].astype(np.uint32) << 16
            | ip[:, 4 * w + 2].astype(np.uint32) << 8
            | ip[:, 4 * w + 3].astype(np.uint32)
        )
    # v4 packets must have zero high words (host parser guarantees this)
    words[kind == 1, 1:] = 0
    return PacketBatch(
        kind=kind,
        l4_ok=l4_ok,
        ifindex=ifindex,
        ip_words=words,
        proto=proto,
        dst_port=dst_port,
        icmp_type=icmp_type,
        icmp_code=icmp_code,
        pkt_len=rng.integers(60, 1500, b).astype(np.int32),
    )


def random_tables_fast(
    rng: np.random.Generator,
    n_entries: int,
    ifindexes: Tuple[int, ...] = (2, 3),
    width: int = 16,
    v6_fraction: float = 0.3,
    group_size: int = 8,
    v4_prefix_lens: Sequence[int] = (0, 8, 12, 16, 20, 24, 24, 24, 28, 32),
    v6_prefix_lens: Sequence[int] = (0, 32, 40, 48, 48, 48, 56, 64, 96, 128),
) -> CompiledTables:
    """Vectorized large-table generator: like random_tables but NumPy-
    vectorized end to end so 100K-1M-entry tables build in seconds (the
    scale tier of BASELINE config 3/5).  Entries cluster into groups
    sharing a base address, prefix lengths drawn uniformly from the
    given lists (by default v4 peaked at /24, v6 at /48), so
    nested/sibling prefixes stress longest-match tie-breaks exactly like
    the per-entry generator."""
    content: Dict[LpmKey, np.ndarray] = {}
    seen = set()
    while len(content) < n_entries:
        n = int((n_entries - len(content)) * 1.4) + 64
        is_v6 = rng.random(n) < v6_fraction
        n_groups = max(1, n // group_size)
        bases = rng.integers(0, 256, (n_groups, 16), dtype=np.uint8)
        gid = rng.integers(0, n_groups, n)
        ip = bases[gid].copy()
        # sibling prefixes: perturb one tail byte on half the entries
        perturb = rng.random(n) < 0.5
        pos = rng.integers(1, 16, n)
        val = rng.integers(0, 256, n, dtype=np.uint8)
        rows_i = np.arange(n)[perturb]
        ip[rows_i, pos[perturb]] = val[perturb]

        v4_lens = np.asarray(v4_prefix_lens)
        v6_lens = np.asarray(v6_prefix_lens)
        mask_len = np.where(
            is_v6,
            v6_lens[rng.integers(0, len(v6_lens), n)],
            v4_lens[rng.integers(0, len(v4_lens), n)],
        ).astype(np.int64)
        ip[~is_v6, 4:] = 0
        ifindex = np.asarray(ifindexes)[rng.integers(0, len(ifindexes), n)]

        rules = random_rules_bulk(rng, n, width)

        ip_bytes = [bytes(row) for row in ip]
        for i in range(n):
            # exact masked-identity dedupe so the final entry count is
            # exactly n_entries (from_content would collapse aliases)
            m = int(mask_len[i])
            nb, rem = m // 8, m % 8
            data = ip_bytes[i][:nb]
            if rem:
                data += bytes([ip_bytes[i][nb] & ((0xFF << (8 - rem)) & 0xFF)])
            ident = (int(ifindex[i]), m, data)
            if ident in seen:
                continue
            seen.add(ident)
            key = LpmKey(
                prefix_len=int(mask_len[i]) + 32,
                ingress_ifindex=int(ifindex[i]),
                ip_data=ip_bytes[i],
            )
            content[key] = rules[i]
            if len(content) >= n_entries:
                break
    return compile_tables_from_content(content, rule_width=width)


def clean_tables_fast(
    rng: np.random.Generator,
    n_entries: int,
    ifindexes: Tuple[int, ...] = (2, 3),
    width: int = 4,
    v6_fraction: float = 0.3,
) -> CompiledTables:
    """Semantically CLEAN large-table generator: the scale of
    random_tables_fast with none of its (deliberate) semantic hazards —
    non-nested prefixes (distinct v4 /24s and v6 /48s, so no entry can
    be LPM-dead or conflict with an ancestor) carrying one distinct
    Allow rule each (no shadowing, no redundancy, no failsafe Deny).
    The static analyzer (infw.analysis.rules) must report ZERO findings
    on these tables at any size — the negative control of its property
    suite, and a clean substrate for future adversarial injections."""
    n_v6 = int(n_entries * v6_fraction)
    n_v4 = n_entries - n_v6
    if n_v4 > 1 << 24 or n_v6 > 1 << 40:
        raise ValueError("n_entries exceeds the disjoint-prefix space")
    content: Dict[LpmKey, np.ndarray] = {}
    v4_vals = rng.choice(1 << 24, size=n_v4, replace=False).astype(np.int64)
    # distinct 40-bit v6 prefixes without materializing the space:
    # random 64-bit draws deduped, topped up on collision
    v6_vals = np.unique(rng.integers(0, 1 << 40, n_v6 + 64, dtype=np.int64))
    while len(v6_vals) < n_v6:
        v6_vals = np.unique(np.concatenate([
            v6_vals, rng.integers(0, 1 << 40, n_v6, dtype=np.int64)
        ]))
    v6_vals = v6_vals[:n_v6]
    ifx = np.asarray(ifindexes)[rng.integers(0, len(ifindexes), n_entries)]
    ports = 70 + (np.arange(n_entries) % 60000)
    i = 0
    for v in v4_vals:
        data = int(v << 8).to_bytes(4, "big") + bytes(12)
        rows = np.zeros((width, 7), np.int32)
        rows[1] = [1, IPPROTO_TCP, ports[i], 0, 0, 0, 2]  # ALLOW
        content[LpmKey(24 + 32, int(ifx[i]), data)] = rows
        i += 1
    for v in v6_vals:
        data = (0x20 << 120 | int(v) << 80).to_bytes(16, "big")
        rows = np.zeros((width, 7), np.int32)
        rows[1] = [1, IPPROTO_TCP, ports[i], 0, 0, 0, 2]  # ALLOW
        content[LpmKey(48 + 32, int(ifx[i]), data)] = rows
        i += 1
    return compile_tables_from_content(content, rule_width=width)


def clean_columns_fast(
    rng: np.random.Generator,
    n_entries: int,
    ifindexes: Tuple[int, ...] = (2, 3),
    width: int = 4,
    v6_fraction: float = 0.3,
) -> TableColumns:
    """clean_tables_fast as COLUMNS: the same disjoint /24+/48 Allow-only
    distribution with zero per-key Python — the generator of the 10M
    bench/test tier, where even a C-level dict build costs real seconds.
    ``compile_tables_from_columns(clean_columns_fast(...))`` is the
    whole cold-build path."""
    n_v6 = int(n_entries * v6_fraction)
    n_v4 = n_entries - n_v6
    if n_v4 > 1 << 24 or n_v6 > 1 << 40:
        raise ValueError("n_entries exceeds the disjoint-prefix space")
    v4_vals = rng.choice(1 << 24, size=n_v4, replace=False).astype(np.int64)
    v6_vals = np.unique(rng.integers(0, 1 << 40, n_v6 + 64, dtype=np.int64))
    while len(v6_vals) < n_v6:
        v6_vals = np.unique(np.concatenate([
            v6_vals, rng.integers(0, 1 << 40, n_v6, dtype=np.int64)
        ]))
    v6_vals = v6_vals[:n_v6]
    ifx = np.asarray(ifindexes, np.int64)[
        rng.integers(0, len(ifindexes), n_entries)
    ]
    ip = np.zeros((n_entries, 16), np.uint8)
    # v4 /24: value << 8 as the first 4 big-endian bytes
    v4_words = (v4_vals << 8).astype(">u4")
    ip[:n_v4, :4] = v4_words.view(np.uint8).reshape(n_v4, 4)
    # v6 /48: 0x20 byte + 40-bit value in bytes 1..5
    v6_hi = (np.int64(0x20) << 40) | v6_vals
    v6_bytes = v6_hi.astype(">u8").view(np.uint8).reshape(n_v6, 8)
    ip[n_v4:, :6] = v6_bytes[:, 2:]
    plen = np.empty(n_entries, np.int32)
    plen[:n_v4] = 24 + 32
    plen[n_v4:] = 48 + 32
    ports = 70 + (np.arange(n_entries) % 60000)
    rules = np.zeros((n_entries, width, 7), np.int32)
    rules[:, 1, 0] = 1
    rules[:, 1, 1] = IPPROTO_TCP
    rules[:, 1, 2] = ports
    rules[:, 1, 6] = 2  # ALLOW
    return TableColumns(prefix_len=plen, ifindex=ifx, ip=ip, rules=rules)


def clean_tables_scale(
    rng: np.random.Generator,
    n_entries: int,
    ifindexes: Tuple[int, ...] = (2, 3),
    width: int = 4,
    v6_fraction: float = 0.3,
) -> CompiledTables:
    """clean_columns_fast through the vectorized compiler — the 10M-tier
    analogue of clean_tables_fast (same distribution family; the
    per-entry port sequence differs only in assignment order)."""
    return compile_tables_from_columns(
        clean_columns_fast(rng, n_entries, ifindexes, width, v6_fraction),
        rule_width=width,
    )


def gate_tripped_tables(
    rng: np.random.Generator,
    n_entries: int = 48,
    ifindexes: Tuple[int, ...] = (2, 3),
    width: int = 4,
) -> CompiledTables:
    """Tables whose joined-targets layout trips the duplication gate
    (jaxpath.JOINED_DUP_LIMIT / the 4096-row floor), so the device state
    keeps the INACTIVE ``(1, 1)`` joined placeholder on the trie path.

    Mid-stride prefixes (/17 under distinct /16 bases) leaf-push into
    2^(24-17) = 128 slots each, so ~40 entries already duplicate to
    >4096 joined positions — the exact layout regime of the PR-4
    placeholder bucket-padding bug, and the substrate of the state
    checker's injected-defect acceptance gate."""
    content: Dict[LpmKey, np.ndarray] = {}
    for i in range(n_entries):
        mask = 17 if i % 4 != 3 else 24  # mostly /17, some /24 siblings
        data = bytes([10, i % 256, (i // 256) % 2 * 128, 0]) + bytes(12)
        rows = np.zeros((width, 7), np.int32)
        rows[1] = [1, IPPROTO_TCP, 70 + (i % 60000), 0, 0, 0, 1 + i % 2]
        ifx = int(ifindexes[i % len(ifindexes)])
        content[LpmKey(mask + 32, ifx, data)] = rows
    return compile_tables_from_content(content, rule_width=width)


def random_rules_bulk(
    rng: np.random.Generator, n: int, width: int
) -> np.ndarray:
    """(n, width, 7) packed rule rows, vectorized version of random_rules:
    index == order == ruleId, index 0 empty, mixed protocols, half port
    ranges / half single ports, DENY or ALLOW actions."""
    rows = np.zeros((n, width, 7), np.int32)
    if width < 2:
        return rows
    # per-entry fill probability in [0.3, 1.0] so table density varies
    fill_p = rng.uniform(0.3, 1.0, (n, 1))
    populated = rng.random((n, width)) < fill_p
    populated[:, 0] = False  # order 0 reserved (catch-all slot semantics)
    order = np.broadcast_to(np.arange(width, dtype=np.int32), (n, width))
    proto = np.asarray(_PROTOS)[rng.integers(0, len(_PROTOS), (n, width))]
    is_transport = (
        (proto == IPPROTO_TCP) | (proto == IPPROTO_UDP) | (proto == IPPROTO_SCTP)
    )
    is_icmp = (proto == IPPROTO_ICMP) | (proto == IPPROTO_ICMPV6)
    start = rng.integers(1, 65000, (n, width))
    use_range = rng.random((n, width)) < 0.5
    span = rng.integers(1, 500, (n, width))
    end = np.where(use_range, np.minimum(start + span, 65535), 0)
    rows[..., 0] = np.where(populated, order, 0)
    rows[..., 1] = np.where(populated, proto, 0)
    rows[..., 2] = np.where(populated & is_transport, start, 0)
    rows[..., 3] = np.where(populated & is_transport, end, 0)
    rows[..., 4] = np.where(populated & is_icmp, rng.integers(0, 256, (n, width)), 0)
    rows[..., 5] = np.where(populated & is_icmp, rng.integers(0, 3, (n, width)), 0)
    rows[..., 6] = np.where(populated, rng.integers(1, 3, (n, width)), 0)
    return rows


def random_batch_fast(
    rng: np.random.Generator,
    tables: CompiledTables,
    n_packets: int,
    extra_ifindexes: Tuple[int, ...] = (9,),
    hit_fraction: float = 0.7,
) -> PacketBatch:
    """Vectorized version of random_batch: packets biased toward table
    hits (address sampled from a random entry, bits flipped beyond — or
    occasionally inside — the mask) and toward rule-match boundaries
    (protocol/port copied from a random populated rule of that entry).
    Generates 10M-packet batches in seconds for the replay tier."""
    b = n_packets
    T = int(tables.num_entries)
    kind = rng.choice([0, 1, 2, 3], size=b, p=[0.02, 0.55, 0.4, 0.03]).astype(np.int32)
    l4_ok = (rng.random(b) > 0.05).astype(np.int32)
    all_if = np.unique(
        np.concatenate([tables.key_words[:T, 0].astype(np.int64),
                        np.asarray(extra_ifindexes, np.int64)])
    )
    ifindex = all_if[rng.integers(0, len(all_if), b)].astype(np.int32)
    # random baseline
    ip = rng.integers(0, 256, (b, 16), dtype=np.uint8)
    proto = np.asarray([6, 17, 132, 1, 58, 47, 0])[rng.integers(0, 7, b)].astype(np.int32)
    dst_port = rng.integers(0, 65536, b).astype(np.int32)
    icmp_type = rng.integers(0, 256, b).astype(np.int32)
    icmp_code = rng.integers(0, 3, b).astype(np.int32)

    hit = rng.random(b) < (hit_fraction if T else 0.0)
    if T:
        e = rng.integers(0, T, b)
        # entry address bytes from the dense key words (big-endian words)
        ent_ip = (
            tables.key_words[:T, 1:5].astype(">u4").copy().view(np.uint8).reshape(T, 16)
        )
        ent_mask = tables.mask_len[:T].astype(np.int64)
        ent_if = tables.key_words[:T, 0].astype(np.int32)
        m = ent_mask[e]
        hip = ent_ip[e].copy()
        # flip a bit beyond the mask (still matches) or, 30% of the time
        # when flippable, inside the mask (usually breaks the match)
        beyond_ok = m < 128
        bit_beyond = (m + (rng.integers(0, 1 << 16, b) % np.maximum(128 - m, 1)))
        inside = (rng.random(b) < 0.3) & (m > 0)
        bit_inside = rng.integers(0, 1 << 16, b) % np.maximum(m, 1)
        bit = np.where(inside, bit_inside, np.where(beyond_ok, bit_beyond, 0))
        do_flip = beyond_ok | inside
        byte_i, mask_v = (bit // 8).astype(np.int64), (0x80 >> (bit % 8)).astype(np.uint8)
        sel = np.where(hit & do_flip)[0]
        hip[sel, byte_i[sel]] ^= mask_v[sel]
        ip[hit] = hip[hit]
        ifindex = np.where(hit & (rng.random(b) < 0.9), ent_if[e], ifindex)
        is_v4_key = (ent_mask[e] <= 32) & ~np.any(hip[:, 4:] != 0, axis=1)
        kind = np.where(
            hit & is_v4_key & (rng.random(b) < 0.8), 1,
            np.where(hit & ~is_v4_key & (rng.random(b) < 0.8), 2, kind),
        ).astype(np.int32)
        # bias protocol/port toward a random populated rule of the entry
        R = tables.rules.shape[1]
        ridx = rng.integers(0, R, b)
        rule = tables.rules[np.clip(e, 0, T - 1), ridx]  # (b, 7)
        has_rule = rule[:, 0] != 0
        use_rule = hit & has_rule & (rng.random(b) < 0.8)
        rproto = rule[:, 1]
        proto = np.where(use_rule & (rproto != 0), rproto, proto)
        is_tr = (rproto == IPPROTO_TCP) | (rproto == IPPROTO_UDP) | (rproto == IPPROTO_SCTP)
        jitter = rng.integers(-1, 2, b)
        port_single = np.clip(rule[:, 2] + jitter, 0, 65535)
        edge = np.stack([
            rule[:, 2] - 1, rule[:, 2], rule[:, 3] - 1, rule[:, 3], rule[:, 3] + 1
        ], 1)[np.arange(b), rng.integers(0, 5, b)]
        port_range = np.clip(edge, 0, 65535)
        dst_port = np.where(
            use_rule & is_tr,
            np.where(rule[:, 3] == 0, port_single, port_range),
            dst_port,
        ).astype(np.int32)
        is_ic = (rproto == IPPROTO_ICMP) | (rproto == IPPROTO_ICMPV6)
        icmp_type = np.where(
            use_rule & is_ic, rule[:, 4] + rng.integers(0, 2, b), icmp_type
        ).astype(np.int32)
        icmp_code = np.where(use_rule & is_ic, rule[:, 5], icmp_code).astype(np.int32)

    ip[kind == 1, 4:] = 0
    words = np.ascontiguousarray(ip).view(">u4").astype(np.uint32).reshape(b, 4)
    return PacketBatch(
        kind=kind,
        l4_ok=l4_ok,
        ifindex=ifindex,
        ip_words=words,
        proto=proto,
        dst_port=dst_port.astype(np.int32),
        icmp_type=icmp_type,
        icmp_code=icmp_code,
        pkt_len=rng.integers(60, 1500, b).astype(np.int32),
    )


# --- arrival processes (open-loop load generation) --------------------------
#
# The SLO serving tier (infw.scheduler, bench_slo, tools/loadgen.py)
# measures tail latency OPEN-LOOP: packet i is declared to arrive at a
# scheduled offset regardless of how the system is keeping up, and its
# latency is measured from that schedule — the coordinated-omission-safe
# methodology (a closed-loop generator that waits for completions before
# sending more silently excludes exactly the queueing it caused).


def poisson_arrivals(
    rng: np.random.Generator, rate_pps: float, n: int
) -> np.ndarray:
    """(n,) float64 cumulative arrival offsets (seconds) of a Poisson
    process at ``rate_pps`` — exponential inter-arrivals, deterministic
    per (seeded rng, rate, n)."""
    if rate_pps <= 0:
        raise ValueError(f"rate must be positive, got {rate_pps}")
    gaps = rng.exponential(1.0 / float(rate_pps), int(n))
    return np.cumsum(gaps)


def burst_arrivals(
    rng: np.random.Generator, rate_pps: float, n: int, burst: int = 64
) -> np.ndarray:
    """(n,) float64 arrival offsets of a bursty process at the SAME mean
    rate as the Poisson generator: packets arrive in back-to-back groups
    of ``burst`` with exponentially distributed gaps BETWEEN bursts
    (mean burst/rate) — the adversarial arrival shape for a coalescing
    scheduler (a whole burst lands on one admission decision)."""
    if rate_pps <= 0:
        raise ValueError(f"rate must be positive, got {rate_pps}")
    burst = max(1, int(burst))
    n = int(n)
    n_bursts = -(-n // burst)
    gaps = rng.exponential(burst / float(rate_pps), n_bursts)
    starts = np.cumsum(gaps)
    return np.repeat(starts, burst)[:n]


def stats_dict_from_array(stats4: np.ndarray) -> Dict[int, List[int]]:
    """(MAX_TARGETS, 4) int64 -> {ruleId: [ap, ab, dp, db]} with zero rows
    dropped, for comparison against the oracle's dict."""
    out: Dict[int, List[int]] = {}
    for rid in np.nonzero(stats4.any(axis=1))[0]:
        out[int(rid)] = [int(x) for x in stats4[rid]]
    return out


# --- flow-locality traffic (the stateful flow tier's workload) ---------------


def flow_locality_fids(
    rng: np.random.Generator, n: int, established_fraction: float,
    chunk_packets: int = 1024,
) -> Tuple[np.ndarray, np.ndarray, int]:
    """The chunk-aware flow-id assignment under flow_trace_batch (and
    tools/loadgen.py's established-fraction mode): returns (fid, fresh,
    n_flows) where ``fresh`` marks first occurrences and repeats only
    reference flows born in EARLIER chunks — so a verdict cache that
    inserts at chunk boundaries sees exactly ~established_fraction hits
    per steady-state chunk (chunk 0 is the all-fresh warmup)."""
    n = int(n)
    e = float(established_fraction)
    if not 0.0 <= e < 1.0:
        raise ValueError(
            f"established_fraction must be in [0, 1), got {e}"
        )
    cp = max(int(chunk_packets), 1)
    chunk = np.arange(n) // cp
    chunk_starts = np.arange(0, n, cp)
    fresh = (rng.random(n) >= e) | (chunk == 0)
    seen = np.cumsum(fresh)            # flows born through packet i
    # flows born BEFORE each packet's chunk (the repeat-eligible pool)
    born_before = np.concatenate(
        [[0], seen[chunk_starts[1:] - 1]]
    )[chunk]
    fresh = fresh | (born_before == 0)
    seen = np.cumsum(fresh)
    born_before = np.concatenate(
        [[0], seen[chunk_starts[1:] - 1]]
    )[chunk]
    pick = rng.random(n)
    fid = np.where(
        fresh, seen - 1,
        (pick * np.maximum(born_before, 1)).astype(np.int64),
    ).astype(np.int64)
    return fid, fresh, int(seen[-1])


def flow_trace_batch(
    rng: np.random.Generator,
    tables: CompiledTables,
    n_packets: int,
    established_fraction: float,
    chunk_packets: int = 1024,
    fin_fraction: float = 0.05,
) -> Tuple[PacketBatch, Dict[str, int]]:
    """Seeded packet stream with controlled flow locality — the workload
    of the flow tier's hit-rate ladder (bench_flow, tools/loadgen.py).

    ``established_fraction`` (e) is the per-chunk fraction of packets
    that repeat a flow born in an EARLIER chunk of ``chunk_packets``
    packets — chunk-aware on purpose: a verdict cache inserts a chunk's
    fresh flows only after that chunk's dispatch, so intra-chunk repeats
    of newborn flows can never hit and would silently dilute the ladder.
    Chunk 0 is the all-fresh warmup; every later chunk carries exactly
    ~e established traffic (TCP flows whose first packet is a pure SYN
    pay one extra miss each — the NEW -> EST handshake gate; the bench
    reports measured hit rates next to the nominal rungs).

    Flow definitions draw from random_batch_fast over ``tables`` (hit-
    biased addresses/rules), repaired to classification-eligible lanes
    (real IP kinds, l4_ok=1) so the locality knob is exact.  TCP flags:
    SYN on a TCP flow's first packet, ACK mid-stream, FIN|ACK on its
    last packet for ``fin_fraction`` of flows.  pkt_len varies per
    packet (it feeds statistics, never the flow key).  Byte-
    deterministic per (seeded rng, arguments).

    Returns (batch, meta) with meta = {"n_flows", "repeats"}."""
    n = int(n_packets)
    fid, fresh, n_flows = flow_locality_fids(
        rng, n, established_fraction, chunk_packets
    )
    pool = random_batch_fast(rng, tables, n_flows)
    # repair to eligible lanes: the locality knob must be exact
    kind = np.asarray(pool.kind)
    kind = np.where((kind == 1) | (kind == 2), kind, 1).astype(np.int32)
    v4 = kind == 1
    ipw = np.asarray(pool.ip_words).copy()
    ipw[v4, 1:] = 0
    batch = PacketBatch(
        kind=kind[fid],
        l4_ok=np.ones(n, np.int32),
        ifindex=np.asarray(pool.ifindex)[fid],
        ip_words=ipw[fid],
        proto=np.asarray(pool.proto)[fid],
        dst_port=np.asarray(pool.dst_port)[fid],
        icmp_type=np.asarray(pool.icmp_type)[fid],
        icmp_code=np.asarray(pool.icmp_code)[fid],
        pkt_len=rng.integers(60, 1500, n).astype(np.int32),
    )
    # TCP state arcs: SYN opens, ACK carries, FIN|ACK closes (sampled)
    from .kernels.jaxpath import TCP_ACK, TCP_FIN, TCP_SYN

    is_tcp = batch.proto == 6
    flags = np.where(is_tcp, TCP_ACK, 0).astype(np.int32)
    flags[fresh & is_tcp] = TCP_SYN
    last = np.zeros(n_flows, np.int64)
    np.maximum.at(last, fid, np.arange(n, dtype=np.int64))
    closing = last[rng.random(n_flows) < fin_fraction]
    closing = closing[is_tcp[closing]]
    flags[closing] = TCP_FIN | TCP_ACK
    batch.tcp_flags = flags
    return batch, {"n_flows": n_flows, "repeats": int(n - n_flows)}


# --- adversarial attack traces (the telemetry tier's workload) ---------------

ATTACK_MODES = ("synflood", "portscan", "denystorm")


def attack_trace_batch(
    rng: np.random.Generator,
    tables: CompiledTables,
    n_packets: int,
    mode: str = "synflood",
    attack_fraction: float = 0.4,
    attack_start: float = 0.25,
    chunk_packets: int = 1024,
    n_attackers: int = 2,
) -> Tuple[PacketBatch, Dict[str, object]]:
    """Seeded adversarial traffic mix for the telemetry tier
    (bench_telemetry, tools/loadgen.py --attack): background traffic
    with flow locality (flow_trace_batch at 50% established — the
    flow_locality_fids arcs) carrying an injected attack that begins at
    ``attack_start`` of the stream (rounded down to a chunk boundary)
    and claims ``attack_fraction`` of the lanes from then on.

    Modes:
    - ``synflood``: ``n_attackers`` v4 sources blast pure-SYN TCP at one
      port — the SYN-rate summary's surface (and the flow tier's NEW
      gate: these never enter the fast path);
    - ``portscan``: ONE v4 source sweeps dst ports sequentially — a
      top-talker with maximal key dispersion below the src;
    - ``denystorm``: attackers replay packets the ORACLE says this
      ruleset denies (sampled from a table-biased pool), driving the
      per-tenant deny fraction over the storm threshold.

    Byte-deterministic per (seeded rng, arguments).  Returns (batch,
    meta) with meta = {"mode", "start", "n_attack", "attackers":
    [(ip_words row, kind)], "attack_mask"}."""
    if mode not in ATTACK_MODES:
        raise ValueError(
            f"unknown attack mode {mode!r} (expected one of {ATTACK_MODES})"
        )
    n = int(n_packets)
    batch, meta = flow_trace_batch(
        rng, tables, n, 0.5, chunk_packets=chunk_packets
    )
    from .kernels.jaxpath import TCP_ACK, TCP_SYN

    cp = max(int(chunk_packets), 1)
    start = (int(n * float(attack_start)) // cp) * cp
    mask = (np.arange(n) >= start) & (rng.random(n) < float(attack_fraction))
    k = int(mask.sum())
    flags = np.asarray(batch.tcp_flags, np.int32)
    attackers: List[Tuple[np.ndarray, int]] = []
    if mode in ("synflood", "portscan"):
        n_src = 1 if mode == "portscan" else max(1, int(n_attackers))
        srcs = np.zeros((n_src, 4), np.uint32)
        srcs[:, 0] = rng.integers(1, 1 << 32, n_src, dtype=np.uint64)
        lane_src = np.arange(k) % n_src
        batch.kind[mask] = 1
        batch.l4_ok[mask] = 1
        batch.ip_words[mask] = srcs[lane_src]
        batch.proto[mask] = IPPROTO_TCP
        batch.icmp_type[mask] = 0
        batch.icmp_code[mask] = 0
        if mode == "synflood":
            batch.dst_port[mask] = 443
            flags[mask] = TCP_SYN  # pure SYN, never promotes
        else:
            batch.dst_port[mask] = np.arange(k) % 65536
            flags[mask] = TCP_ACK
        attackers = [(srcs[i].copy(), 1) for i in range(n_src)]
    else:  # denystorm: oracle-confirmed deny lanes, replayed verbatim
        from . import oracle

        pool = random_batch_fast(rng, tables, max(4 * n_attackers, 256))
        ref = oracle.classify(tables, pool)
        deny = np.nonzero((ref.results & 0xFF) == 1)[0]
        if len(deny) == 0:
            raise ValueError(
                "denystorm needs at least one oracle-DENY lane in the "
                "table-biased pool; got none (all-allow ruleset?)"
            )
        picks = deny[: max(1, int(n_attackers))]
        lane_src = np.arange(k) % len(picks)
        for field in ("kind", "l4_ok", "ifindex", "ip_words", "proto",
                      "dst_port", "icmp_type", "icmp_code"):
            getattr(batch, field)[mask] = np.asarray(
                getattr(pool, field)
            )[picks][lane_src]
        flags[mask] = np.where(
            np.asarray(pool.proto)[picks][lane_src] == IPPROTO_TCP,
            TCP_ACK, 0,
        )
        attackers = [
            (np.asarray(pool.ip_words)[i].copy(),
             int(np.asarray(pool.kind)[i])) for i in picks
        ]
    batch.tcp_flags = flags
    return batch, {
        "mode": mode, "start": int(start), "n_attack": k,
        "attackers": attackers, "attack_mask": mask,
        "n_flows": meta["n_flows"],
    }
