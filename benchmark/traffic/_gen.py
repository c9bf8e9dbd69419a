"""Seeded generators of the benchmark's rule tables and packets.

Copied from the program at commit f44625a8 so that a later change there
cannot move the yardstick.  What came from where:

- ``random_tables``: ``infw/testing.py`` ``random_tables_fast`` and
  ``random_rules_bulk``.  Returns plain arrays instead of compiled
  tables; the prefix-length mixes are parameters (the configuration
  file states them).
- ``pareto_copies``, ``trace_packets``: the benchmark's own, after the
  trace generator of ClassBench (Taylor & Turner, "ClassBench: A Packet Classification
  Benchmark", IEEE INFOCOM 2005): headers inside randomly picked
  filters, each repeated a Pareto-distributed number of times.
- ``nodestate_doc``: ``chip_smoke.py`` ``nodestate_doc``, ``_cidr`` and
  ``_rule``, over the arrays.

Nothing here imports the program: the reference classifier and the
producer process use these arrays as they are.
"""
from __future__ import annotations

import ipaddress
from dataclasses import dataclass
from typing import Dict, Sequence

import numpy as np

TCP, UDP, SCTP, ICMP, ICMPV6 = 6, 17, 132, 1, 58
_PROTOS = (TCP, UDP, SCTP, ICMP, ICMPV6, 0)
_PROTO_NAMES = {TCP: "TCP", UDP: "UDP", SCTP: "SCTP", ICMP: "ICMP",
                ICMPV6: "ICMPv6", 0: ""}


@dataclass
class Table:
    """One rule table: entry ``e`` matches source prefix
    ``ip[e]/mask_len[e]`` on interface ``ifindex[e]`` and scans
    ``rules[e]`` rows [ruleId, proto, port start, port end (0: single
    port, else exclusive), icmp type, icmp code, action (1 deny, 2
    allow)]; row 0 is never a rule.  IPv4 prefixes sit in bytes 0-3
    with the other twelve zero."""

    ifindex: np.ndarray   # (E,) int64
    mask_len: np.ndarray  # (E,) int64, 0..128
    ip: np.ndarray        # (E, 16) uint8
    rules: np.ndarray     # (E, W, 7) int32

    def __len__(self) -> int:
        return int(self.ifindex.shape[0])


@dataclass
class Packets:
    """Per-packet header fields, one array each (the program's
    ``PacketBatch`` columns).  ``kind``: 0 malformed, 1 IPv4, 2 IPv6,
    3 other ethertype; ``ip_words`` are the big-endian words of the
    16-byte source address."""

    kind: np.ndarray
    l4_ok: np.ndarray
    ifindex: np.ndarray
    ip_words: np.ndarray
    proto: np.ndarray
    dst_port: np.ndarray
    icmp_type: np.ndarray
    icmp_code: np.ndarray
    pkt_len: np.ndarray

    FIELDS = ("kind", "l4_ok", "ifindex", "ip_words", "proto", "dst_port",
              "icmp_type", "icmp_code", "pkt_len")

    def __len__(self) -> int:
        return int(self.kind.shape[0])

    def slice(self, lo: int, hi: int) -> "Packets":
        return Packets(**{f: getattr(self, f)[lo:hi] for f in self.FIELDS})


def random_rules(rng: np.random.Generator, n: int, width: int) -> np.ndarray:
    """(n, width, 7) rule rows: index == order == ruleId, row 0 empty,
    mixed protocols, half port ranges and half single ports."""
    rows = np.zeros((n, width, 7), np.int32)
    if width < 2:
        return rows
    fill_p = rng.uniform(0.3, 1.0, (n, 1))
    populated = rng.random((n, width)) < fill_p
    populated[:, 0] = False
    order = np.broadcast_to(np.arange(width, dtype=np.int32), (n, width))
    proto = np.asarray(_PROTOS)[rng.integers(0, len(_PROTOS), (n, width))]
    is_transport = (proto == TCP) | (proto == UDP) | (proto == SCTP)
    is_icmp = (proto == ICMP) | (proto == ICMPV6)
    start = rng.integers(1, 65000, (n, width))
    use_range = rng.random((n, width)) < 0.5
    span = rng.integers(1, 500, (n, width))
    end = np.where(use_range, np.minimum(start + span, 65535), 0)
    rows[..., 0] = np.where(populated, order, 0)
    rows[..., 1] = np.where(populated, proto, 0)
    rows[..., 2] = np.where(populated & is_transport, start, 0)
    rows[..., 3] = np.where(populated & is_transport, end, 0)
    rows[..., 4] = np.where(populated & is_icmp,
                            rng.integers(0, 256, (n, width)), 0)
    rows[..., 5] = np.where(populated & is_icmp,
                            rng.integers(0, 3, (n, width)), 0)
    rows[..., 6] = np.where(populated, rng.integers(1, 3, (n, width)), 0)
    return rows


def random_tables(rng: np.random.Generator, n_entries: int, width: int,
                  v6_fraction: float, ifindexes: Sequence[int],
                  v4_prefix_lens: Sequence[int],
                  v6_prefix_lens: Sequence[int],
                  group_size: int = 8) -> Table:
    """``n_entries`` distinct entries clustered in groups that share a
    base address (nested and sibling prefixes), prefix lengths drawn
    uniformly from the given mixes."""
    v4_lens = np.asarray(v4_prefix_lens, np.int64)
    v6_lens = np.asarray(v6_prefix_lens, np.int64)
    ifx_all = np.asarray(ifindexes, np.int64)
    seen = set()
    out_if, out_mask, out_ip, out_rules = [], [], [], []
    while len(out_if) < n_entries:
        n = int((n_entries - len(out_if)) * 1.4) + 64
        is_v6 = rng.random(n) < v6_fraction
        n_groups = max(1, n // group_size)
        bases = rng.integers(0, 256, (n_groups, 16), dtype=np.uint8)
        gid = rng.integers(0, n_groups, n)
        ip = bases[gid].copy()
        perturb = rng.random(n) < 0.5
        pos = rng.integers(1, 16, n)
        val = rng.integers(0, 256, n, dtype=np.uint8)
        rows_i = np.arange(n)[perturb]
        ip[rows_i, pos[perturb]] = val[perturb]
        mask_len = np.where(
            is_v6,
            v6_lens[rng.integers(0, len(v6_lens), n)],
            v4_lens[rng.integers(0, len(v4_lens), n)],
        ).astype(np.int64)
        ip[~is_v6, 4:] = 0
        ifindex = ifx_all[rng.integers(0, len(ifx_all), n)]
        rules = random_rules(rng, n, width)
        ip_bytes = [bytes(row) for row in ip]
        for i in range(n):
            m = int(mask_len[i])
            nb, rem = m // 8, m % 8
            data = ip_bytes[i][:nb]
            if rem:
                data += bytes([ip_bytes[i][nb] & ((0xFF << (8 - rem)) & 0xFF)])
            ident = (int(ifindex[i]), m, data)
            if ident in seen:
                continue
            seen.add(ident)
            out_if.append(int(ifindex[i]))
            out_mask.append(m)
            out_ip.append(ip[i])
            out_rules.append(rules[i])
            if len(out_if) >= n_entries:
                break
    return Table(
        ifindex=np.asarray(out_if, np.int64),
        mask_len=np.asarray(out_mask, np.int64),
        ip=np.asarray(out_ip, np.uint8).reshape(-1, 16),
        rules=np.asarray(out_rules, np.int32).reshape(-1, width, 7),
    )


def relabel(rng: np.random.Generator, table: Table) -> Table:
    """The same prefix tree under other names: every entry's first four
    address bytes XORed with one random key, and every rule list drawn
    anew.  XOR with one key maps prefixes to prefixes and keeps which
    entry nests in which, so a trie of the result has the shape of the
    original's, level by level: a seed changes the content, not the
    sizes.  IPv4 entries stay IPv4 (bytes 4-15 are untouched)."""
    key = rng.integers(0, 256, 4, dtype=np.uint8)
    ip = table.ip.copy()
    ip[:, :4] ^= key
    n, width = table.rules.shape[:2]
    return Table(ifindex=table.ifindex.copy(), mask_len=table.mask_len.copy(),
                 ip=ip, rules=random_rules(rng, n, width))


def _words(ip: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(ip).view(">u4").astype(np.uint32).reshape(-1, 4)


def pareto_copies(rng: np.random.Generator, n: int, pareto_a: float,
                  pareto_b: float, max_copies: int) -> np.ndarray:
    """Run lengths that sum to ``n``: each ``ceil(b / u ** (1 / a))``
    with ``u`` uniform on (0, 1] (ClassBench's Pareto copies; ``b = 0``
    gives 1), at most ``max_copies``; the last run is cut to fit."""
    runs, total = [], 0
    while total < n:
        k = max(1024, n - total)
        if pareto_b > 0:
            u = 1.0 - rng.random(k)
            c = np.ceil(pareto_b / u ** (1.0 / pareto_a))
            c = np.minimum(c, max_copies).astype(np.int64)
        else:
            c = np.ones(k, np.int64)
        runs.append(c)
        total += int(c.sum())
    copies = np.concatenate(runs)
    h = int(np.searchsorted(np.cumsum(copies), n)) + 1
    copies = copies[:h]
    copies[-1] -= int(copies.sum()) - n
    return copies


def trace_packets(rng: np.random.Generator, table: Table, copies: np.ndarray,
                  wildcard_protos: Sequence[int], frame_sizes: Sequence[int],
                  frame_weights: Sequence[float]) -> Packets:
    """Packets by ClassBench's trace generator: header ``i`` is drawn
    inside a filter picked uniformly at random (here an entry's prefix on
    its interface and one of its populated rules) and repeated
    ``copies[i]`` times in a row.  Frame lengths follow the given mix."""
    T = len(table)
    if T == 0:
        raise ValueError("a trace needs a table with entries")
    h, n = len(copies), int(np.sum(copies))
    e = rng.integers(0, T, h)
    rows = table.rules[e]                                # (h, W, 7)
    populated = rows[..., 0] != 0
    # a uniform pick among an entry's populated rows (row 0 when none is)
    score = np.where(populated, rng.random(populated.shape), -1.0)
    rule = rows[np.arange(h), np.argmax(score, axis=1)]  # (h, 7)

    m = table.mask_len[e]
    bits = np.unpackbits(table.ip[e], axis=1)
    rand = rng.integers(0, 2, bits.shape, dtype=np.uint8)
    keep = np.arange(128)[None, :] < m[:, None]
    ip = np.packbits(np.where(keep, bits, rand), axis=1)
    is_v4 = (m <= 32) & ~np.any(table.ip[e, 4:] != 0, axis=1)
    ip[is_v4, 4:] = 0
    kind = np.where(is_v4, 1, 2).astype(np.int32)

    rproto = rule[:, 1]
    wild = np.asarray(wildcard_protos, np.int32)
    proto = np.where(rproto != 0, rproto,
                     wild[rng.integers(0, len(wild), h)]).astype(np.int32)
    start, end = rule[:, 2], rule[:, 3]
    in_range = start + (rng.random(h) * np.maximum(end - start, 1)).astype(np.int64)
    dst_port = np.where(
        np.isin(rproto, (TCP, UDP, SCTP)), np.where(end == 0, start, in_range),
        rng.integers(0, 65536, h)).astype(np.int32)
    is_icmp = np.isin(rproto, (ICMP, ICMPV6))
    icmp_type = np.where(is_icmp, rule[:, 4], 0).astype(np.int32)
    icmp_code = np.where(is_icmp, rule[:, 5], 0).astype(np.int32)

    sizes = np.asarray(frame_sizes, np.int32)
    w = np.asarray(frame_weights, np.float64)
    pkt_len = sizes[rng.choice(len(sizes), size=n, p=w / w.sum())]

    def rep(a):
        return np.repeat(a, copies, axis=0)

    return Packets(kind=rep(kind), l4_ok=np.ones(n, np.int32),
                   ifindex=rep(table.ifindex[e].astype(np.int32)),
                   ip_words=_words(rep(ip)), proto=rep(proto),
                   dst_port=rep(dst_port), icmp_type=rep(icmp_type),
                   icmp_code=rep(icmp_code), pkt_len=pkt_len)


# -- the table as a NodeState --------------------------------------------------


def _cidr(ip: np.ndarray, mask: int) -> str:
    data = bytes(ip)
    if mask <= 32 and not any(data[4:]):
        return f"{ipaddress.IPv4Address(data[:4])}/{mask}"
    return f"{ipaddress.IPv6Address(data)}/{mask}"


def _rule(row) -> dict:
    order, proto, p_start, p_end, itype, icode, action = (int(v) for v in row)
    name = _PROTO_NAMES[proto]
    pc = {"protocol": name}
    if name in ("TCP", "UDP", "SCTP"):
        pc[name.lower()] = {"ports": f"{p_start}-{p_end}" if p_end else p_start}
    elif name in ("ICMP", "ICMPv6"):
        pc[name.lower()] = {"icmpType": itype, "icmpCode": icode}
    return {"order": order, "protocolConfig": pc,
            "action": "Deny" if action == 1 else "Allow"}


def nodestate_doc(table: Table, node: str, ifaces: Dict[int, str]) -> dict:
    """The NodeState that compiles back to ``table``: one sourceCIDR entry
    per table entry, its populated rule rows as ordered rules."""
    per_iface = {name: [] for name in ifaces.values()}
    for e in range(len(table)):
        per_iface[ifaces[int(table.ifindex[e])]].append({
            "sourceCIDRs": [_cidr(table.ip[e], int(table.mask_len[e]))],
            "rules": [_rule(r) for r in table.rules[e] if r[0] != 0],
        })
    return {"metadata": {"name": node},
            "spec": {"interfaceIngressRules": per_iface}}
