"""Plain reference classifier: what the ingress firewall's XDP program
computes per packet, over the benchmark's own arrays.

For each packet: a malformed or other-ethertype packet, or one whose L4
header did not parse, gets result 0.  Otherwise the longest prefix among
the entries of the packet's interface that covers its 16-byte source
address wins; an IPv4 packet's key is 32 address bits long, so only
entries of length 32 or less can match it.  The winner's rule rows are
scanned in order: a row with protocol 0 matches anything; a TCP, UDP or
SCTP row matches its protocol and a destination port equal to its start
(end 0) or in [start, end); an ICMP row (ICMPv6 for IPv6 packets)
matches its type and code.  The first match gives ``ruleId << 8 |
action``; no entry or no match gives 0.  Statistics count, per ruleId
below 1024, the packets and bytes allowed (action 2) and denied
(action 1).

It imports nothing of the program and reads none of its tables.
"""
from __future__ import annotations

import numpy as np

MAX_RULE_STATS = 1024
_TRANSPORT = (6, 17, 132)


def _mask_bytes(m: int) -> np.ndarray:
    bits = np.zeros(128, np.uint8)
    bits[:m] = 1
    return np.packbits(bits)


def _keys(ifindex: np.ndarray, ip: np.ndarray, m: int) -> np.ndarray:
    """(n,) 20-byte keys: big-endian interface index, then the address
    masked to ``m`` bits."""
    raw = np.empty((len(ifindex), 20), np.uint8)
    raw[:, :4] = np.asarray(ifindex, ">u4").view(np.uint8).reshape(-1, 4)
    raw[:, 4:] = ip & _mask_bytes(m)
    return raw.view("V20").ravel()


class Reference:
    """Built once per table; ``classify`` is vectorized over packets."""

    def __init__(self, table) -> None:
        self.rules = np.asarray(table.rules, np.int64)
        self._lpm = []  # (mask length, sorted keys, their entry indices)
        for m in sorted({int(x) for x in table.mask_len}, reverse=True):
            idx = np.nonzero(table.mask_len == m)[0]
            keys = _keys(table.ifindex[idx], table.ip[idx], m)
            order = np.argsort(keys, kind="stable")
            keys = keys[order]
            if len(keys) > 1 and (keys[1:] == keys[:-1]).any():
                raise ValueError(f"two entries share one /{m} prefix")
            self._lpm.append((m, keys, idx[order]))

    def lookup(self, pk) -> np.ndarray:
        """(n,) index of the matching entry, -1 where none matches."""
        n = len(pk)
        ip = np.ascontiguousarray(
            np.asarray(pk.ip_words, np.uint32).astype(">u4")
        ).view(np.uint8).reshape(n, 16)
        cap = np.where(pk.kind == 1, 32, 128)
        target = np.full(n, -1, np.int64)
        live = (pk.kind == 1) | (pk.kind == 2)
        live &= pk.l4_ok != 0
        for m, keys, entries in self._lpm:
            sel = np.nonzero(live & (target < 0) & (cap >= m))[0]
            if not len(sel):
                continue
            want = _keys(pk.ifindex[sel], ip[sel], m)
            pos = np.minimum(np.searchsorted(keys, want), len(keys) - 1)
            found = keys[pos] == want
            target[sel[found]] = entries[pos[found]]
        return target

    def classify(self, pk, block: int = 8192):
        """(results uint32, matched entry int64) for every packet."""
        target = self.lookup(pk)
        n = len(pk)
        results = np.zeros(n, np.uint32)
        for lo in range(0, n, block):
            hi = min(n, lo + block)
            t = target[lo:hi]
            hit = np.nonzero(t >= 0)[0]
            if not len(hit):
                continue
            rows = self.rules[t[hit]]  # (h, W, 7)
            rid, rproto, ps, pe, it, ic, act = (rows[..., k] for k in range(7))
            proto = pk.proto[lo:hi][hit].astype(np.int64)[:, None]
            dport = pk.dst_port[lo:hi][hit].astype(np.int64)[:, None]
            itype = pk.icmp_type[lo:hi][hit].astype(np.int64)[:, None]
            icode = pk.icmp_code[lo:hi][hit].astype(np.int64)[:, None]
            icmp_proto = np.where(pk.kind[lo:hi][hit] == 1, 1, 58)[:, None]
            same = (rproto != 0) & (rproto == proto)
            transport = np.isin(rproto, _TRANSPORT)
            port_ok = np.where(pe == 0, ps == dport, (ps <= dport) & (dport < pe))
            match = (rid != 0) & (
                (same & transport & port_ok)
                | (same & (rproto == icmp_proto) & (it == itype) & (ic == icode))
                | (rproto == 0)
            )
            first = np.argmax(match, axis=1)
            any_match = match[np.arange(len(hit)), first]
            k = np.arange(len(hit))
            res = (rid[k, first] << 8) | act[k, first]
            results[lo + hit] = np.where(any_match, res, 0).astype(np.uint32)
        return results, target


def rule_stats(results: np.ndarray, pkt_len: np.ndarray) -> np.ndarray:
    """(1024, 4) int64 [allowed packets, allowed bytes, denied packets,
    denied bytes] per ruleId."""
    out = np.zeros((MAX_RULE_STATS, 4), np.int64)
    action = results & 0xFF
    rid = (results >> 8).astype(np.int64)
    length = np.asarray(pkt_len, np.int64)
    for col, act in ((0, 2), (2, 1)):
        m = (action == act) & (rid < MAX_RULE_STATS)
        out[:, col] = np.bincount(rid[m], minlength=MAX_RULE_STATS)
        out[:, col + 1] = np.bincount(rid[m], weights=length[m],
                                      minlength=MAX_RULE_STATS).astype(np.int64)
    return out
