"""The plain reference against the program's scalar oracle, which
transliterates the XDP program packet by packet."""
import numpy as np
import pytest

from benchmark import reference
from benchmark.traffic import _gen


def edge_packets(rng, table, n: int, hit_fraction: float, extra_ifindexes=(9,)):
    """Packets at the reference's edges: malformed, other-ethertype and
    unparsed-L4 packets, unknown interfaces, addresses one bit inside or
    beyond an entry's mask, ports at rule boundaries +-1 (after
    ``infw/testing.py`` ``random_batch_fast``)."""
    T = len(table)
    kind = rng.choice([0, 1, 2, 3], size=n,
                      p=[0.02, 0.55, 0.4, 0.03]).astype(np.int32)
    l4_ok = (rng.random(n) > 0.05).astype(np.int32)
    all_if = np.unique(np.concatenate([
        table.ifindex, np.asarray(extra_ifindexes, np.int64)]))
    ifindex = all_if[rng.integers(0, len(all_if), n)].astype(np.int32)
    ip = rng.integers(0, 256, (n, 16), dtype=np.uint8)
    proto = np.asarray([6, 17, 132, 1, 58, 47, 0])[
        rng.integers(0, 7, n)].astype(np.int32)
    dst_port = rng.integers(0, 65536, n).astype(np.int32)
    icmp_type = rng.integers(0, 256, n).astype(np.int32)
    icmp_code = rng.integers(0, 3, n).astype(np.int32)

    hit = rng.random(n) < (hit_fraction if T else 0.0)
    if T:
        e = rng.integers(0, T, n)
        m = table.mask_len[e]
        hip = table.ip[e].copy()
        beyond_ok = m < 128
        bit_beyond = m + (rng.integers(0, 1 << 16, n) % np.maximum(128 - m, 1))
        inside = (rng.random(n) < 0.3) & (m > 0)
        bit_inside = rng.integers(0, 1 << 16, n) % np.maximum(m, 1)
        bit = np.where(inside, bit_inside, np.where(beyond_ok, bit_beyond, 0))
        do_flip = beyond_ok | inside
        byte_i = (bit // 8).astype(np.int64)
        mask_v = (0x80 >> (bit % 8)).astype(np.uint8)
        sel = np.where(hit & do_flip)[0]
        hip[sel, byte_i[sel]] ^= mask_v[sel]
        ip[hit] = hip[hit]
        ifindex = np.where(hit & (rng.random(n) < 0.9),
                           table.ifindex[e], ifindex).astype(np.int32)
        is_v4_key = (m <= 32) & ~np.any(hip[:, 4:] != 0, axis=1)
        kind = np.where(
            hit & is_v4_key & (rng.random(n) < 0.8), 1,
            np.where(hit & ~is_v4_key & (rng.random(n) < 0.8), 2, kind),
        ).astype(np.int32)
        W = table.rules.shape[1]
        rule = table.rules[e, rng.integers(0, W, n)]  # (n, 7)
        use_rule = hit & (rule[:, 0] != 0) & (rng.random(n) < 0.8)
        rproto = rule[:, 1]
        proto = np.where(use_rule & (rproto != 0), rproto, proto)
        is_tr = (rproto == _gen.TCP) | (rproto == _gen.UDP) | (rproto == _gen.SCTP)
        jitter = rng.integers(-1, 2, n)
        port_single = np.clip(rule[:, 2] + jitter, 0, 65535)
        edge = np.stack([rule[:, 2] - 1, rule[:, 2], rule[:, 3] - 1,
                         rule[:, 3], rule[:, 3] + 1], 1)[
            np.arange(n), rng.integers(0, 5, n)]
        port_range = np.clip(edge, 0, 65535)
        dst_port = np.where(use_rule & is_tr,
                            np.where(rule[:, 3] == 0, port_single, port_range),
                            dst_port).astype(np.int32)
        is_ic = (rproto == _gen.ICMP) | (rproto == _gen.ICMPV6)
        icmp_type = np.where(use_rule & is_ic,
                             rule[:, 4] + rng.integers(0, 2, n),
                             icmp_type).astype(np.int32) & 0xFF
        icmp_code = np.where(use_rule & is_ic, rule[:, 5],
                             icmp_code).astype(np.int32)

    ip[kind == 1, 4:] = 0
    return _gen.Packets(kind=kind, l4_ok=l4_ok, ifindex=ifindex.astype(np.int32),
                   ip_words=_gen._words(ip), proto=proto.astype(np.int32),
                   dst_port=dst_port.astype(np.int32), icmp_type=icmp_type,
                   icmp_code=icmp_code,
                   pkt_len=rng.integers(60, 1500, n).astype(np.int32))


def compiled(table):
    from infw.compiler import LpmKey, compile_tables_from_content

    content = {
        LpmKey(prefix_len=int(m) + 32, ingress_ifindex=int(i), ip_data=bytes(ip)): rows
        for i, m, ip, rows in zip(table.ifindex, table.mask_len, table.ip, table.rules)
    }
    return compile_tables_from_content(content, rule_width=table.rules.shape[1])


def batch(pk):
    from infw.packets import PacketBatch

    return PacketBatch(**{f: getattr(pk, f) for f in _gen.Packets.FIELDS})


@pytest.mark.parametrize("seed,entries,width,v6", [
    (1, 300, 8, 0.3), (2, 120, 100, 0.5), (3, 400, 4, 0.0), (4, 200, 16, 1.0),
])
@pytest.mark.parametrize("mix", ["trace", "edges"])
def test_reference_matches_oracle(seed, entries, width, v6, mix):
    from infw import oracle

    rng = np.random.default_rng(seed)
    table = _gen.random_tables(rng, entries, width, v6, (2, 3, 4),
                               [0, 8, 12, 16, 20, 24, 24, 28, 32],
                               [0, 32, 40, 48, 48, 56, 64, 96, 128])
    if mix == "trace":
        copies = _gen.pareto_copies(rng, 3000, 1.0, 0.1, 64)
        pk = _gen.trace_packets(rng, table, copies, (6, 17), (64, 594, 1518),
                                (7, 4, 1))
    else:
        pk = edge_packets(rng, table, 3000, 0.7, (9,))
    got, target = reference.Reference(table).classify(pk, block=512)
    want = oracle.classify(compiled(table), batch(pk))
    np.testing.assert_array_equal(got, want.results)
    stats = reference.rule_stats(got, pk.pkt_len)
    want_stats = np.zeros_like(stats)
    for rid, row in want.stats.items():
        want_stats[rid] = row
    np.testing.assert_array_equal(stats, want_stats)
    assert (target >= 0).sum() > 0


def test_cross_family_and_caps():
    """An IPv4 packet matches an IPv6 entry of length <= 32 on its first
    four bytes, and no entry longer than 32 bits; interfaces isolate."""
    ip = np.zeros((3, 16), np.uint8)
    ip[0, :4] = [10, 1, 0, 0]                      # 10.1.0.0/16, v4
    ip[1, :16] = [10, 1, 2, 3] + [7] * 12          # v6 /32 on 10.1.2.3's bytes
    ip[2, :16] = [10, 1, 2, 3] + [7] * 12          # v6 /64
    rules = np.zeros((3, 4, 7), np.int32)
    for e in range(3):
        rules[e, 1] = [1, 0, 0, 0, 0, 0, 1 + e % 2]  # catch-all
    table = _gen.Table(ifindex=np.array([2, 2, 2]), mask_len=np.array([16, 32, 64]),
                       ip=ip, rules=rules)
    words = np.zeros((3, 4), np.uint32)
    words[:, 0] = (10 << 24) | (1 << 16) | (2 << 8) | 3
    pk = _gen.Packets(kind=np.array([1, 1, 2], np.int32), l4_ok=np.ones(3, np.int32),
                      ifindex=np.array([2, 3, 2], np.int32), ip_words=words,
                      proto=np.full(3, 6, np.int32), dst_port=np.full(3, 80, np.int32),
                      icmp_type=np.zeros(3, np.int32), icmp_code=np.zeros(3, np.int32),
                      pkt_len=np.full(3, 100, np.int32))
    res, target = reference.Reference(table).classify(pk)
    assert list(target) == [1, -1, 1]   # v4: the /32 v6 entry; other iface: none
    assert list(res) == [(1 << 8) | 2, 0, (1 << 8) | 2]
