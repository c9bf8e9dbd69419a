"""The blocklist-100k cell on the CPU: a rehearsal at 6,000 entries (the
trie path), and the walk's floor bytes on a table built by hand."""
import os
import time

import numpy as np

from benchmark import run, trie_floor
from benchmark.traffic import _gen
from conftest import tiny_cell

TRIE_METRICS = ("trie_ms_per_mpkt.sat", "trie_roofline_pct.sat")


def test_blocklist_cell_traced(tmp_path):
    """The trie path at the configuration's shape: correct, and the walk's
    device metrics read nothing where there is no device plane."""
    cell = tiny_cell("blocklist-100k", "hit-sat", entries=6000,
                     record_packets=256, pool_records=8,
                     per_layer=("loop_busy_pct.sat",) + TRIE_METRICS)
    work = str(tmp_path / "work")
    os.makedirs(work)
    r = run.run_cell(cell, 2**31 + 4242, 1.5, True, work,
                     t_begin=time.monotonic(), warm_timeout=300.0)
    out, info = run.result_line(cell, r, 2**31 + 4242)
    assert out["correct"] is True, info
    assert r.facts["path"] == "trie"
    res = r.facts["resident"]
    assert res["resident_trie_levels"] == 5
    assert res["resident_trie_lane_levels_walked_total"] > \
        res["resident_trie_lane_levels_needed_total"] > 0
    assert set(out["metrics"]) == {"loop_busy_pct.sat"}


def _table(entries):
    """(ip bytes, mask length) entries on ifindex 2, 8 rule slots each."""
    ip = np.zeros((len(entries), 16), np.uint8)
    for i, (addr, _m) in enumerate(entries):
        ip[i, :len(addr)] = addr
    rules = np.zeros((len(entries), 8, 7), np.int32)
    rules[:, 1] = (1, 0, 0, 0, 0, 0, 2)  # ruleId 1: any protocol, allow
    return _gen.Table(ifindex=np.full(len(entries), 2, np.int64),
                      mask_len=np.array([m for _a, m in entries], np.int64),
                      ip=ip, rules=rules)


def _packets(addrs, kinds):
    n = len(addrs)
    ip = np.zeros((n, 16), np.uint8)
    for i, a in enumerate(addrs):
        ip[i, :len(a)] = a
    z = np.zeros(n, np.int32)
    return _gen.Packets(kind=np.asarray(kinds, np.int32), l4_ok=z + 1,
                        ifindex=z + 2, ip_words=_gen._words(ip), proto=z + 6,
                        dst_port=z + 80, icmp_type=z, icmp_code=z,
                        pkt_len=z + 64)


def test_lookup_bytes_by_longest_match():
    joined = 2 * (3 + 5 * 8)
    got = trie_floor.lookup_bytes([-1, 0, 16, 17, 24, 25, 32, 48], 8)
    want = [8, 8 + joined, 8 + joined, 8 + 72 + joined, 8 + 72 + joined,
            8 + 144 + joined, 8 + 144 + joined, 8 + 288 + joined]
    assert got.tolist() == want


def test_record_bytes_on_a_hand_built_table():
    """Nested IPv4 /16 > /20 > /24 and IPv6 /29 > /48: each packet pays the
    root slot, one node row per level down to its longest match, and the
    joined row when it matches."""
    table = _table([((10, 1), 16), ((10, 1, 0x20), 20), ((10, 1, 0x21), 24),
                    ((0x20, 1, 0x0d, 0xb8), 29),
                    ((0x20, 1, 0x0d, 0xb8, 0, 7), 48)])
    pk = _packets([(10, 1, 0x80, 1),           # /16: root only
                   (10, 1, 0x2f, 9),           # /20: 1 level
                   (10, 1, 0x21, 9),           # /24: 1 level
                   (10, 2, 0, 1),              # no match
                   (0x20, 1, 0x0d, 0xb8, 0, 9),  # /29: 2 levels
                   (0x20, 1, 0x0d, 0xb8, 0, 7, 1),  # /48: 4 levels
                   (10, 1, 0x21, 1), (10, 1, 0x21, 2)],
                  [1, 1, 1, 1, 2, 2, 1, 1])
    joined = 2 * (3 + 5 * 8)
    per = [8 + joined, 8 + 72 + joined, 8 + 72 + joined, 8,
           8 + 144 + joined, 8 + 288 + joined, 8 + 72 + joined,
           8 + 72 + joined]
    got = trie_floor.record_bytes(table, pk, record_packets=4, pool_records=2)
    assert got.tolist() == [sum(per[:4]), sum(per[4:])]
