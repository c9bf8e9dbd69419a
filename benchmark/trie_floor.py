"""The bytes a trie lookup has to read, by the plain reference's longest
match: the floor under ``trie_roofline_pct.sat``.

A lookup in a DIR-16 root over 8-bit poptrie levels reads at the least:

- the packet's root slot, 8 bytes (its child and target words);
- one 72-byte node row (child and target bases, two 32-byte bitmaps) per
  level below the root down to the level of its longest match:
  ``ceil((m - 16) / 8)`` levels for a match of length ``m``, none for
  ``m <= 16``;
- when it matches, the matched entry's joined row: its index, its length
  and ``R`` rule slots of five 2-byte words, ``2 * (3 + 5 * R)`` bytes.

What the floor leaves out: the levels a lane walks below its longest
match (a static walk takes every lane of a record to the same depth),
the root table's read per interface, the packet's wire words, the
verdict written back, the rule scan's compute and the flow tier.  It
imports nothing of the program.
"""
from __future__ import annotations

import numpy as np

from benchmark import reference

#: the resident step's stages that hold the walk (the program's named
#: scopes; a program without the three inner ones puts the walk in lpm)
WALK_STAGES = ("lpm", "lpm_root", "lpm_level", "lpm_resolve")
ROOT_SLOT_BYTES = 8
NODE_ROW_BYTES = 72
ROOT_BITS, LEVEL_BITS = 16, 8


def lookup_bytes(mask_len, rule_width: int) -> np.ndarray:
    """(n,) floor bytes of lookups whose longest match has length
    ``mask_len`` (negative: no match) in a table of ``rule_width`` rule
    slots an entry."""
    m = np.asarray(mask_len, np.int64)
    levels = np.where(m > ROOT_BITS, -(-(m - ROOT_BITS) // LEVEL_BITS), 0)
    joined = 2 * (3 + 5 * int(rule_width))
    return ROOT_SLOT_BYTES + NODE_ROW_BYTES * levels + np.where(m >= 0, joined, 0)


def record_bytes(table, pool, record_packets: int, pool_records: int) -> np.ndarray:
    """(pool_records,) floor bytes of each pooled record's lookups."""
    target = reference.Reference(table).lookup(pool)
    m = np.where(target >= 0, table.mask_len[np.maximum(target, 0)], -1)
    per = lookup_bytes(m, table.rules.shape[1])
    return per.reshape(pool_records, record_packets).sum(axis=1)
