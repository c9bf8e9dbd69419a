"""Pure JAX/XLA classification path.

Implements the XDP hot path (/root/reference/bpf/ingress_node_firewall_kernel.c:
189-457) as batched tensor ops with bit-identical verdict semantics:

- LPM lookup over the (ifindex:32 || srcIP:128) key space, packet-side
  prefix caps (64 for v4, 160 for v6) included;
- the ordered 100-entry rule scan with half-open port ranges, end==0
  single-port encoding, family-gated ICMP matching, protocol==0 catch-all
  and ruleId==0 slot skipping;
- result packing (action | ruleId<<8), final XDP verdict mapping, and
  per-ruleId statistics (stats recorded only for ALLOW/DENY with
  ruleId < MAX_TARGETS, mirroring the per-CPU stats map).

Two LPM strategies, selected by table size:
- dense: compare the packet key against every entry (vector-friendly,
  reference-capacity MAX_TARGETS=1024 scale);
- trie:  the poptrie walk (build_poptrie / trie_walk) — a DIR-16 root
  gather plus one compressed-node-row gather per 8-bit level with
  popcount-rank child indexing, statically unrolled; scales to 100K-1M
  CIDRs at ~140MB device memory per million entries.
"""
from __future__ import annotations

import functools
import hashlib
import os
import re
import threading
import time
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..compiler import CompiledTables, trie_level_strides
from ..contracts import must_precede
from ..constants import (
    ALLOW,
    DENY,
    IPPROTO_ICMP,
    IPPROTO_ICMPV6,
    IPPROTO_SCTP,
    IPPROTO_TCP,
    IPPROTO_UDP,
    KIND_IPV4,
    KIND_IPV6,
    KIND_MALFORMED,
    MAX_TARGETS,
    XDP_DROP,
    XDP_PASS,
)
from ..packets import PacketBatch

# Device-side stats tensor layout: (MAX_TARGETS, 6) int32 columns
# [allow_pkts, allow_bytes_hi, allow_bytes_lo, deny_pkts, deny_bytes_hi,
# deny_bytes_lo] where bytes_hi/lo are sums of (len>>8) and (len&0xFF);
# the host accumulator recombines into int64 packets/bytes.
STATS_COLS = 6


class DeviceTables(NamedTuple):
    """Compiled tables resident on device.

    ``trie_levels`` holds the device LPM walk structure (the poptrie
    transform of the compiler's variable-stride slot trie, see
    build_poptrie): element 0 is the DIR-16 root level as a direct-
    indexed (n_0*65536, 2) int32 slot array (tiny, direct index beats
    any compression); elements 1.. are (n_l, 18) uint32 poptrie node
    rows [child_base, target_base, child_bitmap x8, target_bitmap x8].
    ``trie_targets`` is the per-level target-compact arrays concatenated
    (leading 0 sentinel).  The tuple length is part of the pytree
    structure, so jit specializes per level count — the static level
    bound the walk unrolls over."""

    key_words: jax.Array    # (T, 5) uint32
    mask_words: jax.Array   # (T, 5) uint32
    mask_len: jax.Array     # (T,) int32
    #: (T, R*5) uint16 FLATTENED packed rule rows [rid|act<<8,
    #: proto|icmpType<<8, icmpCode, portStart, portEnd] per rule when
    #: every field fits (syncer tables always) — (T, R*7) int32 otherwise
    #: (adversarial direct content with wide values).  Flattened 2D on
    #: purpose: XLA's row gather from a 2D (T, W) layout measures ~2.4x
    #: faster than the same bytes as (T, R, C) 3D (tools/profile_trie.py
    #: variants B vs G on v5e); classify reshapes to (B, R, C) after the
    #: gather, which fuses into the scan.  The mesh rules-sharded path
    #: keeps its own 3D layout (parallel/mesh.py).
    rules: jax.Array
    trie_levels: Tuple[jax.Array, ...]
    trie_targets: jax.Array  # (1 + total present targets,) int32
    #: Joined target rows (build_joined): row p = [tidx+1 lo, tidx+1 hi,
    #: mask_len, packed rules R*5] uint16 (or [tidx+1, mask_len, rules
    #: R*7] int32 for wide tables), indexed by the SAME positions the
    #: walk's win tracking produces — so the trie path's final gather
    #: returns the rules in ONE fat row (row width is free up to ~512B,
    #: tools/profile_gather.py) instead of a separate trie_targets
    #: resolve + rules gather (two diverse ~8-11ns gathers -> one).
    #: Shape (1, 1) uint16 when inactive (duplication-gated; dense path;
    #: mesh shards) — the static shape selects the walk at trace time.
    joined: jax.Array
    root_lut: jax.Array     # (max_if+1,) int32
    num_entries: jax.Array  # () int32


class DeviceBatch(NamedTuple):
    kind: jax.Array       # (B,) int32
    l4_ok: jax.Array      # (B,) int32
    ifindex: jax.Array    # (B,) int32
    ip_words: jax.Array   # (B, 4) uint32
    proto: jax.Array      # (B,) int32
    dst_port: jax.Array   # (B,) int32
    icmp_type: jax.Array  # (B,) int32
    icmp_code: jax.Array  # (B,) int32
    pkt_len: jax.Array    # (B,) int32


class DeviceTableInvariantError(AssertionError):
    """A device-table mutation violated the bucket/placeholder contract
    (see assert_patched_tables) — raised at the mutation site so a bad
    patch never installs, instead of surfacing later as a parity mystery
    (the PR-4 joined-placeholder bucket-padding bug was exactly this
    class: caught by accident, downstream, via the mesh parity suite)."""


#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_JOINED_PAD_BUG env var), patch_device_tables re-introduces
#: the PR-4 bug — bucket-padding the inactive (1, 1) joined placeholder
#: to (8, 1) on structural patches, which flips classify into a
#: zero-width joined walk.  The state-checker acceptance gate
#: (tools/infw_lint.py state --inject-defect) proves the model checker
#: catches this with a shrunk reproducer; never set it in production.
_INJECT_JOINED_PAD_BUG = False


def _inject_joined_pad_bug() -> bool:
    if _INJECT_JOINED_PAD_BUG:
        return True
    env = os.environ.get("INFW_INJECT_JOINED_PAD_BUG", "")
    return env not in ("", "0", "false", "no")


def _row_bucket(n: int) -> int:
    """Bucketed device row count: small tables round to the next power of
    two, large ones to 4096-row chunks — so a few appended entries keep
    every device array shape (and thus the jit cache AND the incremental
    patch path) stable."""
    if n <= 0:
        return 8
    if n <= 4096:
        return max(8, 1 << (n - 1).bit_length())
    return -(-n // 4096) * 4096


def _pad_rows(a: np.ndarray, n_rows: int, fill=0) -> np.ndarray:
    if a.shape[0] >= n_rows:
        return a
    if fill == 0:
        # np.zeros is calloc — lazily mapped zero pages, no write pass
        # (np.full memsets the whole multi-GB buffer; measured 20+s across
        # a large table's padded layouts)
        out = np.zeros((n_rows,) + a.shape[1:], a.dtype)
    else:
        out = np.empty((n_rows,) + a.shape[1:], a.dtype)
        out[a.shape[0] :] = fill
    out[: a.shape[0]] = a
    return out


def build_poptrie(tables: CompiledTables):
    """Host transform: the compiler's slot-indexed variable-stride trie
    (per level (n_l*slots, 2) int32 — ~1% occupied at scale, 3.4GB at 1M
    entries) -> a poptrie-style compressed representation (Asai &
    Ohara's poptrie, adapted: bitmap + popcount-rank node rows with
    IMPLICIT child numbering) that the device walk gathers from:

    - level 0 (DIR-16 root) stays a direct-indexed slot array — it is
      small (n_0*65536 rows only for live ifindexes) and direct indexing
      beats any compression; its child column is remapped to renumbered
      level-1 ids PLUS ONE (0 = no child, since renumbered ids are
      0-based).
    - level l>=1 nodes renumber to the order their parent slots appear
      (row-major (parent, slot) scan), so a node's children occupy the
      contiguous range [child_base, child_base + popcount(bitmap)) at
      the next level and the walk needs NO child-pointer gather: the
      child id is child_base + rank(nib).
    - per-level target values compact the same way; the walk tracks only
      a winning global index into ``targets`` (leading 0 sentinel, so
      index 0 reads 0 == no target) and gathers ONCE after the walk.

    Returns (levels, targets): levels[0] (n_0*65536, 2) int32,
    levels[1:] (n_l, 18) uint32 rows
    [child_base, target_base, child_bm x8, target_bm x8]; targets int32.

    Memoized on the CompiledTables instance — the transform scans the
    full slot arrays (seconds at the 1M tier) and both the upload and
    the patch diff consume it."""
    cached = getattr(tables, "_poptrie_cache", None)
    if cached is not None:
        return cached
    from ..compiler import record_build_phase

    _t0 = time.perf_counter()
    slot_levels = tables.trie_levels
    strides = trie_level_strides(len(slot_levels))
    out_levels = []
    targets_parts = [np.zeros(1, np.int32)]  # index-0 sentinel
    t_off = 1  # global target index of the current level's first target
    perm = None  # new-id -> old-id for the CURRENT level (None: identity)
    for l, (tbl, stride) in enumerate(zip(slot_levels, strides)):
        slots = 1 << stride
        R = tbl.reshape(tbl.shape[0] // slots, slots, 2)
        if perm is not None:
            # renumbered order; unreachable (orphaned) nodes drop out
            R = R[perm] if len(perm) else R[:0]
        n_nodes = R.shape[0]
        child = R[:, :, 0]
        tgt = R[:, :, 1]
        present = child != 0
        # next level's renumbering: present children in (node, slot) order
        perm = child[present]
        if l == 0:
            # The walk computes e0 = root * 65536 + nib0 in int32; keep
            # the root level small enough that the product cannot wrap
            # (>= 32768 root nodes would need a ~17GB host slot array
            # long before this fires, but wrap would silently turn deny
            # entries into UNDEF/PASS via the OOB mask).
            if n_nodes * 65536 > np.iinfo(np.int32).max:
                raise ValueError(
                    f"poptrie root level has {n_nodes} nodes; int32 "
                    "DIR-16 indexing supports at most 32767"
                )
            # remap child ids to renumbered-level-1 ids + 1 (0 = none)
            if len(slot_levels) > 1:
                n_next = slot_levels[1].shape[0] // (1 << strides[1])
                inv = np.zeros(max(n_next, 1), np.int32)
                inv[perm] = np.arange(1, len(perm) + 1, dtype=np.int32)
                remapped = np.where(present, inv[child], 0)
            else:
                remapped = np.zeros_like(child)
            lvl0 = np.stack([remapped, tgt], axis=2).reshape(-1, 2)
            out_levels.append(np.ascontiguousarray(lvl0, np.int32))
            continue
        tpres = tgt > 0
        # LSB-first bit packing: slot s -> word s>>5, bit s&31
        cb = np.packbits(present, axis=1, bitorder="little")
        cb = np.ascontiguousarray(cb).view("<u4").astype(np.uint32)
        tb = np.packbits(tpres, axis=1, bitorder="little")
        tb = np.ascontiguousarray(tb).view("<u4").astype(np.uint32)
        counts = present.sum(axis=1, dtype=np.int64)
        tcounts = tpres.sum(axis=1, dtype=np.int64)
        cbase = np.zeros(n_nodes, np.int64)
        tbase = np.zeros(n_nodes, np.int64)
        if n_nodes:
            np.cumsum(counts[:-1], out=cbase[1:])
            np.cumsum(tcounts[:-1], out=tbase[1:])
        rows = np.empty((max(n_nodes, 1), 18), np.uint32)
        rows[:] = 0
        if n_nodes:
            rows[:n_nodes, 0] = cbase.astype(np.uint32)
            # target_base carries the GLOBAL concat offset, so the walk
            # derives the final targets index with no per-level offset
            # bookkeeping (padding rows keep 0; their bitmap is 0 so the
            # sentinel slot is never selected)
            rows[:n_nodes, 1] = (tbase + t_off).astype(np.uint32)
            rows[:n_nodes, 2:10] = cb.reshape(n_nodes, -1)[:, :8]
            rows[:n_nodes, 10:18] = tb.reshape(n_nodes, -1)[:, :8]
        lvl_targets = tgt[tpres].astype(np.int32)
        t_off += len(lvl_targets)
        out_levels.append(rows)
        targets_parts.append(lvl_targets)
    result = (out_levels, np.concatenate(targets_parts))
    record_build_phase(tables, "build_poptrie", time.perf_counter() - _t0)
    try:
        object.__setattr__(tables, "_poptrie_cache", result)
    except (AttributeError, TypeError):
        pass
    return result


#: joined-targets duplication gate: a trie whose leaf-pushed slot
#: expansion duplicates targets more than this (positions per entry)
#: falls back to the two-gather walk rather than paying the rule-row
#: duplication in device memory
JOINED_DUP_LIMIT = 2.5


def _packed_rules_flat(tables: CompiledTables):
    """(T, R*5) uint16 flattened packed rules, or (T, R*7) int32 for
    wide tables — memoized (shared with _host_device_layout)."""
    rules = getattr(tables, "_packed_rules_cache", None)
    if rules is None:
        rules = pack_rules_u16(tables.rules)
        if rules is None:
            rules = tables.rules
        rules = np.ascontiguousarray(rules).reshape(rules.shape[0], -1)
        try:
            object.__setattr__(tables, "_packed_rules_cache", rules)
        except (AttributeError, TypeError):
            pass
    return rules


def joined_layout(tables: CompiledTables):
    """UNGATED joined-targets layout: (joined, l0_joined, t_vals).

    - ``joined`` row p (p < len(targets)) corresponds to targets
      position p: [tidx+1 (2 x u16), mask_len, packed rules] — so the
      walk's win position indexes it DIRECTLY; rows for the root level's
      targets are appended once per unique root tidx.
    - ``l0_joined`` is levels[0] with the target column rewritten from
      tidx+1 to the appended joined index.
    - ``t_vals`` maps joined row -> tidx+1 (0 = sentinel/padding).

    build_joined applies the device-memory duplication gate on top; the
    fused Pallas walk (kernels.pallas_walk) consumes this directly — its
    gate is the VMEM budget after deep-tail extraction, not HBM
    duplication.  Memoized per tables instance (both consumers run on
    every full load)."""
    cached = getattr(tables, "_joined_layout_cache", None)
    if cached is not None:
        return cached
    levels, targets = build_poptrie(tables)
    rules_flat = _packed_rules_flat(tables)
    l0 = levels[0]
    rt = l0[:, 1]
    uniq = np.unique(rt[rt > 0])  # root target values (tidx+1)
    t_vals = np.concatenate([targets.astype(np.int64), uniq.astype(np.int64)])
    total = len(t_vals)
    tidx = np.maximum(t_vals - 1, 0)
    ml = np.maximum(tables.mask_len, 0)
    valid = (t_vals > 0)[:, None]
    if rules_flat.dtype == np.uint16:
        joined = np.empty((total, 3 + rules_flat.shape[1]), np.uint16)
        joined[:, 0] = t_vals & 0xFFFF
        joined[:, 1] = (t_vals >> 16) & 0xFFFF
        joined[:, 2] = np.minimum(ml[tidx], 0xFFFF)
        joined[:, 3:] = rules_flat[tidx]
    else:
        joined = np.empty((total, 2 + rules_flat.shape[1]), np.int32)
        joined[:, 0] = t_vals
        joined[:, 1] = ml[tidx]
        joined[:, 2:] = rules_flat[tidx]
    joined *= valid.astype(joined.dtype)  # sentinel/zero rows stay zero
    l0j = l0.copy()
    nz = rt > 0
    l0j[nz, 1] = (
        len(targets) + np.searchsorted(uniq, rt[nz])
    ).astype(np.int32)
    result = (joined, l0j, t_vals)
    try:
        object.__setattr__(tables, "_joined_layout_cache", result)
    except (AttributeError, TypeError):
        pass
    return result


def build_joined(tables: CompiledTables):
    """Joined target rows for the one-gather trie tail (see
    DeviceTables.joined): returns (joined, l0_joined, sorted_t, order)
    or None when the duplication gate trips.

    ``(sorted_t, order)``: positions grouped by tidx+1 (argsort of the
    row->tidx+1 map) so a rules-only edit can find and patch exactly
    the joined rows of the dirty entries (searchsorted, no scan).

    Memoized on the tables instance alongside the poptrie cache."""
    cached = getattr(tables, "_joined_cache", None)
    if cached is not None:
        return None if cached == "none" else cached
    _levels, targets = build_poptrie(tables)
    T = _packed_rules_flat(tables).shape[0]
    rt = _levels[0][:, 1]
    uniq = np.unique(rt[rt > 0])
    total = len(targets) + len(uniq)
    result = None
    if total <= max(4096, JOINED_DUP_LIMIT * (T + 1)):
        joined, l0j, t_vals = joined_layout(tables)
        order = np.argsort(t_vals, kind="stable").astype(np.int64)
        result = (joined, l0j, t_vals[order], order)
    try:
        object.__setattr__(
            tables, "_joined_cache", result if result is not None else "none"
        )
    except (AttributeError, TypeError):
        pass
    return result


# --- path/level-compressed poptrie (the "cpoptrie" layout) ------------------
#
# The per-level poptrie walk pays one HBM gather per 8-bit level — a /128
# table is 14 deep gathers even when most of the trie is single-child
# chains (clean /48+/24 distributions at the 10M tier are ~all chain).
# The compressed layout merges every deep level into ONE global node
# array and collapses single-child no-target chains into SKIP nodes
# ("path compression": each step consumes skip_len <= 24 chain bits plus
# its own 8-bit stride, so the effective per-step stride is 8..32 bits,
# selected by subtree occupancy — the level-compression dual).  Node row
# (20 x u32, 80 B — inside the flat-gather cost window):
#
#   [child_base, target_base, skip_len, skip_bits,
#    child_bitmap x8, target_bitmap x8]
#
# Children keep poptrie's implicit contiguous numbering (BFS order), so
# the child id is child_base + rank(nib) with no pointer gather.  Target
# hits record a position into a flat ``targets`` array of tidx+1 values;
# the rules tail indexes a per-TARGET joined row matrix (row t+1 =
# [tidx+1, mask_len, packed rules] — no leaf-push duplication, so the
# JOINED_DUP_LIMIT gate never applies to this layout).
#
# Only chains with NO targets compress (leaf-pushed targets pin their
# nodes), preserving bit-exact LPM semantics: the walk is verified
# bit-identical to trie_walk/the CPU oracle by tests/test_pallas_walk.py
# and the statecheck equivalence engine (compressed configs).

#: max chain bits absorbed into one skip node: skip + the node's own
#: 8-bit stride stays within a 32-bit extraction window (2 ip words)
CPOP_MAX_SKIP = 24


class CTrieTables(NamedTuple):
    """Compressed-poptrie device operands (see module comment above).

    ``d_max`` is NOT carried here — it is a static walk-unroll bound and
    travels through the jitted-factory cache key instead (NamedTuple
    fields are pytree leaves)."""

    l0: jax.Array        # (n0*65536, 2) int32 [cnode_id+1, tidx+1]
    nodes: jax.Array     # (N, 20) uint32 merged skip-node rows
    targets: jax.Array   # (1 + n_tgt,) int32 tidx+1 values (0 sentinel)
    joined: jax.Array    # (T+1, 3+R*5) uint16 per-tidx joined rows
    root_lut: jax.Array  # (max_if+1,) int32


#: TEST-ONLY defect injection for the skip-node path: zero out every
#: skip_bits word so a packet whose skipped chain bits are nonzero
#: wrongly fails (or passes) the skip compare — the statecheck
#: acceptance gate (tools/infw_lint.py state --inject-defect=cskip)
#: proves the model checker catches a compressed-walk defect via oracle
#: divergence.  Never set in production.
_INJECT_CSKIP_BUG = False


def _inject_cskip_bug() -> bool:
    if _INJECT_CSKIP_BUG:
        return True
    env = os.environ.get("INFW_INJECT_CSKIP_BUG", "")
    return env not in ("", "0", "false", "no")


def _single_child_nib(rows: np.ndarray) -> np.ndarray:
    """Slot index of the single set child-bitmap bit per node (valid
    only where the child count is exactly 1)."""
    cbm = rows[:, 2:10].astype(np.uint32)
    nz = cbm != 0
    w = np.argmax(nz, axis=1)
    wv = cbm[np.arange(len(rows)), w].astype(np.float64)
    # log2 is exact for single-bit values up to 2^31
    bit = np.zeros(len(rows), np.int64)
    pos = wv > 0
    bit[pos] = np.log2(wv[pos]).astype(np.int64)
    return w.astype(np.int64) * 32 + bit


def _pc_rows(words: np.ndarray) -> np.ndarray:
    return _popcount32(words.astype(np.uint32)).sum(axis=1).astype(np.int64)


def _crange_concat(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenate [s, s+c) ranges, vectorized (int64)."""
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    ends = np.cumsum(counts)
    offs = np.repeat(starts - np.concatenate([[0], ends[:-1]]), counts)
    return offs + np.arange(total, dtype=np.int64)


def build_cpoptrie(tables: CompiledTables):
    """Host transform: poptrie levels -> the merged path-compressed node
    array.  Fully vectorized (per-level scans + a d_max-bounded BFS of
    array concatenations — no per-node Python), so it rides the same
    build-time budget as build_poptrie whose output it consumes.

    Returns (l0, nodes, targets, d_max):
      l0      (n0*65536, 2) int32 [cnode_id+1, tidx+1]
      nodes   (max(N,1), 20) uint32 skip-node rows
      targets (1 + n_tgt,) int32 tidx+1 per target position (0 sentinel)
      d_max   int — compressed walk depth (static unroll bound)

    Memoized on the tables instance (keyed with the defect-injection
    flag so the acceptance gate cannot serve a stale clean build)."""
    inject = _inject_cskip_bug()
    cached = getattr(tables, "_cpoptrie_cache", None)
    if cached is not None and cached[0] == inject:
        return cached[1]
    from ..compiler import record_build_phase

    _t0 = time.perf_counter()
    levels, targets = build_poptrie(tables)
    deep = [np.asarray(l, np.uint32) for l in levels[1:]]
    L = len(deep)
    n_l = [d.shape[0] for d in deep]
    cc = [_pc_rows(d[:, 2:10]) if d.size else np.zeros(0, np.int64)
          for d in deep]
    tc = [_pc_rows(d[:, 10:18]) if d.size else np.zeros(0, np.int64)
          for d in deep]
    cb_base = [d[:, 0].astype(np.int64) if d.size else np.zeros(0, np.int64)
               for d in deep]
    tb_base = [d[:, 1].astype(np.int64) if d.size else np.zeros(0, np.int64)
               for d in deep]
    nib1 = [_single_child_nib(d) if d.size else np.zeros(0, np.int64)
            for d in deep]

    # -- top-down: pending skip accumulation + the skip/emit decision ----
    pend_len = [np.zeros(n, np.int64) for n in n_l]
    pend_bits = [np.zeros(n, np.int64) for n in n_l]
    skipped = []
    for l in range(L):
        chain = (cc[l] == 1) & (tc[l] == 0) & (l + 1 < L)
        sk = chain & (pend_len[l] + 8 <= CPOP_MAX_SKIP)
        skipped.append(sk)
        if l + 1 < L and sk.any():
            idx = np.nonzero(sk)[0]
            ch = cb_base[l][idx]  # the single child's id at level l+1
            ok = ch < n_l[l + 1]
            idx, ch = idx[ok], ch[ok]
            pend_len[l + 1][ch] = pend_len[l][idx] + 8
            pend_bits[l + 1][ch] = (pend_bits[l][idx] << 8) | nib1[l][idx]

    # -- bottom-up: resolve every node to the emitted node absorbing it --
    res_lvl = [None] * L
    res_id = [None] * L
    for l in range(L - 1, -1, -1):
        lv = np.full(n_l[l], l, np.int64)
        ids = np.arange(n_l[l], dtype=np.int64)
        if l + 1 < L and n_l[l + 1]:
            ch = np.clip(cb_base[l], 0, n_l[l + 1] - 1)
            lv = np.where(skipped[l], res_lvl[l + 1][ch], lv)
            ids = np.where(skipped[l], res_id[l + 1][ch], ids)
        res_lvl[l], res_id[l] = lv, ids

    # -- BFS numbering: emitted nodes in (parent, slot) order so every
    # node's children stay contiguous (the implicit-numbering contract) --
    l0 = np.asarray(levels[0], np.int32)
    c0 = l0[:, 0].astype(np.int64)
    has0 = c0 > 0
    if L and has0.any() and n_l[0]:
        ch0 = np.clip(c0[has0] - 1, 0, n_l[0] - 1)
        f_lvl = res_lvl[0][ch0]
        f_id = res_id[0][ch0]
    else:
        f_lvl = np.zeros(0, np.int64)
        f_id = np.zeros(0, np.int64)

    rows_out: list = []
    tgt_out: list = []
    total = 0
    t_total = 1  # targets[0] sentinel
    l0_child_new = np.zeros(len(c0), np.int64)
    first_ids = None
    d_max = 0
    while len(f_lvl):
        d_max += 1
        n_f = len(f_lvl)
        gids = total + np.arange(n_f, dtype=np.int64)
        total += n_f
        if first_ids is None:
            first_ids = gids
        # gather per-node data (grouped by source level)
        cc_f = np.empty(n_f, np.int64)
        tc_f = np.empty(n_f, np.int64)
        cb_f = np.empty(n_f, np.int64)
        tb_f = np.empty(n_f, np.int64)
        pl_f = np.empty(n_f, np.int64)
        pb_f = np.empty(n_f, np.int64)
        bm_f = np.zeros((n_f, 16), np.uint32)
        lvl_next = np.empty(n_f, np.int64)
        for l in np.unique(f_lvl):
            m = f_lvl == l
            sel = f_id[m]
            cc_f[m] = cc[l][sel]
            tc_f[m] = tc[l][sel]
            cb_f[m] = cb_base[l][sel]
            tb_f[m] = tb_base[l][sel]
            pl_f[m] = pend_len[l][sel]
            pb_f[m] = pend_bits[l][sel]
            bm_f[m] = deep[l][sel, 2:18]
            lvl_next[m] = l + 1
        # next frontier: resolved children, whole contiguous ranges
        child_old = _crange_concat(cb_f, cc_f)
        child_lvl_src = np.repeat(lvl_next, cc_f)
        nf_lvl = np.empty(len(child_old), np.int64)
        nf_id = np.empty(len(child_old), np.int64)
        for l in np.unique(child_lvl_src):
            m = child_lvl_src == l
            if l >= L or n_l[l] == 0:
                # dead pointers below the last level: resolve to self;
                # their bitmaps are zero so the walk never descends
                nf_lvl[m] = l - 1
                nf_id[m] = 0
                continue
            sel = np.clip(child_old[m], 0, n_l[l] - 1)
            nf_lvl[m] = res_lvl[l][sel]
            nf_id[m] = res_id[l][sel]
        # rows for this step
        excl_c = np.concatenate([[0], np.cumsum(cc_f)[:-1]]) if n_f else []
        excl_t = np.concatenate([[0], np.cumsum(tc_f)[:-1]]) if n_f else []
        rows = np.zeros((n_f, 20), np.uint32)
        rows[:, 0] = (total + excl_c).astype(np.uint32)
        rows[:, 1] = (t_total + excl_t).astype(np.uint32)
        rows[:, 2] = pl_f.astype(np.uint32)
        rows[:, 3] = (
            np.zeros(n_f, np.uint32) if inject else pb_f.astype(np.uint32)
        )
        rows[:, 4:20] = bm_f
        rows_out.append(rows)
        # flat targets in node order (values are global tidx+1)
        tgt_out.append(targets[_crange_concat(tb_f, tc_f)].astype(np.int64))
        t_total += int(tc_f.sum())
        f_lvl, f_id = nf_lvl, nf_id

    nodes = (
        np.concatenate(rows_out) if rows_out else np.zeros((1, 20), np.uint32)
    )
    new_targets = np.concatenate(
        [np.zeros(1, np.int64)] + tgt_out
    ).astype(np.int32)
    l0_new = l0.copy()
    if first_ids is not None:
        l0_child_new[:] = 0
        l0_child_new[np.nonzero(has0)[0]] = first_ids + 1
        l0_new[:, 0] = l0_child_new.astype(np.int32)
    else:
        l0_new[:, 0] = 0
    result = (l0_new, nodes, new_targets, d_max)
    record_build_phase(tables, "build_cpoptrie", time.perf_counter() - _t0)
    try:
        object.__setattr__(tables, "_cpoptrie_cache", (inject, result))
    except (AttributeError, TypeError):
        pass
    return result


def joined_by_tidx(tables: CompiledTables):
    """(T+1, 3 + R*5) uint16 joined rows indexed DIRECTLY by tidx+1
    (row 0 = the UNDEF sentinel): [tidx+1 lo, tidx+1 hi, mask_len,
    packed rules].  One row per dense entry — no leaf-push duplication,
    so the compressed walk's rules tail never trips the joined
    duplication gate and a rules-only edit is a scatter at positions
    dirty_tidx + 1.  Returns None for wide (int32) rule tables.
    Memoized on the tables instance."""
    cached = getattr(tables, "_joined_tidx_cache", None)
    if cached is not None:
        return None if isinstance(cached, str) else cached
    rules_flat = _packed_rules_flat(tables)
    if rules_flat.dtype != np.uint16:
        try:
            object.__setattr__(tables, "_joined_tidx_cache", "none")
        except (AttributeError, TypeError):
            pass
        return None
    T = rules_flat.shape[0]
    rows = np.zeros((T + 1, 3 + rules_flat.shape[1]), np.uint16)
    tvals = np.arange(1, T + 1, dtype=np.int64)
    rows[1:, 0] = (tvals & 0xFFFF).astype(np.uint16)
    rows[1:, 1] = (tvals >> 16).astype(np.uint16)
    rows[1:, 2] = np.minimum(
        np.maximum(tables.mask_len, 0), 0xFFFF
    ).astype(np.uint16)
    rows[1:, 3:] = rules_flat
    try:
        object.__setattr__(tables, "_joined_tidx_cache", rows)
    except (AttributeError, TypeError):
        pass
    return rows


def _joined_tidx_patch_rows(
    tables: CompiledTables, dirty: np.ndarray, rules_flat=None
):
    """(pos, rows) scatter payload for the per-tidx joined matrix at
    the dirty dense rows — the ONE place the patch-side joined row
    format [tidx+1 lo, tidx+1 hi, mask_len, packed rules] is spelled
    out (joined_by_tidx builds the full matrix with the same layout;
    patch_ctrie, pallas_walk.patch_cwalk_joined and the host-cache
    seeding all scatter through here).  Returns None for wide rule
    tables."""
    if rules_flat is None:
        rules_flat = _packed_rules_flat(tables)
    if rules_flat.dtype != np.uint16:
        return None
    dirty = dirty[(dirty >= 0) & (dirty < rules_flat.shape[0])]
    pos = dirty + 1
    rows = np.zeros((len(pos), 3 + rules_flat.shape[1]), np.uint16)
    rows[:, 0] = (pos & 0xFFFF).astype(np.uint16)
    rows[:, 1] = (pos >> 16).astype(np.uint16)
    rows[:, 2] = np.minimum(
        np.maximum(np.asarray(tables.mask_len)[dirty], 0), 0xFFFF
    ).astype(np.uint16)
    rows[:, 3:] = rules_flat[dirty]
    return pos, rows


def _seed_ctrie_caches_forward(
    old: CompiledTables, new: CompiledTables, dirty: np.ndarray
) -> None:
    """Carry the compressed-layout host caches from ``old`` to ``new``
    across a RULES-ONLY edit (caller guarantees the trie is untouched):
    the packed-rules cache is patched at the dirty rows, the
    structural transforms (_poptrie_cache/_cpoptrie_cache/
    _depth_lut_cache — they read trie levels and targets, never rules)
    are shared by reference, and the per-tidx joined cache is patched
    in place.  Without this every 1-key ctrie edit repacks the full
    rules tensor and rebuilds the joined matrix — seconds of host work
    at the 10M tier for a kilobyte-sized device scatter.  Best-effort:
    any mismatch leaves a cache unset and the slow rebuild runs."""
    if old.rules.shape != new.rules.shape:
        return
    try:
        old_packed = getattr(old, "_packed_rules_cache", None)
        if old_packed is not None and getattr(
            new, "_packed_rules_cache", None
        ) is None:
            if len(dirty) == 0:
                # nothing changed: the arrays are immutable once handed
                # out — share by reference
                new_packed = old_packed
            elif old_packed.dtype == np.uint16:
                sub = pack_rules_u16(new.rules[dirty])
                if sub is None:
                    return  # edit introduced wide values: full path
                new_packed = old_packed.copy()
                new_packed[dirty] = sub.reshape(len(dirty), -1)
            else:
                new_packed = old_packed.copy()
                new_packed[dirty] = new.rules[dirty].reshape(len(dirty), -1)
            object.__setattr__(new, "_packed_rules_cache", new_packed)
        for name in ("_poptrie_cache", "_cpoptrie_cache",
                     "_depth_lut_cache", "_depth_classes_cache"):
            c = getattr(old, name, None)
            if c is not None and getattr(new, name, None) is None:
                object.__setattr__(new, name, c)
        jt = getattr(old, "_joined_tidx_cache", None)
        if jt is not None and getattr(
            new, "_joined_tidx_cache", None
        ) is None:
            if isinstance(jt, str) or len(dirty) == 0:
                object.__setattr__(new, "_joined_tidx_cache", jt)
            else:
                pr = _joined_tidx_patch_rows(new, dirty)
                if pr is not None:
                    pos, rows = pr
                    if len(pos) and rows.shape[1] == jt.shape[1] and (
                        int(pos.max()) < jt.shape[0]
                    ):
                        jn = jt.copy()
                        jn[pos] = rows
                        object.__setattr__(new, "_joined_tidx_cache", jn)
    except (AttributeError, TypeError, ValueError, IndexError):
        return


def hint_trie_unchanged(hint) -> bool:
    """True when the dirty hint proves the edit was rules-only (no trie
    level rows touched) — the condition for cache seeding, the joined
    fast path, and for a no-hint patch retry to behave differently from
    the hinted attempt."""
    return hint is not None and all(
        len(h) == 0 for h in hint.get("levels", [np.zeros(1)])
    )


def seed_ctrie_caches_forward(
    old: CompiledTables, new: CompiledTables, hint
) -> None:
    """Backend-facing wrapper: seed the compressed-layout host caches
    when the dirty hint proves the trie untouched.  Must run BEFORE
    any eligibility probe touches ``new`` — joined_by_tidx and
    check_wire_ruleids memoize on first touch, so seeding after the
    fact is too late."""
    if not hint_trie_unchanged(hint):
        return
    dirty = np.unique(np.asarray(hint.get("dense", ()), np.int64))
    dirty = dirty[(dirty >= 0) & (dirty < new.rules.shape[0])]
    _seed_ctrie_caches_forward(old, new, dirty)


def device_ctrie(
    tables: CompiledTables, device=None, pad: bool = False
) -> Optional[Tuple[CTrieTables, int]]:
    """Upload the compressed-poptrie layout; returns (CTrieTables,
    d_max) or None when the layout cannot serve this table (wide int32
    rules).  ``pad=True`` buckets the node/target/joined row counts so
    small structural edits can diff-scatter (patch_ctrie) instead of
    re-uploading; padding rows are all-zero and unreachable (bitmaps 0,
    tidx+1 bounds)."""
    joined = joined_by_tidx(tables)
    if joined is None:
        return None
    l0, nodes, targets, d_max = build_cpoptrie(tables)
    root_lut = np.asarray(tables.root_lut, np.int32)
    if pad:
        nodes = _pad_rows(nodes, _row_bucket(nodes.shape[0]))
        targets = _pad_rows(targets, _row_bucket(targets.shape[0]))
        joined = _pad_rows(joined, _row_bucket(joined.shape[0]))
        root_lut = _pad_rows(root_lut, _row_bucket(root_lut.shape[0]))
    put = lambda a: jax.device_put(jnp.asarray(a), device)
    return CTrieTables(
        l0=put(l0),
        nodes=put(nodes),
        targets=put(targets),
        joined=put(joined),
        root_lut=put(root_lut),
    ), d_max


def _ctrie_host_layout(tables: CompiledTables):
    """Unpadded host arrays in device_ctrie order (the patch diff
    source), or None for wide tables."""
    joined = joined_by_tidx(tables)
    if joined is None:
        return None
    l0, nodes, targets, d_max = build_cpoptrie(tables)
    return (l0, nodes, targets, joined,
            np.asarray(tables.root_lut, np.int32)), d_max


def patch_ctrie(
    cdev: CTrieTables,
    old: CompiledTables,
    new: CompiledTables,
    device=None,
    hint=None,
) -> Optional[Tuple[CTrieTables, int]]:
    """Incremental device update of the compressed layout.

    Rules-only edits (dirty hint proves the trie untouched) scatter
    exactly the dirty tidx rows of the per-target joined matrix —
    kilobytes, positions are dirty_tidx + 1 by construction.  Structural
    edits diff the old/new host cpoptrie arrays row-wise (same
    _patch_array machinery as the poptrie path).  Returns
    (patched, rows_changed) or None when the layout shifted beyond the
    row buckets (caller re-uploads)."""
    if hint_trie_unchanged(hint):
        dirty = np.unique(np.asarray(hint.get("dense", ()), np.int64))
        dirty = dirty[(dirty >= 0) & (dirty < new.rules.shape[0])]
        # seed the host caches FIRST so the payload below patches the
        # carried packed-rules cache instead of repacking the full
        # tensor (the level walk's _seed_caches_forward contract)
        _seed_ctrie_caches_forward(old, new, dirty)
        pr = _joined_tidx_patch_rows(new, dirty)
        if pr is None:
            return None
        pos, rows = pr
        if len(pos) == 0:
            return cdev, 0
        if int(pos.max()) >= cdev.joined.shape[0]:
            return None
        if rows.shape[1] != cdev.joined.shape[1]:
            return None
        joined = _capped_scatter(cdev.joined, pos, rows, device)
        if joined is None:
            return None
        return cdev._replace(joined=joined), len(pos)
    o = _ctrie_host_layout(old)
    nw = _ctrie_host_layout(new)
    if o is None or nw is None:
        return None
    (o_arrs, _od), (n_arrs, _nd) = o, nw
    if _od != _nd:
        return None  # static unroll depth changed: re-jit + re-upload
    # Transaction discipline, ctrie structural half: compute every
    # array's host diff first, then stage all payloads' H2D copies in
    # one pass, then launch the warmed scatters — unlike the level-walk
    # path there is no per-array re-upload fallback (the merged layout's
    # arrays are interdependent), so any oversized/bucket-shifted delta
    # fails the whole patch and the caller re-uploads.
    payloads = []
    for dl, ol, nl in zip(cdev, o_arrs, n_arrs):
        if dl.shape[0] % 65536 == 0 and ol.shape[1:] == (2,):
            # l0 is not bucket-shaped; diff it with an exact-shape check
            if ol.shape != nl.shape or dl.shape != ol.shape:
                return None
            changed = np.nonzero((ol != nl).any(axis=1))[0]
            if len(changed) > max(dl.shape[0] // 4, 1):
                return None
            payloads.append((changed, nl[changed]))
            continue
        pay = _patch_diff_payload(dl, ol, nl)
        if pay is None:
            return None
        payloads.append(pay)
    staged = []
    total = 0
    for dl, (pos, vals) in zip(cdev, payloads):
        if len(pos) == 0:
            staged.append(lambda dl=dl: dl)
            continue
        th = _stage_capped(dl, pos, vals, device)
        if th is None:
            return None
        staged.append(th)
        total += len(pos)
    return CTrieTables(*(th() for th in staged)), total


def extract_ip_bits(ip_words: jax.Array, pos: jax.Array, n: jax.Array):
    """(B,) values of the ``n`` bits at absolute bit offset ``pos``
    (both dynamic per lane, n <= 32, window spans <= 2 words) of the
    128-bit address (4 big-endian u32 words, bit 0 = MSB of word 0).
    Pure u32 VPU math — the word pick is selects, not a gather (a
    take_along_axis here lowers to a per-lane gather per step, measured
    ~10x slower in the cpoptrie prototype)."""
    w = jnp.clip(pos >> 5, 0, 4).astype(jnp.int32)
    zeros = jnp.zeros_like(ip_words[:, 0])

    def pick(widx):
        out = zeros
        for k in range(4):
            out = jnp.where(widx == k, ip_words[:, k], out)
        return out

    lo = pick(w).astype(jnp.uint32)
    hi = pick(w + 1).astype(jnp.uint32)
    off = (pos & 31).astype(jnp.uint32)
    n = n.astype(jnp.uint32)
    hi_part = jnp.where(off == 0, jnp.uint32(0), hi >> (jnp.uint32(32) - off))
    top32 = (lo << off) | hi_part
    return jnp.where(n == 0, jnp.uint32(0), top32 >> (jnp.uint32(32) - n))


def _ctrie_descend(
    nodes: jax.Array, batch: DeviceBatch, node: jax.Array,
    alive: jax.Array, d_max: int,
) -> jax.Array:
    """The shared skip-node descent body: ``d_max`` steps over ONE
    merged node array from a caller-resolved entry (node id + alive
    mask) — each step checks the node's skip chain, consumes its 8-bit
    stride, and rank-indexes the contiguous children.  Returns the
    winning flat target position per lane (0 = sentinel / no hit).

    The single-table walk (ctrie_walk_rows) and the multi-tenant paged
    arena walk (arena_ctrie_rows) run EXACTLY this loop: arena slabs
    bake page-global node/target ids at slab-write time, so paging is
    entirely an entry-steering concern and the descent stays one code
    path."""
    pos = jnp.full_like(node, 16)
    cap_bits = jnp.where(batch.kind == KIND_IPV4, 32, 128)
    widx8 = jnp.arange(8, dtype=jnp.int32)[None, :]
    win = jnp.zeros_like(node)  # flat target position (0 = sentinel)

    for _step in range(d_max):
        with jax.named_scope("lpm_level"):
            in_n = (node >= 0) & (node < nodes.shape[0])
            alive = alive & in_n
            r = jnp.take(nodes, node, axis=0, mode="clip")
            skip_len = r[:, 2].astype(jnp.int32)
            skip_ok = jnp.where(
                skip_len > 0,
                extract_ip_bits(batch.ip_words, pos, skip_len) == r[:, 3],
                True,
            )
            alive = alive & skip_ok
            pos = pos + skip_len
            nib = extract_ip_bits(
                batch.ip_words, pos, jnp.full_like(pos, 8)
            ).astype(jnp.int32)
            pos = pos + 8
            w = (nib >> 5)[:, None]
            below = (np.uint32(1) << (nib & 31).astype(jnp.uint32)) - 1
            cb = r[:, 4:12]
            tb = r[:, 12:20]
            pc_cb = _popcount32(cb)
            pc_tb = _popcount32(tb)
            prefix = jnp.sum(jnp.where(widx8 < w, pc_cb, 0), axis=1)
            tprefix = jnp.sum(jnp.where(widx8 < w, pc_tb, 0), axis=1)
            cw = jnp.sum(jnp.where(widx8 == w, cb, 0), axis=1)
            tw = jnp.sum(jnp.where(widx8 == w, tb, 0), axis=1)
            bit = (nib & 31).astype(jnp.uint32)
            ok_t = alive & (((tw >> bit) & 1) > 0) & (pos <= cap_bits)
            win = jnp.where(
                ok_t,
                (r[:, 1] + tprefix + _popcount32(tw & below)).astype(jnp.int32),
                win,
            )
            alive = alive & (((cw >> bit) & 1) > 0)
            node = jnp.where(
                alive,
                (r[:, 0] + prefix + _popcount32(cw & below)).astype(jnp.int32),
                0,
            )
    return win


def ctrie_walk_rows(
    cdev: CTrieTables, batch: DeviceBatch, d_max: int
) -> jax.Array:
    """The compressed walk: DIR-16 root gather, then the shared
    skip-node descent (_ctrie_descend) over the ONE merged node array.
    Returns the (B, 3 + R*5) per-tidx joined rows (row 0 / dead lanes
    all-zero -> UNDEF), bit-identical in verdict semantics to
    trie_walk_joined."""
    l0, nodes, targets, joined, root_lut = cdev
    with jax.named_scope("lpm_root"):
        lut_size = root_lut.shape[0]
        if_ok = (batch.ifindex >= 0) & (batch.ifindex < lut_size)
        root = jnp.where(
            if_ok, jnp.take(root_lut, jnp.clip(batch.ifindex, 0, lut_size - 1)),
            0,
        )
        nib0 = (batch.ip_words[:, 0] >> np.uint32(16)).astype(jnp.int32)
        e0 = root * 65536 + nib0
        in0 = (e0 >= 0) & (e0 < l0.shape[0])
        rows0 = jnp.take(l0, e0, axis=0, mode="clip")
        best0 = jnp.where(in0 & (rows0[:, 1] > 0), rows0[:, 1], 0)  # tidx+1
        alive = in0 & (rows0[:, 0] > 0)
        node = jnp.where(alive, rows0[:, 0] - 1, 0)
    win = _ctrie_descend(nodes, batch, node, alive, d_max)

    with jax.named_scope("lpm_resolve"):
        in_w = (win >= 0) & (win < targets.shape[0])
        tval = jnp.where(in_w, jnp.take(targets, jnp.clip(win, 0), mode="clip"),
                         0)
        sel = jnp.where(tval > 0, tval, best0)  # tidx+1 (0 = no match)
        in_j = (sel > 0) & (sel < joined.shape[0])
        rows = jnp.take(
            joined, jnp.clip(sel, 0, joined.shape[0] - 1), axis=0, mode="clip"
        )
        return jnp.where(in_j[:, None], rows, 0)


def _ctrie_result_and_score(cdev: CTrieTables, batch: DeviceBatch, d_max: int):
    with jax.named_scope("lpm"):
        rows = ctrie_walk_rows(cdev, batch, d_max)
        matched = (
            rows[:, 0].astype(jnp.int32) | (rows[:, 1].astype(jnp.int32) << 16)
        ) > 0
        score = jnp.where(matched, rows[:, 2].astype(jnp.int32) + 1, 0)
    with jax.named_scope("rule_scan"):
        return rule_scan(joined_rule_rows(rows), batch), score


def classify_ctrie(
    cdev: CTrieTables, batch: DeviceBatch, *, d_max: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full forward pass via the compressed walk; verdict-identical to
    classify(use_trie=True) on the same tables."""
    raw, _score = _ctrie_result_and_score(cdev, batch, d_max)
    with jax.named_scope("rule_scan"):
        return finalize(raw, batch)


def classify_ctrie_with_overlay(
    cdev: CTrieTables,
    overlay: DeviceTables,
    batch: DeviceBatch,
    *,
    d_max: int,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compressed-walk classify combined with the dense overlay
    side-table (same longest-prefix combine as classify_with_overlay)."""
    raw_m, score_m = _ctrie_result_and_score(cdev, batch, d_max)
    raw_o, score_o = _raw_result_and_score(overlay, batch, use_trie=False)
    with jax.named_scope("rule_scan"):
        result = jnp.where(score_o > score_m, raw_o, raw_m)
        return finalize(result, batch)


@functools.lru_cache(maxsize=None)
def jitted_classify_ctrie(d_max: int):
    return jax.jit(functools.partial(classify_ctrie, d_max=d_max))


def classify_ctrie_wire(
    cdev: CTrieTables, wire: jax.Array, *, d_max: int
) -> Tuple[jax.Array, jax.Array]:
    res, _xdp, stats = classify_ctrie(cdev, unpack_wire(wire), d_max=d_max)
    return res.astype(jnp.uint16), stats


@functools.lru_cache(maxsize=None)
def jitted_classify_ctrie_wire_fused(d_max: int):
    def f(cdev: CTrieTables, wire: jax.Array) -> jax.Array:
        return fuse_wire_outputs(*classify_ctrie_wire(cdev, wire, d_max=d_max))

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def jitted_classify_ctrie_wire_overlay_fused(d_max: int):
    def f(cdev: CTrieTables, overlay: DeviceTables, wire: jax.Array):
        res, _xdp, stats = classify_ctrie_with_overlay(
            cdev, overlay, unpack_wire(wire), d_max=d_max
        )
        return fuse_wire_outputs(res.astype(jnp.uint16), stats)

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def jitted_classify_ctrie_wire8_fused(d_max: int, overlay: bool):
    """wire8 (8 B/packet) launch over the compressed layout: same
    res16-only packed D2H contract as jitted_classify_wire8_fused.  The
    compressed walk needs no v4 depth truncation — the per-lane cap_bits
    gate bounds v4 descent inside the one merged node array."""
    if overlay:
        def f(cdev, ov, wire, ifmap):
            res, _x, _s = classify_ctrie_with_overlay(
                cdev, ov, unpack_wire8(wire, ifmap), d_max=d_max
            )
            return _pack_res16(res.astype(jnp.uint16))
    else:
        def f(cdev, wire, ifmap):
            res, _x, _s = classify_ctrie(
                cdev, unpack_wire8(wire, ifmap), d_max=d_max
            )
            return _pack_res16(res.astype(jnp.uint16))

    return jax.jit(f)


def warm_ctrie_patch_scatters(cdev: CTrieTables, device=None) -> None:
    """Pre-compile the compressed layout's patch scatters (the
    warm_patch_scatters analogue): nodes/targets/joined/root_lut are the
    bucket-padded patchable arrays; l0 patches through its own
    exact-shape diff, which shares the same capped executables.  The
    dirty-row ladder (scatter_cap_ladder) keeps multi-edit transaction
    flushes compile-free up to TXN_WARM_MAX_ROWS dirty rows."""
    warm_scatters(
        (cdev.nodes, cdev.targets, cdev.joined, cdev.root_lut, cdev.l0),
        device, max_rows=TXN_WARM_MAX_ROWS,
    )


def _seed_caches_forward(
    old: CompiledTables, new: CompiledTables, dirty_tidx
) -> None:
    """Carry the poptrie/packed/joined host caches from ``old`` to
    ``new`` across a RULES-ONLY edit (caller guarantees the trie is
    untouched), patching only the dirty rows.  Best-effort: any shape or
    mode mismatch silently leaves the caches unset and the slow rebuild
    paths take over.  Returns the (positions, rows) joined scatter
    payload when one was computed, so the caller's device patch does not
    recompute it."""
    if dirty_tidx is None:
        return None
    pop = getattr(old, "_poptrie_cache", None)
    old_packed = getattr(old, "_packed_rules_cache", None)
    if pop is None or old_packed is None:
        return None
    if old.rules.shape != new.rules.shape:
        return None
    pr = None
    try:
        dirty = np.unique(np.asarray(dirty_tidx, np.int64))
        dirty = dirty[(dirty >= 0) & (dirty < new.rules.shape[0])]
        if len(dirty) == 0:
            # nothing changed (overlay-only sync): share every cache by
            # reference — the arrays are immutable once handed out
            new_packed = old_packed
        elif old_packed.dtype == np.uint16:
            sub = pack_rules_u16(new.rules[dirty])
            if sub is None:
                return None  # edit introduced wide values: full path
            new_packed = old_packed.copy()
            new_packed[dirty] = sub.reshape(len(dirty), -1)
        else:
            new_packed = old_packed.copy()
            new_packed[dirty] = new.rules[dirty].reshape(len(dirty), -1)
        object.__setattr__(new, "_packed_rules_cache", new_packed)
        # trie untouched: the poptrie transform is identical — share it
        object.__setattr__(new, "_poptrie_cache", pop)
        # ...and so are the depth-steering caches (they read trie
        # levels, never rules): without this every rules-only flush in
        # an update storm re-derived the LUT + class thresholds per
        # generation — O(root slots) host work per transaction
        for name in ("_depth_lut_cache", "_depth_classes_cache"):
            c = getattr(old, name, None)
            if c is not None:
                object.__setattr__(new, name, c)
        built = getattr(old, "_joined_cache", None)
        if built is not None and built != "none":
            joined_old, l0j, sorted_t, order = built
            pr = joined_patch_rows(old, new, dirty)
            if pr is not None:
                pos, rows = pr
                if len(pos):
                    joined_new = joined_old.copy()
                    joined_new[pos] = rows
                else:
                    joined_new = joined_old
                object.__setattr__(
                    new, "_joined_cache", (joined_new, l0j, sorted_t, order)
                )
        elif built == "none":
            object.__setattr__(new, "_joined_cache", "none")
    except (AttributeError, TypeError, ValueError, IndexError):
        return None
    return pr


def joined_patch_rows(
    old: CompiledTables, new: CompiledTables, dirty_tidx: np.ndarray
):
    """(positions, rows) scatter payload updating the joined array for a
    RULES-ONLY edit: positions come from the OLD generation's cached
    position map (the trie — and therefore the position layout — is
    unchanged, which is exactly what the caller's dirty hint proves),
    row contents from the NEW tables' packed rules.  Never triggers a
    poptrie/joined rebuild of the new snapshot.  Returns None when the
    packed-rule layout changed (caller falls back to full upload)."""
    built = build_joined(old)
    if built is None:
        return None
    joined_old, _l0j, sorted_t, order = built
    new_flat = _packed_rules_flat(new)
    if new_flat.dtype != _packed_rules_flat(old).dtype or (
        new_flat.shape[1] != _packed_rules_flat(old).shape[1]
    ):
        return None
    vals = np.unique(np.asarray(dirty_tidx, np.int64)) + 1
    vals = vals[vals > 0]
    lo = np.searchsorted(sorted_t, vals, side="left")
    hi = np.searchsorted(sorted_t, vals, side="right")
    parts = [order[a:b] for a, b in zip(lo, hi)]
    pos = np.concatenate(parts) if parts else np.zeros(0, np.int64)
    if len(pos) == 0:
        return pos, joined_old[:0]
    t = np.repeat(vals, hi - lo)
    tidx = np.minimum(t - 1, new_flat.shape[0] - 1)
    ml = np.maximum(new.mask_len, 0)
    if new_flat.dtype == np.uint16:
        rows = np.empty((len(pos), 3 + new_flat.shape[1]), np.uint16)
        rows[:, 0] = t & 0xFFFF
        rows[:, 1] = (t >> 16) & 0xFFFF
        rows[:, 2] = np.minimum(ml[tidx], 0xFFFF)
        rows[:, 3:] = new_flat[tidx]
    else:
        rows = np.empty((len(pos), 2 + new_flat.shape[1]), np.int32)
        rows[:, 0] = t
        rows[:, 1] = ml[tidx]
        rows[:, 2:] = new_flat[tidx]
    return pos, rows


def _host_device_layout(tables: CompiledTables, pad: bool, with_trie: bool = True):
    """Host-side arrays in the exact layout device_tables uploads:
    mask_len sentinel applied, trie levels in the poptrie device form,
    rows bucket-padded when ``pad``.  Shared by device_tables and
    patch_device_tables so a patched device state is bit-identical to a
    fresh upload.  ``with_trie=False`` skips the (seconds-at-scale)
    poptrie transform and returns empty levels/targets — for patch calls
    whose dirty hint proves the trie is untouched."""
    mask_len = tables.mask_len.copy()
    mask_len[tables.num_entries :] = -1
    # copy=False: the compiler already stores these as uint32; a blind
    # astype would copy the full arrays on every patch diff
    key_words = tables.key_words.astype(np.uint32, copy=False)
    mask_words = tables.mask_words.astype(np.uint32, copy=False)
    # memoized per tables instance (same pattern as _poptrie_cache): the
    # patch path calls this for BOTH generations on every edit, and a
    # full repack is O(table) host work the hint fast path must not pay
    rules = getattr(tables, "_packed_rules_cache", None)
    if rules is None:
        rules = pack_rules_u16(tables.rules)
        if rules is None:
            rules = tables.rules  # wide values: int32 layout
        # flattened 2D device layout (see DeviceTables.rules)
        rules = np.ascontiguousarray(rules).reshape(rules.shape[0], -1)
        try:
            object.__setattr__(tables, "_packed_rules_cache", rules)
        except (AttributeError, TypeError):
            pass
    joined = np.zeros((1, 1), np.uint16)  # inactive placeholder
    if with_trie:
        trie_levels, trie_targets = build_poptrie(tables)
        built = build_joined(tables)
        if built is not None:
            joined, l0j, _st, _o = built
            trie_levels = [l0j] + list(trie_levels[1:])
    else:
        trie_levels, trie_targets = [], np.zeros(1, np.int32)
    root_lut = tables.root_lut
    if pad:
        n = _row_bucket(mask_len.shape[0])
        key_words = _pad_rows(key_words, n)
        mask_words = _pad_rows(mask_words, n)
        mask_len = _pad_rows(mask_len, n, fill=-1)  # padding rows are inert
        rules = _pad_rows(rules, n)
        # level padding rows are unreachable (child ranks only reach
        # allocated nodes) and zero = empty bitmaps anyway
        trie_levels = [_pad_rows(l, _row_bucket(l.shape[0])) for l in trie_levels]
        trie_targets = _pad_rows(trie_targets, _row_bucket(trie_targets.shape[0]))
        root_lut = _pad_rows(root_lut, _row_bucket(root_lut.shape[0]))
        if joined.shape[0] > 1:
            joined = _pad_rows(joined, _row_bucket(joined.shape[0]))
    return (key_words, mask_words, mask_len, rules, trie_levels,
            trie_targets, root_lut, joined)


@functools.lru_cache(maxsize=None)
def _sparse_expand_jit(n_rows: int, n_cols: int, dtype: str):
    """zeros scattered from (idx, vals) — the device side of the sparse
    trie-level transfer.  One jit per level shape; retraces per nnz shape
    are cheap and the persistent compile cache carries them across
    processes."""
    def f(idx, vals):
        return jnp.zeros((n_rows, n_cols), dtype).at[idx].set(vals)

    return jax.jit(f)


def pack_rules_u16(rules: np.ndarray):
    """(T, R, 7) int32 -> (T, R, 5) uint16 packed rule rows, or None when
    any field exceeds its packed width (ruleId/proto/icmp/action 8 bits,
    ports 16).  The scan gathers one row per packet, so the row is the
    HBM cost that matters: 10B/rule vs 28B."""
    if rules.size == 0:
        return np.zeros(rules.shape[:2] + (5,), np.uint16)
    mx = rules.max(axis=(0, 1))
    mn = int(rules.min())
    if mn < 0 or (mx[[0, 1, 4, 5, 6]] > 0xFF).any() or (mx[[2, 3]] > 0xFFFF).any():
        return None
    out = np.empty(rules.shape[:2] + (5,), np.uint16)
    out[..., 0] = rules[..., 0] | (rules[..., 6] << 8)   # rid | act
    out[..., 1] = rules[..., 1] | (rules[..., 4] << 8)   # proto | icmpType
    out[..., 2] = rules[..., 5]                          # icmpCode
    out[..., 3] = rules[..., 2]                          # portStart
    out[..., 4] = rules[..., 3]                          # portEnd
    return out


@functools.lru_cache(maxsize=None)
def _mask_words_dev_jit():
    """Reconstruct (T, 5) uint32 mask_words from mask_len on device —
    mask words are pure prefix masks (compiler.py:789-792: ifindex word
    fully masked on live rows, IP words from _mask_words_vec; dead and
    padding rows are all-zero with the mask_len == -1 sentinel), so
    shipping the 4-byte mask_len column reconstructs the 20-byte mask row
    exactly."""
    def f(mask_len):
        valid = mask_len >= 0
        w = jnp.arange(4, dtype=jnp.int32)[None, :]
        bits = jnp.clip(mask_len[:, None] - 32 * w, 0, 32).astype(jnp.uint32)
        full = jnp.uint32(0xFFFFFFFF)
        ip = jnp.where(
            bits > 0, (full << (jnp.uint32(32) - bits)) & full, 0
        ).astype(jnp.uint32)
        if0 = jnp.where(valid, full, 0).astype(jnp.uint32)[:, None]
        return jnp.concatenate([if0, jnp.where(valid[:, None], ip, 0)], axis=1)

    return jax.jit(f)


#: ship a trie level sparse when its nonzero-row fraction is below this
#: (sparse costs 12B/row shipped vs 8B/row dense, so the byte win starts
#: at 2/3 — 0.5 keeps slack for the extra dispatch)
_SPARSE_DENSITY_LIMIT = 0.5


def device_tables(
    tables: CompiledTables, device=None, pad: bool = False
) -> DeviceTables:
    """Upload to device.  ``pad=True`` buckets row counts (see
    _row_bucket) — used by the long-lived classifier so incremental table
    edits keep array shapes, enabling patch_device_tables and avoiding
    per-size jit recompiles.  Padding rows carry the mask_len == -1
    sentinel so the dense match excludes them without a separate entry
    count (and every array stays shardable along the target axis).

    The TRANSFER layout is compacted — the restart-to-enforcement path
    (the analogue of pinned-map re-adoption, loader.go:381-407) is
    link-bandwidth bound at the 1M-entry tier (3.5GB of trie levels),
    so:
      - trie levels ship sparse (index + nonzero rows; levels measure
        ~1% dense at scale) and expand via on-device scatter;
      - mask_words never ship (reconstructed on device from mask_len);
      - rules ship as uint16 when their values fit (ports are the widest
        field) and upcast on device.
    The resident DeviceTables is bit-identical to a direct upload — the
    patch path diffs against it with no knowledge of how it traveled."""
    from ..compiler import record_build_phase

    _t0 = time.perf_counter()
    (key_words, mask_words, mask_len, rules, trie_levels, trie_targets,
     root_lut, joined) = _host_device_layout(tables, pad)
    record_build_phase(tables, "upload/host-layout", time.perf_counter() - _t0)
    _t0 = time.perf_counter()
    put = lambda a: jax.device_put(jnp.asarray(a), device)

    # -- trie levels: sparse scatter below the density limit (the DIR-16
    # root level is ~0-60% dense; poptrie node rows are mostly dense by
    # construction, so they usually ship whole — and are ~30x smaller
    # than the slot arrays they replaced) --------------------------------
    levels_dev = []
    for tbl in trie_levels:
        n = tbl.shape[0]
        if n == 0:
            levels_dev.append(put(tbl))
            continue
        flat = np.ascontiguousarray(tbl).reshape(n, -1)
        nnz = np.nonzero(flat.any(axis=1))[0]
        # sparse ships (4 + rowbytes) per nnz row vs rowbytes per row
        row_b = flat.shape[1] * tbl.dtype.itemsize
        if len(nnz) * (4 + row_b) <= _SPARSE_DENSITY_LIMIT * n * row_b:
            levels_dev.append(
                _sparse_expand_jit(n, tbl.shape[1], str(tbl.dtype))(
                    put(nnz.astype(np.int32)), put(tbl[nnz])
                )
            )
        else:
            levels_dev.append(put(tbl))

    result = DeviceTables(
        key_words=put(key_words),
        mask_words=_mask_words_dev_jit()(put(mask_len)),
        mask_len=put(mask_len),
        rules=put(rules),
        trie_levels=tuple(levels_dev),
        trie_targets=put(trie_targets),
        joined=put(joined),
        root_lut=put(root_lut),
        num_entries=put(np.int32(tables.num_entries)),
    )
    record_build_phase(tables, "upload/device-put", time.perf_counter() - _t0)
    if pad:
        # same permanent contract the patch path enforces: a padded
        # upload IS the layout every later patch diffs against
        assert_patched_tables(result)
    return result


@functools.lru_cache(maxsize=None)
def _scatter_rows_jit():
    # NOT donated on purpose: in-flight classify dispatches hold the old
    # table handles, and the double-buffer contract says they finish on
    # the old generation.  XLA materializes copy-then-scatter on device —
    # a full-table HBM copy is milliseconds; what the patch saves is the
    # host->device transfer of the unchanged gigabytes.
    return jax.jit(lambda a, idx, rows: a.at[idx].set(rows))


def _patch_diff_payload(dev_arr, old_np: np.ndarray, new_np: np.ndarray,
                        fill=0):
    """The host-diff half of _patch_array: validate the bucket/dtype
    contract and compute the (pos, rows) scatter payload (possibly
    empty) from the UNPADDED old/new sources — no padded copies are
    materialized (np.full of multi-GB pad layouts was 20+s per patch).
    Appended rows scatter their new values; rows the table shrank away
    from reset to the pad fill, keeping the device state bit-identical
    to a fresh ``pad=True`` upload.  Returns (pos, rows) or None when
    the dtype/trailing dims/row bucket changed or the delta exceeds the
    capped-scatter budget (caller re-uploads)."""
    if old_np.dtype != new_np.dtype or old_np.shape[1:] != new_np.shape[1:]:
        return None
    nb = dev_arr.shape[0]
    if nb != _row_bucket(nb):
        # The resident array is not bucket-shaped — e.g. the inactive
        # (1, 1) joined placeholder, whose static shape SELECTS the
        # classify walk and must never be scatter-patched or padded
        # (the PR-4 bug class).  Refusing here is the permanent half of
        # the bucket contract; assert_patched_tables is the other.
        return None
    if (
        tuple(dev_arr.shape[1:]) != new_np.shape[1:]
        or _row_bucket(new_np.shape[0]) != nb
        or _row_bucket(old_np.shape[0]) != nb
    ):
        return None
    no, nn = old_np.shape[0], new_np.shape[0]
    common = min(no, nn)
    changed = np.nonzero(
        (
            old_np[:common].reshape(common, -1)
            != new_np[:common].reshape(common, -1)
        ).any(axis=1)
    )[0]
    parts_idx = [changed]
    parts_rows = [new_np[changed]]
    if nn > no:
        parts_idx.append(np.arange(no, nn))
        parts_rows.append(new_np[no:])
    elif no > nn:
        parts_idx.append(np.arange(nn, no))
        parts_rows.append(
            np.full((no - nn,) + new_np.shape[1:], fill, new_np.dtype)
        )
    idx = np.concatenate(parts_idx)
    rows = np.concatenate(parts_rows)
    if len(idx) > nb // 4:
        # Large delta: a bucketed scatter would ship close to the full
        # array AND pay the device-side copy — the full upload wins.
        return None
    return idx, rows


def _patch_array(dev_arr, old_np: np.ndarray, new_np: np.ndarray, device, fill=0):
    """Scatter-patch one bucket-padded device array from the host diff
    of its UNPADDED old/new sources (payload via _patch_diff_payload,
    launch via the shared capped executable).  Returns
    (patched_or_original_array, rows_changed) or None when the
    dtype/trailing dims/row bucket changed or the delta is oversized
    (caller re-uploads)."""
    pay = _patch_diff_payload(dev_arr, old_np, new_np, fill=fill)
    if pay is None:
        return None
    idx, rows = pay
    if len(idx) == 0:
        return dev_arr, 0
    patched = _capped_scatter(dev_arr, idx, rows, device)
    if patched is None:
        return None
    return patched, len(idx)


#: every patch of <= this many rows shares ONE scatter executable per
#: array shape — precompiled by warm_patch_scatters at load time; the
#: padding transfer cost (256 rows of the widest row layout) is a few KB
_PATCH_CAP = 256


def _scatter_cap(k: int, nb: int) -> int:
    """Padded scatter size for a k-row patch into an nb-row array: the
    fixed _PATCH_CAP for every small patch (one warmable executable),
    pow2 buckets only for rare large deltas."""
    if nb <= _PATCH_CAP:
        return nb
    if k <= _PATCH_CAP:
        return _PATCH_CAP
    return min(1 << (k - 1).bit_length(), nb)


def _scatter(dev_arr, pidx: np.ndarray, prows: np.ndarray, device):
    return _scatter_rows_jit()(
        dev_arr, jax.device_put(pidx, device), jax.device_put(prows, device)
    )


def _capped_payload(pos: np.ndarray, rows: np.ndarray, nb: int):
    """Pad a (pos, rows) scatter payload to its shared capped size
    (_scatter_cap) by repeating the last row — duplicate indices with
    identical values are a deterministic no-op — so every small patch of
    one array shape reuses one warmed executable.  Returns
    (pidx, prows) or None when the delta exceeds the capped-scatter
    budget (callers escalate to a re-upload/rebuild)."""
    k = len(pos)
    if k > nb // 4:
        return None
    cap = _scatter_cap(k, nb)
    pidx = np.empty(cap, np.int64)
    pidx[:k] = pos
    pidx[k:] = pos[-1]
    prows = np.empty((cap,) + rows.shape[1:], rows.dtype)
    prows[:k] = rows
    prows[k:] = rows[-1]
    return pidx, prows


def _capped_scatter(dev_arr, pos: np.ndarray, rows: np.ndarray, device):
    """Scatter ``rows`` at ``pos`` into ``dev_arr`` through the shared
    capped executable (see _scatter_cap): every small patch of one array
    shape reuses ONE warmed scatter compile.  Returns the patched array,
    or None when the delta is too large to win over a re-upload/rebuild
    (callers fall back).  Shared by the joined-row patch and the fused
    walk's byte-plane patch (pallas_walk.patch_walk_joined)."""
    k = len(pos)
    if k == 0:
        return dev_arr
    pay = _capped_payload(pos, rows, dev_arr.shape[0])
    if pay is None:
        return None
    return _scatter(dev_arr, pay[0], pay[1], device)


def _stage_capped(dev_arr, pos: np.ndarray, rows: np.ndarray, device):
    """The two-phase form of _capped_scatter: pad the payload and START
    its H2D copies now (jax.device_put is async), returning a zero-arg
    thunk that launches the warmed scatter.  A transaction patch stages
    EVERY tensor family's payload first — one H2D staging pass whose
    transfers overlap each other and whatever the device is running —
    then launches.  None when the payload exceeds the capped budget."""
    if len(pos) == 0:
        return lambda: dev_arr
    pay = _capped_payload(pos, rows, dev_arr.shape[0])
    if pay is None:
        return None
    didx = jax.device_put(pay[0], device)
    drows = jax.device_put(pay[1], device)
    return lambda: _scatter_rows_jit()(dev_arr, didx, drows)


# --- fused transaction scatter ----------------------------------------------
#
# A flushed edit transaction produces one merged dirty-row set per
# tensor family; the hot (rules-only) flush updates the whole dense
# group + the joined plane in ONE fused executable below, and every
# payload is pre-padded to its capped size so the executable cache stays
# bounded across transaction sizes (the dirty-row-count ladder prewarm
# in warm_txn_scatters keeps the serving path compile-free).

#: dirty-row-count prewarm bound: caps for transactions of up to this
#: many dirty rows are compiled at load time (larger transactions are
#: close to the nb//4 budget where the patch falls back to a re-upload
#: anyway)
TXN_WARM_MAX_ROWS = 512


@functools.lru_cache(maxsize=None)
def jitted_txn_scatter(n: int):
    """ONE fused executable scattering ``n`` (array, idx, rows) payloads
    in a single dispatch — the transaction patch launch.  NOT donated
    for the same double-buffer reason as _scatter_rows_jit: in-flight
    classifies finish on the old generation's handles."""
    def f(arrays, idxs, rows):
        return tuple(a.at[i].set(r) for a, i, r in zip(arrays, idxs, rows))

    return jax.jit(f)


def txn_scatter(entries, device):
    """Fused multi-array transaction scatter: ``entries`` is a sequence
    of ``(dev_arr, pos, rows)`` — one merged dirty-row payload per
    tensor family.  Every payload's H2D copy is staged before any
    launch (one staging pass), then ALL arrays update in one
    jitted_txn_scatter dispatch.  Zero-row payloads pass their array
    through untouched (and stay out of the launch — an identity scatter
    would still pay a device-side full-array copy).  Returns the list
    of patched arrays in entry order, or None when any payload exceeds
    the capped-scatter budget (the caller escalates to a full
    re-upload/rebuild)."""
    payloads = []
    for dev_arr, pos, rows in entries:
        if len(pos) == 0:
            payloads.append(None)
            continue
        pay = _capped_payload(pos, rows, dev_arr.shape[0])
        if pay is None:
            return None
        payloads.append(pay)
    live = [i for i, p in enumerate(payloads) if p is not None]
    out = [a for a, _pos, _rows in entries]
    if not live:
        return out
    # ONE staging pass: every payload's async copy is in flight before
    # the fused launch below
    staged = [
        (
            entries[i][0],
            jax.device_put(payloads[i][0], device),
            jax.device_put(payloads[i][1], device),
        )
        for i in live
    ]
    patched = jitted_txn_scatter(len(staged))(
        tuple(a for a, _i, _r in staged),
        tuple(i for _a, i, _r in staged),
        tuple(r for _a, _i, r in staged),
    )
    for j, i in enumerate(live):
        out[i] = patched[j]
    return out


def scatter_cap_ladder(nb: int, max_rows: int = TXN_WARM_MAX_ROWS):
    """The distinct dirty-row counts whose capped payloads exercise
    every executable shape a 1..max_rows-row patch of an nb-row array
    can emit — the dirty-row-count prewarm ladder (one representative k
    per distinct _scatter_cap)."""
    hi = min(max_rows, nb // 4)
    ks = []
    k = 1
    while k <= hi:
        ks.append(k)
        k = _scatter_cap(k, nb) + 1
    return ks


def warm_txn_scatters(dev: "DeviceTables", device=None,
                      max_rows: int = TXN_WARM_MAX_ROWS) -> None:
    """Pre-compile the fused transaction executable (jitted_txn_scatter)
    for the rules-only flush combo — the dense group plus, when active,
    the joined plane — across the dirty-row-count cap ladder, so a
    flushed edit transaction of any size up to ``max_rows`` launches
    compile-free.  Mixed-cap combos (families whose dirty counts land in
    different >256-row buckets) compile once on first use; uniform
    combos — every transaction below _PATCH_CAP rows, i.e. the churn
    regime — are fully covered here.  Same discard-the-result contract
    as warm_patch_scatters: the resident arrays are never mutated."""
    arrays = [dev.key_words, dev.mask_words, dev.mask_len, dev.rules]
    nb = arrays[0].shape[0]
    if nb <= 1 or nb != _row_bucket(nb):
        return
    if dev.joined.shape[0] > 1:
        arrays.append(dev.joined)
    for k in scatter_cap_ladder(nb, max_rows):
        txn_scatter(
            [
                (
                    a,
                    np.zeros(min(k, max(a.shape[0] // 4, 1)), np.int64),
                    np.zeros(
                        (min(k, max(a.shape[0] // 4, 1)),) + a.shape[1:],
                        a.dtype,
                    ),
                )
                for a in arrays
            ],
            device,
        )


def warm_patch_scatters(dev: DeviceTables, device=None) -> None:
    """Pre-compile the patch path's scatter executables so the FIRST
    incremental update after a (re)load does not pay the scatter-jit
    compile (~10s measured at the 1M tier).  The executable cache is
    keyed on abstract shapes/dtypes, and every <= _PATCH_CAP-row patch
    uses the SAME capped scatter shape (_scatter_cap), so one warm per
    array shape covers all small edits; the dirty-row-count ladder
    (scatter_cap_ladder) extends the coverage to multi-edit transaction
    flushes up to TXN_WARM_MAX_ROWS dirty rows, and warm_txn_scatters
    covers the FUSED rules-only combo the transaction patch launches.
    Each warm scatters against the RESIDENT array — _scatter is
    non-donating, so the live table is never mutated (XLA materializes
    copy-then-scatter) and the discarded result is the only transient
    allocation; scattering into a separate zeros scratch would double
    the transient HBM right after a full load, when the double-buffer
    contract may still hold the previous generation live."""
    warm_scatters(
        (dev.key_words, dev.mask_words, dev.mask_len, dev.rules,
         *dev.trie_levels, dev.trie_targets, dev.joined, dev.root_lut),
        device, max_rows=TXN_WARM_MAX_ROWS,
    )
    warm_txn_scatters(dev, device)


def warm_scatters(arrays, device=None, max_rows: int = 1) -> None:
    """Pre-compile the capped scatter executable for each distinct
    (shape, dtype) among ``arrays`` (the shared body of
    warm_patch_scatters, also used for the fused walk's patchable joined
    planes), across the dirty-row-count cap ladder up to ``max_rows``
    (default 1 = the single-edit cap only).  Arrays with <= 1 rows are
    skipped: a non-bucket resident (the (1, 1) placeholders) is never
    patchable by contract."""
    seen = set()
    for arr in arrays:
        for k in scatter_cap_ladder(arr.shape[0], max(max_rows, 1)):
            cap = _scatter_cap(k, arr.shape[0])
            key = (cap, tuple(arr.shape), str(arr.dtype))
            if arr.shape[0] <= 1 or key in seen:
                continue
            seen.add(key)
            pidx = np.zeros(cap, np.int64)
            # index 0 rewritten with... whatever value row 0 holds is NOT
            # needed: the scatter result is discarded, so writing zeros
            # into the COPY is harmless — the resident buffer is
            # untouched.
            prows = np.zeros((cap,) + arr.shape[1:], arr.dtype)
            _scatter(arr, pidx, prows, device)


def _patch_rows_payload(dev_arr, new_np: np.ndarray, rows: np.ndarray):
    """Hint-mode payload: ``new_np[rows]`` with no host diff.  ``rows``
    must be a SUPERSET of the rows whose values changed (the compiler's
    dirty tracking guarantees this); unchanged hinted rows rewrite their
    identical value.  Returns (pos, row values) — possibly empty — or
    None when the bucket/dtype no longer matches or the hint is too
    large to win."""
    nb = dev_arr.shape[0]
    if nb != _row_bucket(nb):
        return None  # non-bucket resident (placeholder): never patchable
    if (
        dev_arr.dtype != new_np.dtype
        or tuple(dev_arr.shape[1:]) != new_np.shape[1:]
        or _row_bucket(new_np.shape[0]) != nb
    ):
        return None
    rows = rows[rows < new_np.shape[0]]
    if len(rows) > nb // 4:
        return None
    return rows, new_np[rows]


def _patch_array_rows(dev_arr, new_np: np.ndarray, rows: np.ndarray, device):
    """Hint-mode patch: scatter ``new_np[rows]`` without any host diff
    (payload via _patch_rows_payload, launch via the shared capped
    executable).  Returns (array, k) or None when the bucket/dtype no
    longer matches or the hint is too large to win."""
    pay = _patch_rows_payload(dev_arr, new_np, rows)
    if pay is None:
        return None
    pos, vals = pay
    if len(pos) == 0:
        return dev_arr, 0
    patched = _capped_scatter(dev_arr, pos, vals, device)
    if patched is None:
        return None
    return patched, len(pos)


def patch_device_tables(
    dev: DeviceTables,
    old: CompiledTables,
    new: CompiledTables,
    device=None,
    hint=None,
) -> Tuple[DeviceTables, int] | None:
    """Incremental device update — the TPU-native Map.Update
    (/root/reference/pkg/ebpf/ingress_node_firewall_loader.go:200-218,
    where a one-CIDR edit touches one kernel map key): diff the old/new
    host tables row-wise (in the padded device layout, so the patched
    state is bit-identical to a fresh ``device_tables(new, pad=True)``
    upload) and ship ONLY the changed rows, scattering them into a
    device-side copy of the resident arrays.  A one-key edit at 1M
    entries uploads kilobytes instead of the ~3.4GB full table.

    ``dev`` must have been built with ``pad=True``.  Returns
    (new DeviceTables, total_rows_changed), or None when the structure
    changed beyond the row buckets (level count, bucket growth,
    compaction shrink past a bucket) and the caller re-uploads in full.

    Trie levels live on device in the poptrie form (build_poptrie), and
    a CIDR edit renumbers/ranks nodes, so per-level changes are diffed
    on the poptrie HOST arrays — the level hint (slot-space row numbers)
    does not apply to them and only accelerates the dense arrays; a
    rules-only edit (the common Map.Update case) leaves every level's
    poptrie bytes identical and the diff is a cheap vectorized compare."""
    if len(dev.trie_levels) != len(new.trie_levels) or len(
        old.trie_levels
    ) != len(new.trie_levels):
        return None
    # Rules-only edits (the common Map.Update) leave the trie untouched —
    # the dirty hint proves it (its level lists track slot-space repush
    # writes), so the poptrie transform AND the per-level diffs are
    # skipped entirely and the resident level arrays carry over.
    trie_unchanged = hint_trie_unchanged(hint)
    if trie_unchanged:
        # Seed the NEW generation's host caches from the old one BEFORE
        # any layout call: without this, every patched generation rebuilt
        # the packed-rules array (O(table) repack) and — worse — the next
        # edit's joined_patch_rows(old=this generation) re-ran the FULL
        # poptrie transform because no cache existed (measured: 1-key
        # rule edits at 1M cost ~6s instead of ~1s).  Rules-only edits
        # keep the trie and the position layout identical, so the poptrie
        # cache is shared by reference and packed/joined caches are
        # copied + dirty-row-patched.
        seeded_pr = _seed_caches_forward(old, new, hint.get("dense"))
    else:
        seeded_pr = None
    o = _host_device_layout(old, pad=False, with_trie=not trie_unchanged)
    nw = _host_device_layout(new, pad=False, with_trie=not trie_unchanged)
    # only trie levels / targets / root_lut go through put: pad fill is 0
    put = lambda a: jax.device_put(
        jnp.asarray(_pad_rows(a, _row_bucket(a.shape[0]))), device
    )
    total = 0

    fused_joined = None  # resident joined patched by the fused launch
    if hint is not None:
        # Transaction fast path (the update-storm flush): ONE merged
        # dirty-row payload per dense array — plus the joined plane on
        # rules-only flushes — staged in one H2D pass and launched as
        # ONE fused scatter executable (jitted_txn_scatter, pre-warmed
        # across the dirty-row ladder by warm_txn_scatters), so a
        # 64-edit folded transaction costs one dispatch, not 5 x 64.
        entries = []
        for dl, nl in zip(
            (dev.key_words, dev.mask_words, dev.mask_len, dev.rules),
            nw[:4],
        ):
            pay = _patch_rows_payload(dl, nl, hint["dense"])
            if pay is None:
                return None
            entries.append((dl,) + pay)
        if trie_unchanged and dev.joined.shape[0] > 1:
            # the joined array carries RULE BYTES, so a rules-only edit
            # must patch its rows too (positions from the old
            # generation's cached map; trie unchanged = positions valid)
            pr = (seeded_pr if seeded_pr is not None
                  else joined_patch_rows(old, new, hint["dense"]))
            if pr is None:
                return None
            pos, rows = pr
            if len(pos):
                if (
                    rows.dtype != dev.joined.dtype
                    or rows.shape[1:] != tuple(dev.joined.shape[1:])
                    or int(pos.max()) >= dev.joined.shape[0]
                ):
                    return None
            entries.append((dev.joined, pos, rows))
        patched = txn_scatter(entries, device)
        if patched is None:
            return None
        dense = patched[:4]
        total += sum(len(e[1]) for e in entries)
        if len(entries) > 4:
            fused_joined = patched[4]
    else:
        dense = []
        for dl, ol, nl, fill in zip(
            (dev.key_words, dev.mask_words, dev.mask_len, dev.rules),
            o[:4],
            nw[:4],
            (0, 0, -1, 0),
        ):
            p = _patch_array(dl, ol, nl, device, fill=fill)
            if p is None:
                return None
            dense.append(p[0])
            total += p[1]
    if trie_unchanged:
        levels = list(dev.trie_levels)
        trie_targets = dev.trie_targets
        joined = fused_joined if fused_joined is not None else dev.joined
    else:
        # Structural flush: compute every family's host diff FIRST, then
        # start every payload's (and fallback re-upload's) H2D copy in
        # one staging pass — the transfers overlap each other and
        # whatever the device is running — then launch the per-family
        # warmed scatters.  A family whose bucket changed (or whose
        # delta is oversized) re-uploads just itself.
        specs = []  # (tag, dev_arr, new host array, payload | None)
        for dl, ol, nl in zip(dev.trie_levels, o[4], nw[4]):
            specs.append(("level", dl, nl, _patch_diff_payload(dl, ol, nl)))
        specs.append((
            "targets", dev.trie_targets, nw[5],
            _patch_diff_payload(dev.trie_targets, o[5], nw[5]),
        ))
        if nw[7].shape[0] <= 1:
            specs.append(("joined-inactive", dev.joined, nw[7], None))
        else:
            specs.append((
                "joined", dev.joined, nw[7],
                _patch_diff_payload(dev.joined, o[7], nw[7]),
            ))
        staged = []  # ("ready", array, rows) | ("launch", thunk, rows)
        for tag, dl, nl, pay in specs:
            if tag == "joined-inactive":
                # Inactive joined row ((1, 1) placeholder or single-
                # sentinel layout): it must keep its exact single-row
                # shape — classify selects the joined walk on
                # joined.shape[0] > 1, so a bucket-padded put() here
                # would flip a non-joined table into walking a
                # zero/garbage-width rules tail (and the payload helpers
                # always refuse it: _row_bucket(1) == 8 != 1).
                # assert_patched_tables below enforces this as a
                # permanent contract at the mutation site.
                if _inject_joined_pad_bug():
                    arr = put(nl)  # the PR-4 defect, re-introduced
                else:
                    arr = jax.device_put(jnp.asarray(nl), device)
                staged.append(
                    ("ready", arr, 0 if dev.joined.shape[0] <= 1 else 1)
                )
                continue
            if pay is None:
                staged.append(("ready", put(nl), len(nl)))
                continue
            pos, vals = pay
            if len(pos) == 0:
                staged.append(("ready", dl, 0))
                continue
            th = _stage_capped(dl, pos, vals, device)
            if th is None:
                staged.append(("ready", put(nl), len(nl)))
            else:
                staged.append(("launch", th, len(pos)))
        outs = []
        for mode, x, k in staged:
            outs.append(x if mode == "ready" else x())
            total += k
        n_lv = len(dev.trie_levels)
        levels = outs[:n_lv]
        trie_targets = outs[n_lv]
        joined = outs[n_lv + 1]
    p = _patch_array(dev.root_lut, o[6], nw[6], device)
    if p is None:
        root_lut = put(nw[6])
        total += len(nw[6])
    else:
        root_lut, k = p
        total += k
    result = DeviceTables(
        key_words=dense[0],
        mask_words=dense[1],
        mask_len=dense[2],
        rules=dense[3],
        trie_levels=tuple(levels),
        trie_targets=trie_targets,
        joined=joined,
        root_lut=root_lut,
        num_entries=jax.device_put(
            jnp.asarray(np.int32(new.num_entries)), device
        ),
    )
    # Permanent post-patch contract (shape-only, negligible cost): the
    # PR-4 bug class — a placeholder that stopped being exactly (1, 1),
    # a de-bucketed row count — raises HERE, at the mutation site.
    assert_patched_tables(result)
    return result, total


def assert_patched_tables(dev: DeviceTables) -> None:
    """Cheap permanent shape contract on a padded/patched DeviceTables;
    raises DeviceTableInvariantError on violation.  Checks only static
    shapes/dtypes (no device reads), so it is always on — the deep
    data-level pass lives in infw.analysis.statecheck.check_device_tables
    and runs under INFW_CHECK_INVARIANTS / the model checker."""
    nb = dev.key_words.shape[0]
    for name, arr in (
        ("key_words", dev.key_words), ("mask_words", dev.mask_words),
        ("mask_len", dev.mask_len), ("rules", dev.rules),
    ):
        if arr.shape[0] != nb:
            raise DeviceTableInvariantError(
                f"dense row-count mismatch: {name} has {arr.shape[0]} rows, "
                f"key_words has {nb}"
            )
    if nb != _row_bucket(nb):
        raise DeviceTableInvariantError(
            f"dense arrays have {nb} rows — not a valid row bucket "
            f"(_row_bucket({nb}) == {_row_bucket(nb)})"
        )
    j = dev.joined
    meta_w = 3 if j.dtype == jnp.uint16 else 2
    if j.shape[0] <= 1:
        # Inactive for classify (the walk selects on shape[0] > 1): the
        # (1, 1) placeholder, or a single-sentinel-row joined layout
        # from a tiny/empty table.  Any other width means something
        # padded or truncated the placeholder.
        if j.shape[1] != 1 and j.shape[1] != meta_w + dev.rules.shape[1]:
            raise DeviceTableInvariantError(
                f"inactive joined row has width {j.shape[1]} — neither "
                "the (1, 1) placeholder nor the sentinel joined layout "
                f"({meta_w} + rules width {dev.rules.shape[1]})"
            )
    else:
        # ACTIVE for classify: the row must really carry
        # [tidx, mask_len, rules] in the resident rules layout — a
        # bucket-padded placeholder ((8, 1), the PR-4 bug) or a stale
        # width would make classify walk a zero/garbage-width rules tail.
        if j.dtype != dev.rules.dtype:
            raise DeviceTableInvariantError(
                f"active joined dtype {j.dtype} != rules dtype "
                f"{dev.rules.dtype}"
            )
        if j.shape[1] != meta_w + dev.rules.shape[1]:
            raise DeviceTableInvariantError(
                f"active joined row width {j.shape[1]} != {meta_w} + rules "
                f"width {dev.rules.shape[1]} (a bucket-padded placeholder "
                "masquerading as an active joined plane — the PR-4 bug "
                "class)"
            )
        if j.shape[0] != _row_bucket(j.shape[0]):
            raise DeviceTableInvariantError(
                f"active joined array has {j.shape[0]} rows — not a valid "
                "row bucket"
            )
    for i, lvl in enumerate(dev.trie_levels):
        n = lvl.shape[0]
        if i == 0:
            if n % 65536:
                raise DeviceTableInvariantError(
                    f"trie level 0 has {n} rows — not a whole number of "
                    "DIR-16 root nodes (65536 slots each)"
                )
        elif n != _row_bucket(n):
            raise DeviceTableInvariantError(
                f"trie level {i} has {n} rows — not a valid row bucket"
            )


def device_batch(batch: PacketBatch, device=None) -> DeviceBatch:
    put = lambda a: jax.device_put(jnp.asarray(a), device)
    return DeviceBatch(
        kind=put(batch.kind),
        l4_ok=put(batch.l4_ok),
        ifindex=put(batch.ifindex),
        ip_words=put(batch.ip_words.astype(np.uint32)),
        proto=put(batch.proto),
        dst_port=put(batch.dst_port),
        icmp_type=put(batch.icmp_type),
        icmp_code=put(batch.icmp_code),
        pkt_len=put(batch.pkt_len),
    )


def unpack_wire(wire: jax.Array) -> DeviceBatch:
    """Device-side inverse of PacketBatch.pack_wire / pack_wire_v4 /
    packets.narrow_wire, discriminated by the (static) wire width:
    (B, 7) full layout, (B, 4) v4-compact (IP word 0 only, high words
    reconstructed as zeros — the v4 key invariant), (B, 3) / (B, 6) the
    NARROW layouts (ifindex folded into w0, dst_port overlaid with the
    ICMP fields in one l4 word — lossless for classification, see
    narrow_wire).  Pure elementwise bit ops, fused by XLA into whatever
    consumes the fields — the packed descriptor never round-trips HBM."""
    w0 = wire[:, 0]
    w1 = wire[:, 1]
    narrow = wire.shape[1] in (3, 6)
    ip_off = 2 if narrow else 3
    if wire.shape[1] in (3, 4):
        ip_words = jnp.concatenate(
            [
                wire[:, ip_off : ip_off + 1],
                jnp.zeros((wire.shape[0], 3), wire.dtype),
            ],
            axis=1,
        )
    else:
        ip_words = wire[:, ip_off : ip_off + 4]
    proto = ((w0 >> 3) & 0xFF).astype(jnp.int32)
    if narrow:
        is_icmp = (proto == IPPROTO_ICMP) | (proto == IPPROTO_ICMPV6)
        l4w = (w1 & 0xFFFF).astype(jnp.int32)
        ifindex = ((w0 >> 11) & 0xFFFF).astype(jnp.int32)
        dst_port = jnp.where(is_icmp, 0, l4w)
        icmp_type = jnp.where(is_icmp, l4w >> 8, 0)
        icmp_code = jnp.where(is_icmp, l4w & 0xFF, 0)
        pkt_len = ((w1 >> 16) & 0xFFFF).astype(jnp.int32)
    else:
        ifindex = wire[:, 2].astype(jnp.int32)
        dst_port = (w1 & 0xFFFF).astype(jnp.int32)
        icmp_type = ((w0 >> 11) & 0xFF).astype(jnp.int32)
        icmp_code = ((w0 >> 19) & 0xFF).astype(jnp.int32)
        pkt_len = (((w1 >> 16) & 0xFFFF) | ((w0 >> 27) << 16)).astype(jnp.int32)
    return DeviceBatch(
        kind=(w0 & 3).astype(jnp.int32),
        l4_ok=((w0 >> 2) & 1).astype(jnp.int32),
        ifindex=ifindex,
        ip_words=ip_words,
        proto=proto,
        dst_port=dst_port,
        icmp_type=icmp_type,
        icmp_code=icmp_code,
        pkt_len=pkt_len,
    )


def unpack_wire8(wire: jax.Array, ifmap: jax.Array) -> DeviceBatch:
    """Device-side inverse of packets.wire8: (B, 2) uint32 rows + the
    (16,) int32 ifindex dictionary.  pkt_len is reconstructed as ZERO —
    this format never carries lengths; byte statistics are computed
    host-side from the returned verdicts (daemon.stats_from_results), so
    callers must NOT consume the device stats of a wire8 classify."""
    w0 = wire[:, 0]
    proto = ((w0 >> 3) & 0xFF).astype(jnp.int32)
    is_icmp = (proto == IPPROTO_ICMP) | (proto == IPPROTO_ICMPV6)
    l4w = ((w0 >> 15) & 0xFFFF).astype(jnp.int32)
    ifd = ((w0 >> 11) & 0xF).astype(jnp.int32)
    ifindex = jnp.take(ifmap, ifd, mode="clip").astype(jnp.int32)
    zeros = jnp.zeros_like(proto)
    return DeviceBatch(
        kind=(w0 & 3).astype(jnp.int32),
        l4_ok=((w0 >> 2) & 1).astype(jnp.int32),
        ifindex=ifindex,
        ip_words=jnp.concatenate(
            [wire[:, 1:2], jnp.zeros((wire.shape[0], 3), wire.dtype)], axis=1
        ),
        proto=proto,
        dst_port=jnp.where(is_icmp, 0, l4w),
        icmp_type=jnp.where(is_icmp, l4w >> 8, 0),
        icmp_code=jnp.where(is_icmp, l4w & 0xFF, 0),
        pkt_len=zeros,
    )


def _pack_res16(res16: jax.Array) -> jax.Array:
    """(B,) u16 -> ceil(B/2) int32 single-buffer D2H payload.  The
    (nw, 2) u16 -> u32 bitcast is a pure reinterpretation, no
    lane-crossing shuffles (the strided r[0::2] | r[1::2] << 16 form
    measures ~40% slower on the chip)."""
    r = res16
    if r.shape[0] % 2:
        r = jnp.concatenate([r, jnp.zeros(1, jnp.uint16)])
    packed = jax.lax.bitcast_convert_type(r.reshape(-1, 2), jnp.uint32)
    return jax.lax.bitcast_convert_type(packed, jnp.int32)


def unpack_res16_host(arr: np.ndarray, b: int) -> np.ndarray:
    u = arr.view(np.uint32)
    res16 = np.empty(len(u) * 2, np.uint16)
    res16[0::2] = u & 0xFFFF
    res16[1::2] = u >> 16
    return res16[:b]


def classify_wire8(
    tables: DeviceTables, wire: jax.Array, ifmap: jax.Array,
    overlay: "Optional[DeviceTables]" = None, *, v4_only: bool = True
) -> jax.Array:
    """wire8 classify: res16-only packed D2H (stats are host-derived for
    this format; the wire is v4-compact by construction, so the walk
    truncates like classify_wire's v4_only path)."""
    if v4_only:
        depth = v4_trie_depth(len(tables.trie_levels))
        tables = tables._replace(trie_levels=tables.trie_levels[:depth])
    batch = unpack_wire8(wire, ifmap)
    if overlay is not None:
        res, _x, _s = classify_with_overlay(
            tables, overlay, batch, use_trie=True
        )
    else:
        res, _x, _s = classify(tables, batch, use_trie=True)
    return _pack_res16(res.astype(jnp.uint16))


@functools.lru_cache(maxsize=None)
def jitted_classify_wire8_fused(overlay: bool, v4_only: bool = True):
    if overlay:
        def f(tables, ov, wire, ifmap):
            return classify_wire8(tables, wire, ifmap, ov, v4_only=v4_only)
    else:
        def f(tables, wire, ifmap):
            return classify_wire8(tables, wire, ifmap, v4_only=v4_only)

    return jax.jit(f)


def build_depth_lut(tables: CompiledTables) -> np.ndarray:
    """(n0*65536,) int8 per-root-slot DEEP-LEVEL requirement: the number
    of trie levels BELOW the DIR-16 root reachable under each root slot
    — i.e. packets whose (root, top-16-bits) slot maps to value d are
    fully classified by trie_levels[:1+d].

    This is the depth-steering analogue of the v4 family split: measured
    on the 100K bench table, 52% of v6 packets need <=3 deep levels (26%
    need none at all) while the static walk pays all 14 — and the walk
    cost is linear in levels (~2.45 ns/level on v5e).  The LUT is a
    TABLE-SHAPE property: conservative under deletes (targets only
    disappear, depth never grows), recomputed on any structural load
    (the host-cache carry-forward only survives provably rules-only
    edits, see _seed_caches_forward).

    Memoized on the tables instance."""
    cached = getattr(tables, "_depth_lut_cache", None)
    if cached is not None:
        return cached
    levels = tables.trie_levels
    strides = trie_level_strides(len(levels))
    depth_next = None  # per-node depth of the NEXT level
    for l in range(len(levels) - 1, 0, -1):
        slots = 1 << strides[l]
        child = levels[l].reshape(-1, slots, 2)[:, :, 0]
        if depth_next is None:
            d = np.ones(child.shape[0], np.int8)
        else:
            cd = np.where(
                child > 0,
                depth_next[np.clip(child, 0, len(depth_next) - 1)],
                0,
            )
            d = (1 + cd.max(axis=1, initial=0)).astype(np.int8)
        depth_next = d
    l0 = levels[0].reshape(-1, 2)
    if depth_next is None:
        lut = np.zeros(l0.shape[0], np.int8)
    else:
        lut = np.where(
            l0[:, 0] > 0,
            depth_next[np.clip(l0[:, 0], 0, len(depth_next) - 1)],
            0,
        ).astype(np.int8)
    try:
        object.__setattr__(tables, "_depth_lut_cache", lut)
    except (AttributeError, TypeError):
        pass
    return lut


#: deep-level class thresholds for depth steering: each v6 chunk walks
#: the smallest class >= its packets' LUT depth.  A fixed small set
#: bounds the number of compiled executables.
DEPTH_CLASS_THRESHOLDS = (0, 3, 7)


def depth_group_indices(root_lut_np, lut, classes, ifindex, ip_words, idx):
    """Host-side depth-class binning shared by the classifier's
    v6_depth_groups and the bench's steered split: returns
    [(class_or_None, positions)] partitioning ``idx``; the last class is
    reported as None (full depth — untruncated executable).  Out-of-range
    ifindexes bin to class 0 (they resolve to the reserved null root
    whose subtree is empty)."""
    ifx = np.asarray(ifindex)[idx].astype(np.int64)
    ok = (ifx >= 0) & (ifx < len(root_lut_np))
    root = np.where(ok, root_lut_np[np.clip(ifx, 0, len(root_lut_np) - 1)], 0)
    nib0 = (
        np.asarray(ip_words)[idx, 0].astype(np.uint32) >> 16
    ).astype(np.int64)
    e0 = root * 65536 + nib0
    in0 = ok & (e0 >= 0) & (e0 < len(lut))
    pd = np.where(in0, lut[np.clip(e0, 0, len(lut) - 1)], 0)
    out = []
    prev = -1
    for c in classes:
        sub = idx[np.nonzero((pd > prev) & (pd <= c))[0]]
        prev = c
        if len(sub):
            out.append((None if c == classes[-1] else int(c), sub))
    return out


def depth_classes(n_levels: int):
    """The usable class list for a table of ``n_levels`` trie levels:
    thresholds below the full deep depth, plus the full depth."""
    full = n_levels - 1
    return tuple(t for t in DEPTH_CLASS_THRESHOLDS if t < full) + (full,)


def depth_class_histogram(tables: CompiledTables) -> np.ndarray:
    """(full_depth + 1,) root-slot counts per deep-level requirement —
    the depth histogram the steering thresholds are tuned against.
    Index d = number of DIR-16 slots whose subtree needs exactly d deep
    levels (build_depth_lut); slot mass is the available proxy for
    packet mass (the bench logs the per-class packet split so the
    recorded run shows both)."""
    lut = build_depth_lut(tables)
    full = max(len(tables.trie_levels) - 1, 0)
    return np.bincount(
        np.asarray(lut, np.int64), minlength=full + 1
    )[: full + 1]


def tune_depth_classes(tables: CompiledTables, max_classes: int = 4):
    """Depth-class thresholds tuned to THIS table's depth histogram
    instead of the static DEPTH_CLASS_THRESHOLDS (which were picked
    against the 100K bench table and under-split the 1M adversarial
    histogram — round-5 verdict ask #3): up to ``max_classes - 1``
    thresholds at equal-mass quantiles of the sub-full-depth slot mass,
    deduped, always ending with the full depth.  Degenerate histograms
    (no sub-full mass, single level) fall back to the static classes.
    Memoized on the tables instance (rides the build_depth_lut cache
    plus its own) — the classifier asks on every load."""
    cached = getattr(tables, "_depth_classes_cache", None)
    if cached is not None:
        return cached
    full = len(tables.trie_levels) - 1
    if full <= 0:
        return (max(full, 0),)
    hist = depth_class_histogram(tables).astype(np.float64)
    below = hist[:full]
    # depth 0 ("no deep levels") always gets its own class: it is the
    # cheapest executable AND the dominant slot mass on real tables, so
    # quantiles are computed over the REMAINING (depth >= 1) mass — a
    # depth-0-dominated histogram would otherwise collapse every
    # threshold to 0 and leave the full class covering depths 1..full.
    mass = below[1:].sum()
    if mass <= 0:
        result = depth_classes(len(tables.trie_levels))
    else:
        cum = np.cumsum(below[1:]) / mass  # cum[i] = mass at depth <= i+1
        picks = {0}
        n_thresh = max(max_classes - 2, 1)
        for k in range(1, n_thresh + 1):
            q = k / (n_thresh + 1)
            d = 1 + int(np.searchsorted(cum, q))
            if 0 < d < full:
                picks.add(d)
        result = tuple(sorted(picks)) + (full,)
    try:
        object.__setattr__(tables, "_depth_classes_cache", result)
    except (AttributeError, TypeError):
        pass
    return result


def v4_trie_depth(n_levels: int) -> int:
    """Number of leading trie levels whose bit boundary is within the IPv4
    packet-side cap (32 bits): entries longer than /32 can never match a
    v4 packet (kernel.c:207), so a v4-only batch walks only these levels.
    With 16-8-8-... strides that is min(3, n_levels)."""
    strides = trie_level_strides(n_levels)
    depth, bit_end = 0, 0
    for s in strides:
        bit_end += s
        if bit_end > 32:
            break
        depth += 1
    return max(1, depth)


def walk_levels(n_levels: int, v4_only: bool, depth: Optional[int]) -> int:
    """Trie levels (the DIR-16 root counted) the resident step walks on
    a table of ``n_levels``: a v4-only batch stops at the /32 boundary,
    a depth class at its deepest level, any other batch walks them all."""
    if v4_only:
        return v4_trie_depth(n_levels)
    return n_levels if depth is None else 1 + depth


def classify_wire(
    tables: DeviceTables, wire: jax.Array, *, use_trie: bool,
    v4_only: bool = False, depth: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Wire-format forward pass: packed descriptors in, (results_u16,
    stats) out.  The D2H payload is 2B/packet — ruleId ≤ 255 always holds
    (MAX_RULES_PER_TARGET=100), and the XDP verdict is host-derivable from
    (results, kind), so neither the u32 results nor the xdp array crosses
    the link.

    ``v4_only`` is the depth-specialization fast path: when the caller
    guarantees the batch holds no IPv6 packets, the trie walk is truncated
    to the levels reachable under the 32-bit cap — a /128-deep table walks
    3 gathers instead of 15.  ``depth`` is the v6 analogue (depth-class
    steering, build_depth_lut): the caller guarantees every packet's root
    slot needs at most ``depth`` DEEP levels, so the walk keeps
    trie_levels[:1+depth].  The truncated level tuple changes the pytree
    structure, so jit compiles a separate (cheaper) executable."""
    if use_trie and v4_only:
        d = v4_trie_depth(len(tables.trie_levels))
        tables = tables._replace(trie_levels=tables.trie_levels[:d])
    elif use_trie and depth is not None:
        tables = tables._replace(
            trie_levels=tables.trie_levels[: 1 + depth])
    res, _xdp, stats = classify(tables, unpack_wire(wire), use_trie=use_trie)
    return res.astype(jnp.uint16), stats


def check_wire_ruleids(tables: CompiledTables) -> None:
    """The wire result is (ruleId<<8 | action) cast to uint16, so ruleIds
    must fit in 8 bits.  Syncer-compiled tables always satisfy this
    (ruleId == order < MAX_RULES_PER_TARGET), but
    compile_tables_from_content accepts adversarial direct content where
    rid goes up to 2^24 — fail loudly at load time instead of silently
    corrupting reported ruleIds (the analogue of the pallas rule_width
    guard in build_pallas_tables)."""
    max_rid = int(tables.rules[..., 0].max()) if tables.rules.size else 0
    if max_rid > 0xFF:
        raise ValueError(
            f"max ruleId {max_rid} > 255 does not fit the uint16 wire "
            "result; use the u32 (non-wire) classify path"
        )


@functools.lru_cache(maxsize=None)
def jitted_classify_wire(use_trie: bool, v4_only: bool = False,
                         depth: Optional[int] = None):
    return jax.jit(
        functools.partial(classify_wire, use_trie=use_trie,
                          v4_only=v4_only, depth=depth)
    )


def fuse_wire_outputs(res16: jax.Array, stats: jax.Array) -> jax.Array:
    """Pack (results_u16, stats_i32) into ONE int32 device buffer.

    Each D2H materialization pays the host-device sync floor once, so
    reading results and stats separately doubles the per-chunk readback
    latency for the 24KB of stats.

    Layout: ceil(B/2) words of u16-pair-packed results, then stats
    flattened; bitcast (not convert) keeps the high result's top bit."""
    return jnp.concatenate([_pack_res16(res16), stats.reshape(-1)])


def split_wire_outputs(arr: np.ndarray, b: int) -> Tuple[np.ndarray, np.ndarray]:
    """Host inverse of fuse_wire_outputs -> (results_u16[b], stats_i32)."""
    nw = (b + 1) // 2
    res16 = unpack_res16_host(arr[:nw], b)
    stats = arr[nw:].reshape(MAX_TARGETS, 6)
    return res16[:b], stats


@functools.lru_cache(maxsize=None)
def jitted_classify_wire_fused(use_trie: bool, v4_only: bool = False,
                               depth: Optional[int] = None):
    def f(tables: DeviceTables, wire: jax.Array) -> jax.Array:
        return fuse_wire_outputs(
            *classify_wire(tables, wire, use_trie=use_trie,
                           v4_only=v4_only, depth=depth)
        )

    return jax.jit(f)


def classify_wire_overlay(
    tables: DeviceTables,
    overlay: DeviceTables,
    wire: jax.Array,
    *,
    use_trie: bool,
    v4_only: bool = False,
    depth: Optional[int] = None,
) -> Tuple[jax.Array, jax.Array]:
    """classify_wire with the overlay combine (see classify_with_overlay);
    the v4/depth truncation applies to the main trie only."""
    if use_trie and v4_only:
        d = v4_trie_depth(len(tables.trie_levels))
        tables = tables._replace(trie_levels=tables.trie_levels[:d])
    elif use_trie and depth is not None:
        tables = tables._replace(
            trie_levels=tables.trie_levels[: 1 + depth])
    res, _xdp, stats = classify_with_overlay(
        tables, overlay, unpack_wire(wire), use_trie=use_trie
    )
    return res.astype(jnp.uint16), stats


@functools.lru_cache(maxsize=None)
def jitted_classify_wire_overlay_fused(use_trie: bool, v4_only: bool = False,
                                       depth: Optional[int] = None):
    def f(tables: DeviceTables, overlay: DeviceTables, wire: jax.Array):
        return fuse_wire_outputs(
            *classify_wire_overlay(
                tables, overlay, wire, use_trie=use_trie, v4_only=v4_only,
                depth=depth,
            )
        )

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def jitted_classify_with_overlay(use_trie: bool):
    return jax.jit(
        functools.partial(classify_with_overlay, use_trie=use_trie)
    )


def host_finalize_wire(res16: np.ndarray, kind: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """Host-side completion of the wire path: widen results to u32 and
    rebuild the XDP verdict exactly as finalize() does on device
    (kernel.c:423-455 — malformed DROP, deny DROP, else PASS)."""
    results = res16.astype(np.uint32)
    action = results & 0xFF
    xdp = np.where(
        kind == KIND_MALFORMED,
        XDP_DROP,
        np.where(action == DENY, XDP_DROP, XDP_PASS),
    ).astype(np.int32)
    return results, xdp


def packet_key_words(batch: DeviceBatch) -> jax.Array:
    """(B, 5) uint32 — [ifindex, ip word0..3]: the LPM key the kernel
    builds at kernel.c:206-212 / 292-295."""
    return jnp.concatenate(
        [batch.ifindex.astype(jnp.uint32)[:, None], batch.ip_words], axis=1
    )


def lpm_dense_scores(tables: DeviceTables, batch: DeviceBatch) -> jax.Array:
    """(B, T) compare-all LPM match scores: mask_len + 1 for matching
    non-padding entries within the packet-side cap (32 for v4, 128 for
    v6 — kernel.c:206-219), else 0.  The ONE dense-match implementation:
    both the single-chip path (lpm_dense) and the mesh rules-sharded
    partial (parallel.mesh._local_dense_partial) consume these scores, so
    a semantics change lands everywhere at once."""
    pkt = packet_key_words(batch)  # (B,5)
    diff = (pkt[:, None, :] ^ tables.key_words[None]) & tables.mask_words[None]
    match = jnp.all(diff == 0, axis=-1)  # (B,T)
    cap = jnp.where(batch.kind == KIND_IPV4, 32, 128)  # packet-side mask cap
    ok = match & (tables.mask_len[None] >= 0) & (tables.mask_len[None] <= cap[:, None])
    return jnp.where(ok, tables.mask_len[None] + 1, 0)  # (B,T)


def lpm_dense(tables: DeviceTables, batch: DeviceBatch) -> jax.Array:
    """Compare-all LPM: returns per-packet target index or -1."""
    score = lpm_dense_scores(tables, batch)
    tidx = jnp.argmax(score, axis=1).astype(jnp.int32)
    return jnp.where(jnp.max(score, axis=1) > 0, tidx, -1)


def _popcount32(x: jax.Array) -> jax.Array:
    """SWAR popcount on uint32 lanes (no native popcount in jnp) — 5
    vector ops, fused by XLA into the walk's per-level arithmetic."""
    x = x - ((x >> 1) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> 2) & np.uint32(0x33333333))
    x = (x + (x >> 4)) & np.uint32(0x0F0F0F0F)
    return (x * np.uint32(0x01010101)) >> 24


def _poptrie_descend(trie_levels, root_lut: jax.Array, batch: DeviceBatch):
    """The level loop trie_walk and trie_walk_joined share: the DIR-16
    root gather (stage ``lpm_root``), then per level one node-row
    gather, the bitmap rank and the win update (``lpm_level``).  Returns
    (in0, rows0, win): the root range check, the root slot rows and the
    deepest target position the levels found (uint32, 0: none); the
    callers resolve it in ``lpm_resolve``."""
    strides = trie_level_strides(len(trie_levels))
    with jax.named_scope("lpm_root"):
        lut_size = root_lut.shape[0]
        if_ok = (batch.ifindex >= 0) & (batch.ifindex < lut_size)
        root = jnp.where(
            if_ok, jnp.take(root_lut, jnp.clip(batch.ifindex, 0, lut_size - 1)),
            0,
        )
        # OOB policy for every gather in the walk: indices are in-range
        # by construction (child ranks only reach allocated nodes; dead
        # lanes pin to 0; build_poptrie bounds the root level so e0
        # below cannot wrap int32), and should a future build bug break
        # that, the lane is INVALIDATED — an explicit range mask forces
        # it to UNDEF, i.e. XDP PASS (deterministic, never a
        # wrong-verdict read; note this default is allow, matching the
        # kernel's no-match semantics, kernel.c:453).
        nib0 = (batch.ip_words[:, 0] >> np.uint32(16)).astype(jnp.int32)
        e0 = root * 65536 + nib0
        in0 = (e0 >= 0) & (e0 < trie_levels[0].shape[0])
        rows0 = jnp.take(trie_levels[0], e0, axis=0, mode="clip")
        alive = in0 & (rows0[:, 0] > 0)  # child ids are stored +1 (0 = none)
        node = jnp.where(alive, rows0[:, 0] - 1, 0)
        cap_bits = jnp.where(batch.kind == KIND_IPV4, 32, 128)
        win = jnp.zeros_like(node, dtype=jnp.uint32)  # targets[0] sentinel
    widx8 = jnp.arange(8, dtype=jnp.int32)[None, :]

    bit_end = strides[0]
    for stride, tbl in zip(strides[1:], trie_levels[1:]):
        bit_start, bit_end = bit_end, bit_end + stride
        with jax.named_scope("lpm_level"):
            w32 = bit_start // 32
            shift = 32 - stride - (bit_start % 32)
            nib = (
                (batch.ip_words[:, w32] >> np.uint32(shift))
                & np.uint32((1 << stride) - 1)
            ).astype(jnp.int32)
            in_l = (node >= 0) & (node < tbl.shape[0])
            alive = alive & in_l
            r = jnp.take(tbl, node, axis=0, mode="clip")
            w = (nib >> 5)[:, None]          # bitmap word 0..7
            below = (np.uint32(1) << (nib & 31).astype(jnp.uint32)) - 1
            cb = r[:, 2:10]
            tb = r[:, 10:18]
            pc_cb = _popcount32(cb)
            pc_tb = _popcount32(tb)
            prefix = jnp.sum(jnp.where(widx8 < w, pc_cb, 0), axis=1)
            tprefix = jnp.sum(jnp.where(widx8 < w, pc_tb, 0), axis=1)
            cw = jnp.sum(jnp.where(widx8 == w, cb, 0), axis=1)
            tw = jnp.sum(jnp.where(widx8 == w, tb, 0), axis=1)
            bit = (nib & 31).astype(jnp.uint32)
            ok_t = (
                alive
                & (((tw >> bit) & 1) > 0)
                & (bit_end <= cap_bits)
            )
            win = jnp.where(
                ok_t, r[:, 1] + tprefix + _popcount32(tw & below), win
            )
            alive = alive & (((cw >> bit) & 1) > 0)
            node = jnp.where(
                alive,
                (r[:, 0] + prefix + _popcount32(cw & below)).astype(jnp.int32),
                0,
            )
    return in0, rows0, win


def trie_walk(
    trie_levels, trie_targets: jax.Array, root_lut: jax.Array,
    batch: DeviceBatch
) -> jax.Array:
    """Poptrie walk (build_poptrie layout): the DIR-16 root level is one
    direct-indexed slot-row gather; every deeper level is ONE (18-word)
    node-row gather + bitmap-rank arithmetic — the child id is
    child_base + rank(nib) (implicit numbering, no pointer gather), and
    target hits record only a global index into ``trie_targets``,
    resolved with a single gather AFTER the walk (stage
    ``lpm_resolve``).  Statically unrolled over the level count; no
    data-dependent control flow.  Returns the target index or -1.

    vs the previous slot-array walk: each deep level's gather now lands
    in an array ~30x smaller (nodes, not nodes x 256 slots), which is
    what the gather-bound walk's throughput follows; the rank math is
    ~60 cheap VPU ops per level.

    Targets at a level cover prefixes with mask_len in (prev_boundary,
    boundary]; the IPv4 packet-side cap (entries longer than /32 cannot
    match a v4 packet, kernel.c:207) is the boundary test
    ``bit_end <= cap_bits`` — boundaries are 16, 24, 32, 40, ... so 32
    always lands exactly on one."""
    in0, rows0, win = _poptrie_descend(trie_levels, root_lut, batch)
    with jax.named_scope("lpm_resolve"):
        win = win.astype(jnp.int32)
        best0 = jnp.where(in0 & (rows0[:, 1] > 0), rows0[:, 1] - 1, -1)
        in_w = (win >= 0) & (win < trie_targets.shape[0])
        tval = jnp.take(trie_targets, win, mode="clip")
        return jnp.where(in_w & (tval > 0), tval - 1, best0)


def lpm_trie(tables: DeviceTables, batch: DeviceBatch) -> jax.Array:
    return trie_walk(
        tables.trie_levels, tables.trie_targets, tables.root_lut, batch
    )


def trie_walk_joined(
    trie_levels, joined: jax.Array, root_lut: jax.Array, batch: DeviceBatch
) -> jax.Array:
    """The poptrie walk with the joined-targets tail: identical level
    loop to trie_walk, but the win is a POSITION that indexes ``joined``
    directly (level-0's target column was rewritten to appended joined
    rows by build_joined), so target resolve + rules fetch collapse into
    ONE fat-row gather.  Returns the (B, W) joined rows; row 0 / invalid
    lanes read all-zero (-> ruleId 0 -> UNDEF)."""
    in0, rows0, win = _poptrie_descend(trie_levels, root_lut, batch)
    with jax.named_scope("lpm_resolve"):
        win = win.astype(jnp.int32)
        best0 = jnp.where(in0 & (rows0[:, 1] > 0), rows0[:, 1], 0)
        pos = jnp.where(win > 0, win, best0)
        in_p = (pos > 0) & (pos < joined.shape[0])
        rows = jnp.take(joined, jnp.clip(pos, 0, joined.shape[0] - 1), axis=0,
                        mode="clip")
        return jnp.where(in_p[:, None], rows, 0)


def joined_rule_rows(rows: jax.Array) -> jax.Array:
    """(B, W) joined rows -> (B, R, C) scan operand."""
    if rows.dtype == jnp.uint16:
        return rows[:, 3:].reshape(rows.shape[0], -1, 5)
    return rows[:, 2:].reshape(rows.shape[0], -1, 7)


def rule_scan(rows: jax.Array, batch: DeviceBatch) -> jax.Array:
    """Vectorized ordered first-match scan (kernel.c:222-258).

    rows: (B, R, 5) uint16 packed (pack_rules_u16 — the resident form
    for in-range tables, halving the gather bytes that dominate this
    path) or (B, R, 7) int32 — already gathered (zeroed for
    no-LPM-match packets, which then yield ruleId==0 everywhere ->
    UNDEF).

    Perf note (the single biggest lever on this path): the first-match
    select is a min-index + masked-sum, NOT take_along_axis.  On TPU the
    composed classify with a take_along_axis select runs at ~34 M pkts/s
    at 100K CIDRs; the gather-free formulation of the exact same scan
    runs at ~311 M/s (measured on v5e, 628K-packet shard) — XLA fuses
    the masked reduction into the hit computation, while the per-lane
    gather forces a separate materialize-and-gather pass.  The scan also
    runs in (R, B) orientation so packets ride the 128-wide vector lanes;
    the transpose folds into the preceding rules gather."""
    if rows.shape[-1] == 5:
        s = jnp.transpose(rows.astype(jnp.int32), (2, 1, 0))  # (5, R, B)
        rid = s[0] & 0xFF
        act = s[0] >> 8
        rproto = s[1] & 0xFF
        it = s[1] >> 8
        ic = s[2]
        ps = s[3]
        pe = s[4]
    else:
        s = jnp.transpose(rows, (2, 1, 0))  # (7, R, B): field, rule, packet
        rid, rproto, ps, pe, it, ic, act = (s[i] for i in range(7))

    proto = batch.proto[None, :]
    dport = batch.dst_port[None, :]
    valid = rid != 0
    proto_eq = (rproto != 0) & (rproto == proto)
    is_transport = (
        (rproto == IPPROTO_TCP) | (rproto == IPPROTO_UDP) | (rproto == IPPROTO_SCTP)
    )
    port_hit = jnp.where(
        pe == 0, dport == ps, (dport >= ps) & (dport < pe)
    )
    fam = jnp.where(batch.kind == KIND_IPV4, IPPROTO_ICMP, IPPROTO_ICMPV6)[None, :]
    icmp_hit = (
        (rproto == fam)
        & (it == batch.icmp_type[None, :])
        & (ic == batch.icmp_code[None, :])
    )
    catch_all = rproto == 0
    hit = valid & ((proto_eq & ((is_transport & port_hit) | icmp_hit)) | catch_all)

    R = rid.shape[0]
    idx = jnp.arange(R, dtype=jnp.int32)[:, None]
    first = jnp.min(jnp.where(hit, idx, R), axis=0)
    any_hit = first < R
    sel = hit & (idx == first[None, :])
    rid_f = jnp.sum(jnp.where(sel, rid, 0), axis=0)
    act_f = jnp.sum(jnp.where(sel, act, 0), axis=0)
    result = jnp.where(
        any_hit,
        ((rid_f.astype(jnp.uint32) & 0xFFFFFF) << 8) | (act_f.astype(jnp.uint32) & 0xFF),
        0,
    )
    return result.astype(jnp.uint32)


def result_stats(result: jax.Array, batch: DeviceBatch) -> jax.Array:
    """(MAX_TARGETS, STATS_COLS) int32 per-batch statistics from PACKED
    results (kernel.c:361-400: allow/deny only, ruleId < MAX_TARGETS) —
    the stats half of finalize, exposed so the resident fused step can
    derive statistics from the MERGED flow-hit/stateless verdict vector
    on device (the in-program twin of daemon.stats_from_results; the
    host merge is jaxpath.merge_stats_host either way)."""
    is_ip = (batch.kind == KIND_IPV4) | (batch.kind == KIND_IPV6)
    action = (result & 0xFF).astype(jnp.int32)
    rule_id = ((result >> 8) & 0xFFFFFF).astype(jnp.int32)
    allow = (action == ALLOW) & is_ip
    deny = (action == DENY) & is_ip
    recorded = (allow | deny) & (rule_id < MAX_TARGETS)
    sid = jnp.where(recorded, rule_id, MAX_TARGETS)
    ln = batch.pkt_len
    hi = (ln >> 8) & 0xFFFFFF
    lo = ln & 0xFF
    a = allow.astype(jnp.int32)
    d = deny.astype(jnp.int32)
    data = jnp.stack([a, a * hi, a * lo, d, d * hi, d * lo], axis=1)  # (B,6)
    stats = jax.ops.segment_sum(data, sid, num_segments=MAX_TARGETS + 1)[:MAX_TARGETS]
    return stats.astype(jnp.int32)


def finalize(result: jax.Array, batch: DeviceBatch) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Ethertype/kind dispatch and stats (kernel.c:412-457, 361-400).

    Returns (results, xdp, stats) where stats is (MAX_TARGETS, STATS_COLS)
    int32 per-batch sums."""
    is_ip = (batch.kind == KIND_IPV4) | (batch.kind == KIND_IPV6)
    looked_up = is_ip & (batch.l4_ok != 0)
    result = jnp.where(looked_up, result, 0).astype(jnp.uint32)

    action = (result & 0xFF).astype(jnp.int32)

    xdp = jnp.where(
        batch.kind == KIND_MALFORMED,
        XDP_DROP,
        jnp.where(is_ip & (action == DENY), XDP_DROP, XDP_PASS),
    ).astype(jnp.int32)

    return result, xdp, result_stats(result, batch)


def gather_rule_rows(rules: jax.Array, tidx: jax.Array) -> jax.Array:
    """Per-packet rule rows for the scan: (B, R, C) from either the
    flattened 2D device layout (fast-gather form, see DeviceTables.rules)
    or a 3D (T, R, C) layout (mesh shards).  No-LPM-match packets get
    all-zero rows -> ruleId 0 everywhere -> UNDEF."""
    rows = jnp.take(rules, jnp.clip(tidx, 0), axis=0)
    if rows.ndim == 2:
        c = 5 if rows.dtype == jnp.uint16 else 7
        rows = rows.reshape(rows.shape[0], -1, c)
    return jnp.where((tidx >= 0)[:, None, None], rows, 0)


def _raw_result_and_score(
    tables: DeviceTables, batch: DeviceBatch, *, use_trie: bool
) -> Tuple[jax.Array, jax.Array]:
    """(raw scan result, LPM score) where score = mask_len + 1 of the
    winning entry (0 = no match) — the combine key for the overlay path
    (equal scores are impossible across disjoint tables: same mask_len
    matching one packet implies the same masked prefix, and identities
    are deduplicated at compile/routing time)."""
    if use_trie and tables.joined.shape[0] > 1:
        with jax.named_scope("lpm"):
            rows = trie_walk_joined(
                tables.trie_levels, tables.joined, tables.root_lut, batch
            )
            if rows.dtype == jnp.uint16:
                matched = (rows[:, 0].astype(jnp.int32)
                           | (rows[:, 1].astype(jnp.int32) << 16)) > 0
                ml = rows[:, 2].astype(jnp.int32)
            else:
                matched = rows[:, 0] > 0
                ml = rows[:, 1]
            score = jnp.where(matched, ml + 1, 0)
        with jax.named_scope("rule_scan"):
            return rule_scan(joined_rule_rows(rows), batch), score
    with jax.named_scope("lpm"):
        if use_trie:
            tidx = lpm_trie(tables, batch)
        else:
            tidx = lpm_dense(tables, batch)
        ml = jnp.take(tables.mask_len, jnp.clip(tidx, 0), mode="clip")
        score = jnp.where(tidx >= 0, ml + 1, 0)
    with jax.named_scope("rule_scan"):
        return rule_scan(gather_rule_rows(tables.rules, tidx), batch), score


def classify(
    tables: DeviceTables, batch: DeviceBatch, *, use_trie: bool
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full forward pass: LPM -> gather rules -> scan -> finalize (the
    ``lpm`` and ``rule_scan`` stages of RESIDENT_STAGES)."""
    if use_trie and tables.joined.shape[0] > 1:
        # one-gather tail: the walk's win position returns the rules row
        with jax.named_scope("lpm"):
            rows = trie_walk_joined(
                tables.trie_levels, tables.joined, tables.root_lut, batch
            )
        with jax.named_scope("rule_scan"):
            result = rule_scan(joined_rule_rows(rows), batch)
            return finalize(result, batch)
    with jax.named_scope("lpm"):
        if use_trie:
            tidx = lpm_trie(tables, batch)
        else:
            tidx = lpm_dense(tables, batch)
    with jax.named_scope("rule_scan"):
        result = rule_scan(gather_rule_rows(tables.rules, tidx), batch)
        return finalize(result, batch)


def classify_with_overlay(
    tables: DeviceTables,
    overlay: DeviceTables,
    batch: DeviceBatch,
    *,
    use_trie: bool,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Main-table classify combined with a SMALL dense overlay table —
    the structural-update fast path (the Map.Update analogue for CIDR
    ADDS, loader.go:200-218): new keys land in the overlay (a dense
    compare over <= a few hundred entries, uploaded in kilobytes) so the
    main trie's device form is untouched; the longest-prefix winner
    across both tables is selected by mask_len score.  Equal scores
    cannot occur (the router keeps identities disjoint), so strict
    greater-than gives the overlay exactly kernel-LPM semantics."""
    raw_m, score_m = _raw_result_and_score(tables, batch, use_trie=use_trie)
    raw_o, score_o = _raw_result_and_score(overlay, batch, use_trie=False)
    with jax.named_scope("rule_scan"):
        result = jnp.where(score_o > score_m, raw_o, raw_m)
        return finalize(result, batch)


@functools.lru_cache(maxsize=None)
def jitted_classify(use_trie: bool):
    """Compiled classify entry point; cache keyed on the static config
    (the trie level count is part of the DeviceTables pytree structure,
    so jit re-specializes per table depth automatically).  Always use
    this (never eager) — op-by-op dispatch is orders of magnitude slower
    than the fused XLA program."""
    return jax.jit(functools.partial(classify, use_trie=use_trie))


def merge_stats_host(stats: np.ndarray) -> np.ndarray:
    """Device (MAX_TARGETS, 6) int32 -> host (MAX_TARGETS, 4) int64
    [allow_pkts, allow_bytes, deny_pkts, deny_bytes]."""
    s = stats.astype(np.int64)
    out = np.zeros((stats.shape[0], 4), np.int64)
    out[:, 0] = s[:, 0]
    out[:, 1] = s[:, 1] * 256 + s[:, 2]
    out[:, 2] = s[:, 3]
    out[:, 3] = s[:, 4] * 256 + s[:, 5]
    return out


# === multi-tenant paged table arena ==========================================
#
# The capacity-scaling layer (ISSUE-10): thousands of tenant rulesets
# share ONE preallocated HBM pool per layout family instead of one
# DeviceTables instance each.  Every family pool is divided into
# fixed-size SLABS (pages); a tenant's compiled table is baked into its
# slab with PAGE-GLOBAL indices (child/target pointers, joined
# positions, root ids all offset by the slab base at write time), so
# the classify kernels index one flat pool and the per-packet tenant
# column steers only the ENTRY — the same way ingress_ifindex steers
# the LPM root today.  Consequences:
#
# - one classify batch carries mixed-tenant traffic (the tenant column
#   picks each packet's slab base through the device-resident
#   tenant -> page table);
# - tenant activation / hot-swap is a page-table ROW FLIP (one 1-row
#   scatter, pre-warmed like the txn ladder) instead of a full table
#   re-upload;
# - the incremental patch machinery applies PER SLAB unchanged: a
#   rules-only tenant edit is the usual joined/dense row scatter with
#   positions offset by the slab base, through the same capped/fused
#   executables (_capped_scatter / txn_scatter) the single-table path
#   warms.
#
# Two families: "dense" (compare-all slabs — also the overlay side-pool)
# and "ctrie" (the path/level-compressed poptrie, whose ONE merged node
# array is what makes slab paging natural: the descent loop
# (_ctrie_descend) is shared verbatim with the single-table walk).

#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_PAGEFLIP_BUG env var), ArenaAllocator.activate skips the
#: device page-table row flip after a tenant swap — the host-side
#: registry believes the swap landed while the device keeps serving the
#: STALE slab.  The statecheck acceptance gate (tools/infw_lint.py
#: state --inject-defect pageflip) proves the model checker catches
#: this via oracle divergence with a shrunk reproducer.  Never set in
#: production.
_INJECT_PAGEFLIP_BUG = False


def _inject_pageflip_bug() -> bool:
    if _INJECT_PAGEFLIP_BUG:
        return True
    env = os.environ.get("INFW_INJECT_PAGEFLIP_BUG", "")
    return env not in ("", "0", "false", "no")


#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_COWLEAK_BUG env var), the copy-on-write clone path of
#: ArenaAllocator.load_tenant "forgets" the donor page's refcount
#: decrement after flipping the editing tenant onto its private clone —
#: the classic CoW leak (the donor page can never drop to zero and be
#: reclaimed).  The statecheck acceptance gate (tools/infw_lint.py
#: state --inject-defect cowleak, on the shared-then-edited-biased
#: "arena-cow" config) proves check_arena's refcount/aliasing
#: invariants catch it with a shrunk reproducer.  Never set in
#: production.
_INJECT_COWLEAK_BUG = False


def _inject_cowleak_bug() -> bool:
    if _INJECT_COWLEAK_BUG:
        return True
    env = os.environ.get("INFW_INJECT_COWLEAK_BUG", "")
    return env not in ("", "0", "false", "no")


#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_SPLICELEAK_BUG env var), the unsplice path of
#: ArenaAllocator._splice_edit "forgets" the old subtree plane's
#: refcount decrement after repointing the editing tenant's splice row
#: at its private (or re-merged) plane — the subtree-granular CoW leak
#: (the shared plane can never drop to zero and be reclaimed).  The
#: statecheck acceptance gate (tools/infw_lint.py state --inject-defect
#: spliceleak, on the near-copy-biased "arena-splice" config) proves
#: check_arena's splice refcount invariants catch it with a shrunk
#: reproducer.  Never set in production.
_INJECT_SPLICELEAK_BUG = False


def _inject_spliceleak_bug() -> bool:
    if _INJECT_SPLICELEAK_BUG:
        return True
    env = os.environ.get("INFW_INJECT_SPLICELEAK_BUG", "")
    return env not in ("", "0", "false", "no")


#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_COWRACE_BUG env var), ArenaAllocator._cow_install defers
#: the CoW donor's refcount decrement past the allocator lock release —
#: load_tenant then lands it as an UNLOCKED read-modify-write (with an
#: explicit sched_point in the window), so a concurrently locked
#: decrement (destroy_tenant / dedup_sweep) can interleave between the
#: read and the write-back and be lost.  The schedcheck acceptance gate
#: (tools/infw_lint.py sched --inject-defect cowrace) proves the
#: deterministic interleaving explorer finds the race, ddmin-shrinks
#: the schedule, and check_arena's cowleak refcount invariant names the
#: stale page.  Never set in production.
_INJECT_COWRACE_BUG = False


def _inject_cowrace_bug() -> bool:
    if _INJECT_COWRACE_BUG:
        return True
    env = os.environ.get("INFW_INJECT_COWRACE_BUG", "")
    return env not in ("", "0", "false", "no")


#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_CLAMPGATHER_BUG env var), arena_ctrie_rows skips the
#: ``& _SPLICE_PAGE_MASK`` decode of spliced page-table rows — the
#: bank bit (bit 30) leaks into the page id, so ``pg0 * SL + ifindex``
#: indexes the root lut out of bounds for any spliced tenant.  The
#: static bounds verifier's acceptance gate (tools/infw_lint.py bounds
#: --inject-defect clampgather) proves abstract interpretation flags
#: the unclamped gather and concretizes a diverging boundary witness.
#: TRACE-time flag: must be set before the entrypoint is first traced
#: (the acceptance gate runs it in a fresh process).  Never set in
#: production.
_INJECT_CLAMPGATHER_BUG = False


def _inject_clampgather_bug() -> bool:
    if _INJECT_CLAMPGATHER_BUG:
        return True
    env = os.environ.get("INFW_INJECT_CLAMPGATHER_BUG", "")
    return env not in ("", "0", "false", "no")


class ArenaCapacityError(ValueError):
    """A tenant table does not fit the arena's slab geometry (entries,
    node rows, trie depth, rule width, lut span) or the pool is out of
    free pages.  Callers either re-size the arena (a new pool
    generation) or refuse the tenant — never silently truncate."""


class _PlaneCapacityError(ArenaCapacityError):
    """The subtree plane pool is exhausted.  Internal: the decomposed
    install/stage paths catch it and fall back to the whole-slab path
    (degrade to flat slabs, never refuse the tenant); page exhaustion
    keeps raising plain ArenaCapacityError."""


#: Splice-indirect walk encoding (ISSUE-17).  A spliced l0 slot stores
#: SPLICE_TAG + slot_id in column 0 (instead of root-node-id + 1): the
#: walk entry resolves the slot through the tenant's splice-table rows
#: to a shared plane id, and descends from that plane's root row in the
#: appended plane pool region.  The tag bit doubles as the page-table
#: BANK bit: a spliced arena's page-table rows encode
#: ``page | bank << 30`` so one 1-row flip switches a tenant's page AND
#: its (double-buffered) splice-table bank atomically.
SPLICE_TAG = np.int32(1 << 30)
_SPLICE_BANK_SHIFT = 30
_SPLICE_PAGE_MASK = (1 << 30) - 1


class ArenaSpec(NamedTuple):
    """Geometry of one paged arena (a layout family's pool).  All row
    counts are PER SLAB; device pools are ``pages`` slabs, flat along
    rows.  Constructed via make_arena_spec (which buckets/validates) —
    the raw constructor is for tests."""

    family: str        # "dense" | "ctrie"
    pages: int
    max_tenants: int
    entries: int       # dense-entry capacity per slab (T)
    rule_slots: int    # packed rules per entry (row width = rule_slots*5)
    lut_rows: int      # root_lut rows per slab (max ifindex + 1 bound)
    root_nodes: int    # ctrie DIR-16 root nodes per slab (R0)
    node_rows: int     # ctrie merged skip-node rows per slab (SN)
    target_rows: int   # ctrie flat target rows per slab (ST)
    d_max: int         # static descent unroll bound (pool-wide)
    # -- structural (subtree-splice) compression geometry (ISSUE-17) ---------
    # zero everywhere -> a plain (unspliced) arena; ctrie-only.  The
    # plane pool is APPENDED to the slab pools (rows pages*SN .. on),
    # so every resident index stays pool-global and the walk kernels
    # never branch on "slab vs plane".
    plane_slots: int = 0        # shared subtree planes in the pool (PP)
    plane_node_rows: int = 0    # skip-node rows per plane (SNP)
    plane_target_rows: int = 0  # target rows per plane (STP)
    plane_joined_rows: int = 0  # joined rows per plane (SJP, row 0 unused)
    splice_slots: int = 0       # splice-table rows per tenant slab (K)

    @property
    def joined_rows(self) -> int:
        """Per-slab joined rows: tidx+1 indexing plus the slab's own
        zero sentinel row."""
        return self.entries + 1

    @property
    def l0_rows(self) -> int:
        return self.root_nodes * 65536

    @property
    def spliced(self) -> bool:
        """True when the arena factors shared subtrees into the
        refcounted plane pool and reads them through the per-tenant
        splice table."""
        return self.plane_slots > 0 and self.splice_slots > 0

    @property
    def splice_rows(self) -> int:
        """Device splice-table rows: two banks (double-buffered per
        tenant so a splice-map update lands atomically with the
        page-table flip) of max_tenants * splice_slots."""
        if not self.spliced:
            return 1  # degenerate placeholder array
        return 2 * self.max_tenants * self.splice_slots


def make_arena_spec(
    family: str,
    pages: int,
    max_tenants: int,
    entries: int,
    rule_slots: int,
    lut_rows: int = 8,
    root_nodes: int = 1,
    node_rows: int = 128,
    target_rows: int = 64,
    d_max: int = 6,
    plane_slots: int = 0,
    plane_node_rows: int = 0,
    plane_target_rows: int = 0,
    plane_joined_rows: int = 0,
    splice_slots: int = 0,
) -> ArenaSpec:
    """Normalize + validate an arena geometry: row counts bucket to the
    shared scatter-ladder shapes (node rows additionally to 128-row
    tiles for the Pallas byte planes), and the pool must satisfy the
    capped-scatter budget (a full-slab write is <= pool/4 rows, i.e.
    pages >= 4) and the int32 DIR-16 indexing bound.  Non-zero splice
    geometry (ctrie-only) appends a ``plane_slots``-deep shared subtree
    plane pool and a two-bank per-tenant splice table."""
    if family not in ("dense", "ctrie"):
        raise ValueError(f"unknown arena family {family!r}")
    if pages < 4:
        raise ValueError(
            f"arena needs >= 4 pages (full-slab writes ride the capped "
            f"scatter budget of pool/4 rows); got {pages}"
        )
    if max_tenants < 1 or entries < 1 or rule_slots < 1:
        raise ValueError("max_tenants, entries and rule_slots must be >= 1")
    entries = _row_bucket(entries)
    lut_rows = _row_bucket(lut_rows)
    target_rows = _row_bucket(target_rows)
    node_rows = -(-max(node_rows, 128) // 128) * 128
    if family == "ctrie" and pages * root_nodes * 65536 > np.iinfo(np.int32).max:
        raise ValueError(
            f"arena l0 pool {pages}x{root_nodes} root nodes exceeds int32 "
            "DIR-16 indexing"
        )
    splicey = (plane_slots, plane_node_rows, plane_target_rows,
               plane_joined_rows, splice_slots)
    if any(v < 0 for v in splicey):
        raise ValueError("splice geometry fields must be >= 0")
    if any(splicey):
        if family != "ctrie":
            raise ValueError("subtree-splice compression is ctrie-only")
        if not all(splicey):
            raise ValueError(
                "splice geometry is all-or-nothing: plane_slots, "
                "plane_node_rows, plane_target_rows, plane_joined_rows "
                "and splice_slots must all be > 0"
            )
        # plane rows bucket to small multiples of 8 (they ride the same
        # warmed fused scatter; no 128-row tiling needed — the Pallas
        # byte planes pad the POOL TOTAL to 128 rows internally)
        r8 = lambda x: -(-int(x) // 8) * 8
        plane_node_rows = r8(plane_node_rows)
        plane_target_rows = r8(plane_target_rows)
        plane_joined_rows = r8(plane_joined_rows)
        total_nodes = pages * node_rows + plane_slots * plane_node_rows
        if total_nodes + 1 >= int(SPLICE_TAG):
            raise ValueError(
                f"node pool {total_nodes} rows collides with the splice "
                f"tag space (< {int(SPLICE_TAG)})"
            )
        if splice_slots >= int(SPLICE_TAG):
            raise ValueError("splice_slots exceeds the splice tag space")
    return ArenaSpec(
        family=family, pages=pages, max_tenants=max_tenants,
        entries=entries, rule_slots=rule_slots, lut_rows=lut_rows,
        root_nodes=root_nodes, node_rows=node_rows,
        target_rows=target_rows, d_max=d_max,
        plane_slots=plane_slots, plane_node_rows=plane_node_rows,
        plane_target_rows=plane_target_rows,
        plane_joined_rows=plane_joined_rows, splice_slots=splice_slots,
    )


def arena_spec_for(
    family: str,
    tables_iter,
    pages: int,
    max_tenants: int,
    headroom: float = 1.0,
    d_max: Optional[int] = None,
    **splice_kwargs,
) -> ArenaSpec:
    """Size an ArenaSpec from sample tenant tables: take per-family
    maxima over the samples, scaled by ``headroom``, then bucket via
    make_arena_spec.  The samples must be u16-packable (the arena's
    resident rule layout).  ``splice_kwargs`` (plane_slots,
    plane_node_rows, ...) pass through to make_arena_spec for spliced
    geometries."""
    ent = 1
    rs = 1
    lut = 1
    r0 = 1
    nn = 1
    tt = 1
    dm = 1
    for t in tables_iter:
        rules = _packed_rules_flat(t)
        if rules.dtype != np.uint16:
            raise ArenaCapacityError(
                "arena slabs hold u16-packed rules; a sample table has "
                "wide int32 values"
            )
        ent = max(ent, t.rules.shape[0])
        rs = max(rs, rules.shape[1] // 5)
        lut = max(lut, np.asarray(t.root_lut).shape[0])
        if family == "ctrie":
            l0, nodes, targets, d = build_cpoptrie(t)
            r0 = max(r0, l0.shape[0] // 65536)
            nn = max(nn, nodes.shape[0])
            tt = max(tt, targets.shape[0])
            dm = max(dm, d)
    h = lambda x: int(-(-x * headroom // 1))
    return make_arena_spec(
        family, pages, max_tenants,
        entries=h(ent), rule_slots=rs, lut_rows=h(lut), root_nodes=r0,
        node_rows=h(nn), target_rows=h(tt),
        d_max=d_max if d_max is not None else dm,
        **splice_kwargs,
    )


class DenseArena(NamedTuple):
    """Dense-family device pool: ``pages`` compare-all slabs flat along
    rows, plus the tenant -> page table.  Unassigned rows carry the
    mask_len == -1 sentinel (inert exactly like single-table padding);
    page_table rows are -1 for absent tenants."""

    key_words: jax.Array   # (P*S, 5) uint32
    mask_words: jax.Array  # (P*S, 5) uint32
    mask_len: jax.Array    # (P*S,) int32
    rules: jax.Array       # (P*S, R*5) uint16
    page_table: jax.Array  # (max_tenants,) int32


class CtrieArena(NamedTuple):
    """Ctrie-family device pool: per-slab compressed-poptrie layouts
    with PAGE-GLOBAL indices baked at slab-write time (node ids, target
    positions, joined positions, root ids), so the shared descent
    (_ctrie_descend) and the tail gathers run on the flat pools
    untouched.  Pool row 0 of ``targets``/``joined`` doubles as the
    global sentinel (page 0's slab sentinel — all slabs keep their
    local row 0 zero).

    Spliced geometries (spec.spliced) APPEND the shared subtree plane
    pool to ``nodes``/``targets``/``joined`` (plane_slots slabs of
    plane_*_rows each, starting at row pages*SN / pages*ST / pages*SJ)
    and carry the two-bank per-tenant ``splice`` table: row
    (bank*max_tenants + tenant)*K + slot holds the plane id serving
    that tenant's spliced l0 slot (-1 = unused).  Plane-internal
    indices are baked pool-global exactly like slab indices, so the
    descent and tail gathers stay splice-oblivious; only the l0 entry
    resolves through the indirection."""

    l0: jax.Array          # (P*R0*65536, 2) int32
    nodes: jax.Array       # (P*SN [+ PP*SNP], 20) uint32
    targets: jax.Array     # (P*ST [+ PP*STP],) int32 global joined positions
    joined: jax.Array      # (P*(S+1) [+ PP*SJP], 3+R*5) uint16
    root_lut: jax.Array    # (P*SL,) int32 global root ids
    splice: jax.Array      # (2*max_tenants*K,) int32 plane ids, -1 unused
    page_table: jax.Array  # (max_tenants,) int32 (spliced: page|bank<<30)


# -- slab baking (host) ------------------------------------------------------


def _dense_slab_arrays(spec: ArenaSpec, tables: CompiledTables):
    """Full-slab host arrays for the dense family (page-offset-free:
    dense slabs carry no cross-row indices).  Raises ArenaCapacityError
    when the table exceeds the slab geometry."""
    kw, mw, ml, rules, _lv, _tg, _lut, _j = _host_device_layout(
        tables, pad=False, with_trie=False
    )
    S = spec.entries
    if kw.shape[0] > S:
        raise ArenaCapacityError(
            f"tenant has {kw.shape[0]} entries > slab capacity {S}"
        )
    if rules.dtype != np.uint16:
        raise ArenaCapacityError("arena slabs hold u16-packed rules")
    if rules.shape[1] != spec.rule_slots * 5:
        raise ArenaCapacityError(
            f"rule row width {rules.shape[1]} != slab width "
            f"{spec.rule_slots * 5} (compile tenants with rule_width="
            f"{spec.rule_slots})"
        )
    return (
        _pad_rows(kw, S),
        _pad_rows(mw, S),
        _pad_rows(ml, S, fill=-1),
        _pad_rows(rules, S),
    )


def _ctrie_canonical_slab(spec: ArenaSpec, tables: CompiledTables):
    """Page-independent ("canonical") full-slab host arrays for the
    ctrie family: slab-local indices, zero padding — the form the
    content hash is computed over (identical rulesets bake to identical
    bytes regardless of which physical page they land on).  Returns
    (arrays, n_nodes); ``n_nodes`` is the real skip-node row count,
    needed because node-row offsets apply unconditionally to real rows
    (offsetting/un-offsetting is row-count-dependent).  Raises
    ArenaCapacityError when any per-slab bound is exceeded."""
    host = _ctrie_host_layout(tables)
    if host is None:
        raise ArenaCapacityError(
            "tenant table is not ctrie-eligible (wide int32 rules)"
        )
    (l0, nodes, targets, joined, root_lut), d_max = host
    if d_max > spec.d_max:
        raise ArenaCapacityError(
            f"tenant trie depth d_max={d_max} > arena unroll bound "
            f"{spec.d_max}"
        )
    n0 = l0.shape[0] // 65536
    if n0 > spec.root_nodes:
        raise ArenaCapacityError(
            f"{n0} root nodes > slab bound {spec.root_nodes}"
        )
    if nodes.shape[0] > spec.node_rows:
        raise ArenaCapacityError(
            f"{nodes.shape[0]} skip nodes > slab bound {spec.node_rows}"
        )
    if targets.shape[0] > spec.target_rows:
        raise ArenaCapacityError(
            f"{targets.shape[0]} targets > slab bound {spec.target_rows}"
        )
    if joined.shape[0] > spec.joined_rows:
        raise ArenaCapacityError(
            f"{joined.shape[0]} joined rows > slab bound "
            f"{spec.joined_rows}"
        )
    if joined.shape[1] != 3 + spec.rule_slots * 5:
        raise ArenaCapacityError(
            f"joined row width {joined.shape[1]} != slab width "
            f"{3 + spec.rule_slots * 5}"
        )
    if root_lut.shape[0] > spec.lut_rows:
        raise ArenaCapacityError(
            f"root_lut spans {root_lut.shape[0]} ifindexes > slab bound "
            f"{spec.lut_rows}"
        )
    l0b = np.zeros((spec.l0_rows, 2), np.int32)
    l0b[: l0.shape[0]] = l0
    nodesb = np.zeros((spec.node_rows, 20), np.uint32)
    nodesb[: nodes.shape[0]] = nodes.astype(np.uint32)
    tgtb = np.zeros(spec.target_rows, np.int32)
    tgtb[: targets.shape[0]] = targets.astype(np.int32)
    joinb = np.zeros((spec.joined_rows, joined.shape[1]), np.uint16)
    joinb[: joined.shape[0]] = joined
    lutb = np.zeros(spec.lut_rows, np.int32)
    lutb[: root_lut.shape[0]] = root_lut.astype(np.int32)
    return (l0b, nodesb, tgtb, joinb, lutb), int(nodes.shape[0])


def _offset_ctrie_slab(spec: ArenaSpec, arrays, n_nodes: int, page: int):
    """Canonical ctrie slab arrays -> the page's resident form: node
    ids += page*SN, target positions += page*ST, joined positions +=
    page*SJ, root ids += page*R0 (zero entries stay zero; real node
    rows offset unconditionally — hence ``n_nodes``).  Never mutates
    the canonical arrays."""
    l0, nodes, targets, joined, root_lut = arrays
    if page == 0:
        return l0, nodes, targets, joined, root_lut
    nb = page * spec.node_rows
    tb = page * spec.target_rows
    jb = page * spec.joined_rows
    rb = page * spec.root_nodes
    l0o = np.zeros_like(l0)
    # spliced l0 slots (SPLICE_TAG + slot) are slab-local slot ids
    # resolved through the tenant splice table — never page-offset
    tag = l0[:, 0] >= SPLICE_TAG
    l0o[:, 0] = np.where(
        tag, l0[:, 0], np.where(l0[:, 0] > 0, l0[:, 0] + nb, 0)
    )
    l0o[:, 1] = np.where(l0[:, 1] > 0, l0[:, 1] + jb, 0)
    nodeso = nodes.copy()
    nodeso[:n_nodes, 0] += np.uint32(nb)
    nodeso[:n_nodes, 1] += np.uint32(tb)
    tgto = np.where(targets > 0, targets + jb, 0).astype(np.int32)
    luto = (root_lut.astype(np.int64) + rb).astype(np.int32)
    return l0o, nodeso, tgto, joined, luto


def _unoffset_ctrie_slab(spec: ArenaSpec, arrays, n_nodes: int, page: int):
    """Inverse of _offset_ctrie_slab: a page's resident slab rows back
    to the canonical (page-independent) form — what the content hash
    and the CoW clone read from the host mirror."""
    l0, nodes, targets, joined, root_lut = arrays
    if page == 0:
        return l0, nodes, targets, joined, root_lut
    nb = page * spec.node_rows
    tb = page * spec.target_rows
    jb = page * spec.joined_rows
    rb = page * spec.root_nodes
    l0c = np.zeros_like(l0)
    tag = l0[:, 0] >= SPLICE_TAG
    l0c[:, 0] = np.where(
        tag, l0[:, 0], np.where(l0[:, 0] > 0, l0[:, 0] - nb, 0)
    )
    l0c[:, 1] = np.where(l0[:, 1] > 0, l0[:, 1] - jb, 0)
    nodesc = nodes.copy()
    nodesc[:n_nodes, 0] -= np.uint32(nb)
    nodesc[:n_nodes, 1] -= np.uint32(tb)
    tgtc = np.where(targets > 0, targets - jb, 0).astype(np.int32)
    lutc = (root_lut.astype(np.int64) - rb).astype(np.int32)
    return l0c, nodesc, tgtc, joined, lutc


def _ctrie_slab_arrays(spec: ArenaSpec, page: int, tables: CompiledTables):
    """Full-slab host arrays for the ctrie family with the page's
    GLOBAL offsets baked in (the canonical bake + the page offset pass).
    Raises ArenaCapacityError when any per-slab bound is exceeded."""
    arrays, n_nodes = _ctrie_canonical_slab(spec, tables)
    return _offset_ctrie_slab(spec, arrays, n_nodes, page)


def slab_content_hash(arrays, n_nodes: int = 0) -> bytes:
    """Canonical content hash of one baked slab: sha256 over the
    page-independent slab arrays' bytes (shape/dtype-framed) plus the
    real node-row count.  Hashing the BAKED arrays (not the spec) means
    two rulesets that compile to the same forwarding state dedup even
    when their specs differ cosmetically."""
    h = hashlib.sha256()
    h.update(str(int(n_nodes)).encode())
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(repr((a.shape, a.dtype.str)).encode())
        h.update(a.tobytes())
    return h.digest()


# -- structural (subtree) decomposition (host) --------------------------------


def _np_popcount_rows(bitmaps: np.ndarray) -> np.ndarray:
    """(n, 8) uint32 bitmap rows -> (n,) per-row set-bit counts."""
    b = np.ascontiguousarray(bitmaps.astype(np.uint32)).view(np.uint8)
    return np.unpackbits(b, axis=-1).sum(axis=1).astype(np.int64)


class _SpliceSub(NamedTuple):
    """One factored subtree of a decomposed ctrie slab: the mapping
    between the subtree's canonical PLANE form (plane-local indices,
    content-canonical bytes shared across tenants) and its footprint in
    the tenant's whole-slab canonical form.  ``node_rows``/``tpos`` are
    the ORIGINAL (ascending) slab row positions the subtree occupied;
    plane-local row i is node_rows[i] / tpos[i] (BFS emission order is
    monotone in node id, so the sorted restriction IS the subtree's own
    BFS order).  ``tidx`` is the sorted list of tidx+1 joined positions
    the subtree owns; plane-local joined row 1+j carries the original
    row bytes of tidx[j] (the self-indexed bytes are identical across
    rules-only-variant tenants, which is what makes planes shareable).
    ``dead_cb``/``dead_tb`` preserve the original base values of rows
    with zero children/targets (dead pointers are never descended but
    must round-trip bit-exactly for the whole-slab hash invariant)."""

    slot: int               # splice slot id (l0 slot order)
    e: int                  # l0 row of the subtree root's DIR-16 slot
    root: int               # original root node id
    node_rows: np.ndarray   # (n_local,) int64 ascending original node ids
    dead_cb: np.ndarray     # (n_local,) int64, -1 where child_base live
    dead_tb: np.ndarray     # (n_local,) int64, -1 where target_base live
    tpos: np.ndarray        # (n_t,) int64 ascending original target rows
    tidx: np.ndarray        # (n_j,) int64 ascending original tidx+1 values
    n_local: int            # real plane node rows
    plane: tuple            # (pnodes, ptargets, pjoined) canonical arrays
    phash: bytes            # slab_content_hash(plane, n_local)


def _decompose_one_subtree(spec, arrays, n_nodes, cc, tc, e, slot,
                           claimed_nodes, claimed_tidx, best0_tidx):
    """Try to factor the subtree rooted at l0 row ``e`` into one plane.
    Returns a _SpliceSub or None (doesn't fit the plane geometry, or
    overlaps an already-claimed/trunk-owned row — the subtree then
    stays resident in the trunk slab)."""
    l0, nodes, targets, joined, _root_lut = arrays
    snp = spec.plane_node_rows
    root = int(l0[e, 0]) - 1
    if root < 0 or root >= n_nodes:
        return None
    cb = nodes[:n_nodes, 0].astype(np.int64)
    tb = nodes[:n_nodes, 1].astype(np.int64)
    # BFS-collect the subtree's node rows (bounded by the plane size)
    rows: list = []
    seen: set = set()
    frontier = [root]
    while frontier:
        nxt: list = []
        for nid in frontier:
            if nid in seen or len(rows) >= snp:
                return None
            seen.add(nid)
            rows.append(nid)
            c = int(cc[nid])
            if c:
                b = int(cb[nid])
                if b < 0 or b + c > n_nodes:
                    return None
                nxt.extend(range(b, b + c))
        frontier = nxt
    if not rows:
        return None
    nr = np.array(sorted(rows), np.int64)
    if claimed_nodes[nr].any():
        return None
    # target rows owned by the subtree (contiguous per node)
    tl: list = []
    for nid in nr:
        t = int(tc[nid])
        if t:
            b = int(tb[nid])
            if b < 1 or b + t > targets.shape[0]:
                return None
            tl.extend(range(b, b + t))
    if len(tl) != len(set(tl)) or len(tl) > spec.plane_target_rows:
        return None
    tpos = np.array(sorted(tl), np.int64)
    tvals = targets[tpos].astype(np.int64) if len(tpos) else np.zeros(0, np.int64)
    live = tvals[tvals > 0]
    if len(set(live.tolist())) != len(live):
        return None
    tidx = np.unique(live)
    if len(tidx) + 1 > spec.plane_joined_rows:
        return None
    if len(tidx) and int(tidx.max()) >= joined.shape[0]:
        return None
    for v in tidx.tolist():
        if v in claimed_tidx or v in best0_tidx:
            return None
    # bake the canonical plane (plane-local indices)
    n_local = len(nr)
    pn = np.zeros((snp, 20), np.uint32)
    pn[:n_local] = nodes[nr]
    dead_cb = np.full(n_local, -1, np.int64)
    dead_tb = np.full(n_local, -1, np.int64)
    pos_of_node = {int(v): i for i, v in enumerate(nr)}
    pos_of_t = {int(v): i for i, v in enumerate(tpos)}
    for i, nid in enumerate(nr.tolist()):
        if int(cc[nid]):
            lb = pos_of_node.get(int(cb[nid]))
            if lb is None:
                return None
            pn[i, 0] = np.uint32(lb)
        else:
            dead_cb[i] = int(cb[nid])
            pn[i, 0] = 0
        if int(tc[nid]):
            lt = pos_of_t.get(int(tb[nid]))
            if lt is None:
                return None
            pn[i, 1] = np.uint32(lt)
        else:
            dead_tb[i] = int(tb[nid])
            pn[i, 1] = 0
    pt = np.zeros(spec.plane_target_rows, np.int32)
    for j, v in enumerate(tvals.tolist()):
        pt[j] = 0 if v <= 0 else 1 + int(np.searchsorted(tidx, v))
    pj = np.zeros((spec.plane_joined_rows, joined.shape[1]), np.uint16)
    for j, v in enumerate(tidx.tolist()):
        pj[1 + j] = joined[v]
    plane = (pn, pt, pj)
    return _SpliceSub(
        slot=slot, e=int(e), root=root, node_rows=nr, dead_cb=dead_cb,
        dead_tb=dead_tb, tpos=tpos, tidx=tidx, n_local=n_local,
        plane=plane, phash=slab_content_hash(plane, n_local),
    )


def _decompose_ctrie_slab(spec: ArenaSpec, arrays, n_nodes: int):
    """Factor a canonical ctrie slab into (trunk arrays, subtree metas):
    each factorable l0 subtree (fits the plane geometry, disjoint from
    every other factored subtree, owns none of the <=16-bit best0
    joined rows) moves to a canonical plane; its l0 slot becomes
    SPLICE_TAG + slot and its node/target/joined rows ZERO in the
    trunk (no renumbering — trunk bytes stay content-canonical, and
    structurally-identical tenants produce bit-identical trunks).
    Returns None when nothing factors (caller installs whole-slab)."""
    if not spec.spliced or n_nodes <= 0:
        return None
    l0, nodes, targets, joined, root_lut = arrays
    cc = _np_popcount_rows(nodes[:n_nodes, 4:12])
    tc = _np_popcount_rows(nodes[:n_nodes, 12:20])
    best0 = l0[:, 1]
    best0_tidx = set(int(v) for v in best0[best0 > 0].tolist())
    claimed_nodes = np.zeros(n_nodes, bool)
    claimed_tidx: set = set()
    metas: list = []
    for e in np.nonzero(l0[:, 0] > 0)[0].tolist():
        if len(metas) >= spec.splice_slots:
            break
        m = _decompose_one_subtree(
            spec, arrays, n_nodes, cc, tc, e, len(metas),
            claimed_nodes, claimed_tidx, best0_tidx,
        )
        if m is None:
            continue
        claimed_nodes[m.node_rows] = True
        claimed_tidx.update(m.tidx.tolist())
        metas.append(m)
    if not metas:
        return None
    tl0 = l0.copy()
    tn = nodes.copy()
    tt = targets.copy()
    tj = joined.copy()
    for m in metas:
        tl0[m.e, 0] = np.int32(int(SPLICE_TAG) + m.slot)
        tn[m.node_rows] = 0
        tt[m.tpos] = 0
        tj[m.tidx] = 0
    return (tl0, tn, tt, tj, root_lut.copy()), tuple(metas)


def _recompose_ctrie_slab(spec: ArenaSpec, trunk_arrays, metas, planes):
    """Inverse of _decompose_ctrie_slab: trunk + canonical planes back
    to the tenant's whole-slab canonical arrays — the invariant teeth
    of check_arena (residual slab + spliced planes must reproduce the
    whole-slab canonical bytes/hash bit-exactly).  ``planes`` aligns
    with ``metas``: (pnodes, ptargets, pjoined, n_local) each."""
    l0, nodes, targets, joined, root_lut = (
        np.array(a, copy=True) for a in trunk_arrays
    )
    for m, (pn, pt, pj, n_local) in zip(metas, planes):
        l0[m.e, 0] = np.int32(m.root + 1)
        out = np.array(pn[:n_local], copy=True)
        ccp = _np_popcount_rows(out[:, 4:12])
        tcp = _np_popcount_rows(out[:, 12:20])
        local_cb = np.clip(out[:, 0].astype(np.int64), 0, max(n_local - 1, 0))
        glob_cb = np.where(ccp > 0, m.node_rows[local_cb], m.dead_cb)
        if len(m.tpos):
            local_tb = np.clip(
                out[:, 1].astype(np.int64), 0, len(m.tpos) - 1
            )
            glob_tb = np.where(tcp > 0, m.tpos[local_tb], m.dead_tb)
        else:
            glob_tb = m.dead_tb
        out[:, 0] = glob_cb.astype(np.uint32)
        out[:, 1] = glob_tb.astype(np.uint32)
        nodes[m.node_rows] = out
        for j, p in enumerate(m.tpos.tolist()):
            v = int(pt[j])
            targets[p] = 0 if v <= 0 else np.int32(m.tidx[v - 1])
        for j, v in enumerate(m.tidx.tolist()):
            joined[v] = pj[1 + j]
    return l0, nodes, targets, joined, root_lut


def _offset_plane_slab(spec: ArenaSpec, plane_arrays, n_local: int, ps: int):
    """Canonical plane arrays -> the plane slot's resident (pool-
    global) form: node rows += plane-pool base + ps*SNP, target bases
    += target base + ps*STP, target values += joined base + ps*SJP —
    after which the shared descent walks the plane exactly like slab
    rows.  Never mutates the canonical arrays."""
    pn, pt, pj = plane_arrays
    nb = spec.pages * spec.node_rows + ps * spec.plane_node_rows
    tb = spec.pages * spec.target_rows + ps * spec.plane_target_rows
    jb = spec.pages * spec.joined_rows + ps * spec.plane_joined_rows
    pno = pn.copy()
    pno[:n_local, 0] += np.uint32(nb)
    pno[:n_local, 1] += np.uint32(tb)
    pto = np.where(pt > 0, pt + jb, 0).astype(np.int32)
    return pno, pto, pj


def _unoffset_plane_slab(spec: ArenaSpec, plane_arrays, n_local: int,
                         ps: int):
    """Inverse of _offset_plane_slab: resident plane rows back to the
    canonical plane form (the dedup-rehash / recompose source)."""
    pn, pt, pj = plane_arrays
    nb = spec.pages * spec.node_rows + ps * spec.plane_node_rows
    tb = spec.pages * spec.target_rows + ps * spec.plane_target_rows
    jb = spec.pages * spec.joined_rows + ps * spec.plane_joined_rows
    pnc = pn.copy()
    pnc[:n_local, 0] -= np.uint32(nb)
    pnc[:n_local, 1] -= np.uint32(tb)
    ptc = np.where(pt > 0, pt - jb, 0).astype(np.int32)
    return pnc, ptc, np.array(pj, copy=True)


# -- arena classify kernels --------------------------------------------------


def _arena_pages(page_table: jax.Array, tenant: jax.Array) -> jax.Array:
    """(B,) page index per packet from the device page table; -1 for
    out-of-range tenant ids and absent tenants (their lanes classify to
    UNDEF — the deterministic no-table verdict, never a read from
    another tenant's slab)."""
    mt = page_table.shape[0]
    t_ok = (tenant >= 0) & (tenant < mt)
    pg = jnp.take(
        page_table, jnp.clip(tenant, 0, mt - 1), mode="clip"
    ).astype(jnp.int32)
    return jnp.where(t_ok, pg, -1)


def arena_dense_result_and_score(
    arena: DenseArena, batch: DeviceBatch, tenant: jax.Array, *, pages: int
) -> Tuple[jax.Array, jax.Array]:
    """(raw scan result, LPM score) over the dense pool: each packet
    compares against ITS OWN slab's rows (a (B, S)-shaped gather-
    compare — same arithmetic as lpm_dense_scores, slab-local).  Also
    the overlay side of the arena combine."""
    S = arena.mask_len.shape[0] // pages
    pg = _arena_pages(arena.page_table, tenant)
    valid = pg >= 0
    base = jnp.clip(pg, 0) * S
    ridx = base[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]
    kw = jnp.take(arena.key_words, ridx, axis=0, mode="clip")   # (B,S,5)
    mw = jnp.take(arena.mask_words, ridx, axis=0, mode="clip")
    ml = jnp.take(arena.mask_len, ridx, axis=0, mode="clip")    # (B,S)
    pkt = packet_key_words(batch)
    diff = (pkt[:, None, :] ^ kw) & mw
    match = jnp.all(diff == 0, axis=-1)
    cap = jnp.where(batch.kind == KIND_IPV4, 32, 128)
    ok = valid[:, None] & match & (ml >= 0) & (ml <= cap[:, None])
    score_all = jnp.where(ok, ml + 1, 0)
    loc = jnp.argmax(score_all, axis=1).astype(jnp.int32)
    score = jnp.max(score_all, axis=1)
    rows = jnp.take(arena.rules, base + loc, axis=0, mode="clip")
    rows = jnp.where((score > 0)[:, None], rows, 0)
    rows = rows.reshape(rows.shape[0], -1, 5)
    return rule_scan(rows, batch), score


def classify_arena_dense(
    arena: DenseArena, batch: DeviceBatch, tenant: jax.Array, *, pages: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    raw, _score = arena_dense_result_and_score(
        arena, batch, tenant, pages=pages
    )
    return finalize(raw, batch)


def _arena_ctrie_entry(
    ca: CtrieArena, batch: DeviceBatch, tenant: jax.Array, *,
    pages: int, spec: Optional[ArenaSpec] = None,
):
    """Tenant-steered entry of the paged compressed walk: tenant ->
    page (device page table) -> slab root_lut row -> GLOBAL root node
    -> DIR-16 slot.  Returns (node, alive, best0) in pool-global terms
    — everything past here is the shared _ctrie_descend.

    On a spliced arena (``spec.spliced``), page-table rows decode to
    (page, bank), and a SPLICE_TAG-tagged l0 slot resolves through the
    tenant's active splice-table bank to a shared plane id: the walk
    enters at that plane's root row in the appended plane pool (local
    row 0 — BFS emission makes the subtree root the minimum node id)
    with NO host round-trip.  best0 (<=16-bit prefixes) is always
    trunk-owned, so the leaf-push fallback is splice-oblivious."""
    SL = ca.root_lut.shape[0] // pages
    R0 = ca.l0.shape[0] // (pages * 65536)
    pg_raw = _arena_pages(ca.page_table, tenant)
    valid = pg_raw >= 0
    spliced = spec is not None and spec.spliced
    if spliced:
        bank = jnp.where(valid, pg_raw >> _SPLICE_BANK_SHIFT, 0)
        if _inject_clampgather_bug():
            pg = jnp.where(valid, pg_raw, -1)
        else:
            pg = jnp.where(valid, pg_raw & _SPLICE_PAGE_MASK, -1)
    else:
        pg = pg_raw
    pg0 = jnp.clip(pg, 0)
    if_ok = (batch.ifindex >= 0) & (batch.ifindex < SL)
    lidx = pg0 * SL + jnp.clip(batch.ifindex, 0, SL - 1)
    # out-of-lut ifindexes resolve to the page's OWN null root (the
    # single-table if_ok -> root 0 semantics, slab-local)
    root = jnp.where(
        if_ok, jnp.take(ca.root_lut, lidx, mode="clip"), pg0 * R0
    ).astype(jnp.int32)
    nib0 = (batch.ip_words[:, 0] >> np.uint32(16)).astype(jnp.int32)
    e0 = root * 65536 + nib0
    in0 = valid & (e0 >= 0) & (e0 < ca.l0.shape[0])
    rows0 = jnp.take(ca.l0, e0, axis=0, mode="clip")
    best0 = jnp.where(in0 & (rows0[:, 1] > 0), rows0[:, 1], 0)
    v = rows0[:, 0]
    if spliced:
        K = spec.splice_slots
        mt = ca.page_table.shape[0]
        is_sp = v >= jnp.int32(SPLICE_TAG)
        slot = jnp.clip(v - jnp.int32(SPLICE_TAG), 0, K - 1)
        t0 = jnp.clip(tenant, 0, mt - 1).astype(jnp.int32)
        srow = (bank * mt + t0) * K + slot
        ps = jnp.take(ca.splice, srow, mode="clip").astype(jnp.int32)
        plane_root = (
            pages * spec.node_rows
            + jnp.clip(ps, 0) * spec.plane_node_rows
        )
        alive = in0 & jnp.where(is_sp, ps >= 0, v > 0)
        node = jnp.where(
            is_sp, plane_root, jnp.maximum(v, 1) - 1
        ).astype(jnp.int32)
        node = jnp.where(alive, node, 0)
    else:
        alive = in0 & (v > 0)
        node = jnp.where(alive, v - 1, 0)
    return node, alive, best0


def arena_ctrie_rows(
    ca: CtrieArena, batch: DeviceBatch, tenant: jax.Array, *,
    pages: int, d_max: int, spec: Optional[ArenaSpec] = None,
) -> jax.Array:
    """(B, 3 + R*5) joined rows from the paged compressed walk —
    per-tenant verdicts bit-identical to ctrie_walk_rows over that
    tenant's standalone CTrieTables."""
    node, alive, best0 = _arena_ctrie_entry(
        ca, batch, tenant, pages=pages, spec=spec
    )
    win = _ctrie_descend(ca.nodes, batch, node, alive, d_max)
    in_w = (win >= 0) & (win < ca.targets.shape[0])
    tval = jnp.where(
        in_w, jnp.take(ca.targets, jnp.clip(win, 0), mode="clip"), 0
    )
    sel = jnp.where(tval > 0, tval, best0)  # global joined position
    in_j = (sel > 0) & (sel < ca.joined.shape[0])
    rows = jnp.take(
        ca.joined, jnp.clip(sel, 0, ca.joined.shape[0] - 1), axis=0,
        mode="clip",
    )
    return jnp.where(in_j[:, None], rows, 0)


def arena_ctrie_result_and_score(
    ca: CtrieArena, batch: DeviceBatch, tenant: jax.Array, *,
    pages: int, d_max: int, spec: Optional[ArenaSpec] = None,
) -> Tuple[jax.Array, jax.Array]:
    rows = arena_ctrie_rows(
        ca, batch, tenant, pages=pages, d_max=d_max, spec=spec
    )
    matched = (
        rows[:, 0].astype(jnp.int32) | (rows[:, 1].astype(jnp.int32) << 16)
    ) > 0
    score = jnp.where(matched, rows[:, 2].astype(jnp.int32) + 1, 0)
    return rule_scan(joined_rule_rows(rows), batch), score


def classify_arena_ctrie(
    ca: CtrieArena, batch: DeviceBatch, tenant: jax.Array, *,
    pages: int, d_max: int, spec: Optional[ArenaSpec] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    raw, _s = arena_ctrie_result_and_score(
        ca, batch, tenant, pages=pages, d_max=d_max, spec=spec
    )
    return finalize(raw, batch)


def classify_arena_with_overlay(
    main, overlay: DenseArena, batch: DeviceBatch, tenant: jax.Array, *,
    pages: int, ov_pages: int, d_max: int = 0,
    spec: Optional[ArenaSpec] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Arena classify with the per-tenant dense overlay side-pool: the
    longest-prefix combine of classify_with_overlay, both sides
    tenant-steered.  ``main`` is a CtrieArena (d_max > 0) or a
    DenseArena."""
    if isinstance(main, CtrieArena):
        raw_m, score_m = arena_ctrie_result_and_score(
            main, batch, tenant, pages=pages, d_max=d_max, spec=spec
        )
    else:
        raw_m, score_m = arena_dense_result_and_score(
            main, batch, tenant, pages=pages
        )
    raw_o, score_o = arena_dense_result_and_score(
        overlay, batch, tenant, pages=ov_pages
    )
    result = jnp.where(score_o > score_m, raw_o, raw_m)
    return finalize(result, batch)


@functools.lru_cache(maxsize=None)
def jitted_classify_arena_wire_fused(
    family: str, pages: int, d_max: int = 0, ov_pages: int = 0,
    spec: Optional[ArenaSpec] = None,
):
    """The arena wire launch: (arena[, overlay], wire, tenant) ->
    fused (res16, stats) single-buffer output — the production
    mixed-tenant dispatch.  Cache keyed on the pool geometry statics
    (family, pages, d_max, overlay pages, and — spliced arenas only —
    the full spec), which are FIXED per arena: tenant count, swaps and
    patches never re-specialize.  Callers pass ``spec`` only when
    spec.spliced (legacy cache arity preserved)."""
    if family == "dense":
        if ov_pages:
            def f(arena, ov, wire, tenant):
                res, _x, stats = classify_arena_with_overlay(
                    arena, ov, unpack_wire(wire), tenant,
                    pages=pages, ov_pages=ov_pages,
                )
                return fuse_wire_outputs(res.astype(jnp.uint16), stats)
        else:
            def f(arena, wire, tenant):
                res, _x, stats = classify_arena_dense(
                    arena, unpack_wire(wire), tenant, pages=pages
                )
                return fuse_wire_outputs(res.astype(jnp.uint16), stats)
    elif family == "ctrie":
        if ov_pages:
            def f(arena, ov, wire, tenant):
                res, _x, stats = classify_arena_with_overlay(
                    arena, ov, unpack_wire(wire), tenant,
                    pages=pages, ov_pages=ov_pages, d_max=d_max,
                    spec=spec,
                )
                return fuse_wire_outputs(res.astype(jnp.uint16), stats)
        else:
            def f(arena, wire, tenant):
                res, _x, stats = classify_arena_ctrie(
                    arena, unpack_wire(wire), tenant,
                    pages=pages, d_max=d_max, spec=spec,
                )
                return fuse_wire_outputs(res.astype(jnp.uint16), stats)
    else:
        raise ValueError(f"unknown arena family {family!r}")
    return jax.jit(f)


# -- the allocator -----------------------------------------------------------


class ArenaAllocator:
    """Host-side slab allocator over one family pool: page alloc/free,
    full-slab bakes, per-slab incremental patches, page-table flips and
    compaction — every device mutation through the SAME warmed capped/
    fused scatter executables as the single-table patch path, so a
    warm arena performs zero jit compiles across tenant create / swap /
    patch / destroy (test-pinned by the recompile-lint suite).

    Slabs are CONTENT-ADDRESSED and shared COPY-ON-WRITE (ISSUE-15):
    a canonical sha256 over the baked (page-independent) slab arrays
    maps identical rulesets to ONE physical page with refcounted
    page-table rows — N tenants on the same baseline cost one slab,
    and installing a ruleset whose content is already resident is a
    page-table row flip (no bake, no device write).  A tenant EDIT on
    a shared page triggers clone-then-patch: the donor's canonical
    arrays copy host-side, the dirty rows patch the copy, and the
    result lands in a free page through the warmed full-slab fused
    scatter before the editing tenant's page-table row flips — the
    donor's refcount decrements (free at zero) and every OTHER sharer
    keeps serving the untouched donor slab, gap-free.  In-place
    patches of a private (refcount-1) page stay O(dirty rows); they
    mark the page's content hash stale, and a background
    ``dedup_sweep`` re-hashes stale pages and re-merges pages whose
    content re-converged.

    Thread-safety: all mutating entry points take the internal lock
    (re-entrant: the ``pre_flip`` plane-refresh callback reads
    allocator state back); ``arena`` snapshots the current device
    tuple (classify dispatches finish on the tuple they captured — the
    double-buffer contract, per-row granular here because a page-table
    flip only redirects lanes of the flipped tenant)."""

    def __init__(self, spec: ArenaSpec, device=None, shardings=None):
        """``device`` is a jax device OR a Sharding (scatter payloads
        and flips are placed with it — on a mesh, pass the REPLICATED
        sharding); ``shardings`` optionally overrides the initial
        placement PER POOL ARRAY name (the mesh backend passes the
        slab-family partition rules here, pages sharded along the
        "rules" axis)."""
        self.spec = spec
        self._device = device
        self._shardings = shardings or {}
        self._lock = threading.RLock()
        P = spec.pages
        if spec.family == "dense":
            S = P * spec.entries
            host = {
                "key_words": np.zeros((S, 5), np.uint32),
                "mask_words": np.zeros((S, 5), np.uint32),
                "mask_len": np.full(S, -1, np.int32),
                "rules": np.zeros((S, spec.rule_slots * 5), np.uint16),
            }
        else:
            pp = spec.plane_slots
            host = {
                "l0": np.zeros((P * spec.l0_rows, 2), np.int32),
                "nodes": np.zeros(
                    (P * spec.node_rows + pp * spec.plane_node_rows, 20),
                    np.uint32,
                ),
                "targets": np.zeros(
                    P * spec.target_rows + pp * spec.plane_target_rows,
                    np.int32,
                ),
                "joined": np.zeros(
                    (P * spec.joined_rows + pp * spec.plane_joined_rows,
                     3 + spec.rule_slots * 5),
                    np.uint16,
                ),
                "root_lut": np.zeros(P * spec.lut_rows, np.int32),
                "splice": np.full(spec.splice_rows, -1, np.int32),
            }
        host["page_table"] = np.full(spec.max_tenants, -1, np.int32)
        self._host = host
        dev = {
            k: jax.device_put(
                jnp.asarray(v), self._shardings.get(k, device)
            )
            for k, v in host.items()
        }
        if spec.family == "dense":
            self._dev = DenseArena(**dev)
        else:
            self._dev = CtrieArena(**dev)
        self._free = list(range(P))
        self._tenant_page: dict = {}
        self._tenant_tables: dict = {}
        #: CoW bookkeeping -------------------------------------------------
        #: page -> count of page-table rows referencing it (the tenant
        #: references; the check_arena invariant is that this equals
        #: the recount from _tenant_page at every boundary)
        self._page_refs: dict = {}
        #: page -> count of stage() reservations not yet activated /
        #: released (holds keep a page alive independent of refs and
        #: pin its page id against compaction/dedup moves)
        self._page_holds: dict = {}
        #: page -> real skip-node row count of the resident slab (what
        #: makes page-offset stripping well-defined; persists across a
        #: free so a standby claim-back stays canonicalizable)
        self._page_nnodes: dict = {}
        #: content hash -> page and inverse, for pages whose hash is
        #: KNOWN-current; pages go _hash_dirty on in-place patch /
        #: CoW clone / free-list claim-back and dedup_sweep re-indexes
        self._hash_page: dict = {}
        self._page_hash: dict = {}
        self._hash_dirty: set = set()
        self.counters = {
            "assigns": 0, "patches": 0, "swaps": 0, "flips": 0,
            "destroys": 0, "compactions": 0, "slab_writes": 0,
            "shared_hits": 0, "cow_clones": 0, "dedup_merges": 0,
            "plane_writes": 0, "plane_hits": 0, "splice_unsplices": 0,
            "splice_merges": 0,
        }
        #: structural (subtree-splice) compression state (ISSUE-17) ---------
        self._spliced = spec.spliced
        #: plane id free list / refcounts / stage holds (plane analogue
        #: of the page bookkeeping; a plane frees at zero refs + holds)
        self._plane_free = list(range(spec.plane_slots))
        self._plane_refs: dict = {}
        self._plane_holds: dict = {}
        self._plane_nnodes: dict = {}
        #: plane content hash -> plane id and inverse (plane-granular
        #: content addressing; planes go hash-dirty on in-place joined
        #: patches and dedup_sweep re-merges re-converged planes)
        self._hash_plane: dict = {}
        self._plane_hash: dict = {}
        self._plane_hash_dirty: set = set()
        #: tenant -> {splice slot -> plane id} (the host truth of the
        #: active splice-table bank) and tenant -> _SpliceSub metas
        #: (slot ownership maps for recompose / edit routing)
        self._tenant_splices: dict = {}
        self._tenant_splice_meta: dict = {}
        #: tenant -> active splice bank (0/1); the page-table row
        #: encodes page | bank << 30 so both flip in ONE scatter
        self._tenant_bank: dict = {}
        #: pages whose resident slab is a decomposed TRUNK (hash-index
        #: keys domain-tagged b"T"+hash so a trunk never dedups against
        #: a whole slab of coincidentally-equal bytes)
        self._page_decomposed: set = set()
        #: page -> stack of staged splice plans [((slot, plane), ...)]
        #: consumed LIFO by activate()/release()
        self._stage_plans: dict = {}
        #: planes whose node rows changed since the last
        #: consume_dirty_plane_rows() (the Pallas byte-plane refresh)
        self._dirty_plane_rows: set = set()
        #: bumps on every structural slab write — consumers that derive
        #: secondary layouts from the node pool (the paged Pallas walk's
        #: byte planes) rebuild when this moves; rules-only patches
        #: never touch it
        self.node_gen = 0
        #: pages whose node slab changed since the last
        #: consume_dirty_node_pages() — lets plane consumers re-derive
        #: ONLY the written slabs' rows instead of the whole pool
        self._dirty_node_pages: set = set()
        self._warm()

    # -- introspection -------------------------------------------------------

    @property
    def arena(self):
        """Snapshot of the current device pool tuple."""
        with self._lock:
            return self._dev

    @property
    def family(self) -> str:
        return self.spec.family

    def page_of(self, tenant: int):
        with self._lock:
            return self._tenant_page.get(tenant)

    def tables_of(self, tenant: int):
        with self._lock:
            return self._tenant_tables.get(tenant)

    def tenants(self):
        with self._lock:
            return sorted(self._tenant_page)

    def free_pages(self) -> int:
        with self._lock:
            return len(self._free)

    def page_refcount(self, page: int) -> int:
        """Page-table references on one physical page (0 for free /
        hold-only pages)."""
        with self._lock:
            return self._page_refs.get(page, 0)

    def page_holds(self, page: int) -> int:
        with self._lock:
            return self._page_holds.get(page, 0)

    def tenant_shares_page(self, tenant: int) -> bool:
        """True when the tenant's slab is shared (another tenant's
        page-table row or a stage hold references the same physical
        page) — the condition under which an edit must CoW instead of
        patching in place."""
        with self._lock:
            page = self._tenant_page.get(tenant)
            return page is not None and self._is_shared(page)

    def distinct_slabs(self) -> int:
        """Live physical pages (referenced or held) — the real HBM
        occupancy denominator under sharing."""
        with self._lock:
            live = set(self._page_refs) | {
                p for p, h in self._page_holds.items() if h > 0
            }
            return len(live)

    def pool_bytes(self) -> int:
        """Resident HBM footprint of the pools (the denominator of the
        arena-vs-N-tables bench line)."""
        with self._lock:
            return sum(int(np.asarray(a).nbytes) for a in self._dev)

    def host_nodes(self) -> Optional[np.ndarray]:
        """Host mirror of the merged skip-node pool (ctrie family) —
        the paged Pallas walk derives its byte planes from this; pair
        reads with ``node_gen`` to know when to rebuild."""
        with self._lock:
            arr = self._host.get("nodes")
            return None if arr is None else arr.copy()

    def consume_dirty_node_pages(self):
        """(node_gen, pages, node-slab host rows per page) of every
        slab whose node rows changed since the last call — the
        incremental feed for plane consumers (a full-pool re-derive on
        every tenant mutation would put O(pool) work on the O(1) swap
        path)."""
        with self._lock:
            pages = sorted(self._dirty_node_pages)
            self._dirty_node_pages = set()
            sn = self.spec.node_rows
            rows = {
                p: self._host["nodes"][p * sn : (p + 1) * sn].copy()
                for p in pages
            } if "nodes" in self._host else {}
            return self.node_gen, pages, rows

    def consume_dirty_plane_rows(self):
        """(node_gen, [(pool row base, plane node rows), ...]) of every
        subtree plane whose node rows changed since the last call — the
        plane-region analogue of consume_dirty_node_pages (plane writes
        bump node_gen but touch no page slab, so the Pallas byte-plane
        consumer refreshes O(touched subtrees), never the pool)."""
        with self._lock:
            planes = sorted(self._dirty_plane_rows)
            self._dirty_plane_rows = set()
            blocks: list = []
            if planes and "nodes" in self._host:
                snp = self.spec.plane_node_rows
                for ps in planes:
                    b = self._plane_base(ps)[0]
                    blocks.append(
                        (b, self._host["nodes"][b: b + snp].copy())
                    )
            return self.node_gen, blocks

    def plane_refcount(self, ps: int) -> int:
        """Splice-row references on one subtree plane (0 for free /
        hold-only planes)."""
        with self._lock:
            return self._plane_refs.get(ps, 0)

    def tenant_splices(self, tenant: int) -> dict:
        """{splice slot -> plane id} of the tenant's active splice
        bank (empty for unspliced tenants)."""
        with self._lock:
            return dict(self._tenant_splices.get(tenant) or {})

    def distinct_planes(self) -> int:
        """Live subtree planes (referenced or held) — the plane-pool
        half of the HBM occupancy numerator."""
        with self._lock:
            live = set(self._plane_refs) | {
                p for p, h in self._plane_holds.items() if h > 0
            }
            return len(live)

    def counter_values(self) -> dict:
        """tenant_* counters for /metrics (the obs satellite): gauges
        for slab occupancy plus monotonic mutation counts."""
        with self._lock:
            live = set(self._page_refs) | {
                p for p, h in self._page_holds.items() if h > 0
            }
            out = {
                "tenant_active_slabs": len(self._tenant_page),
                "tenant_free_slabs": len(self._free),
                "tenant_distinct_slabs": len(live),
                "tenant_shared_pages": sum(
                    1 for n in self._page_refs.values() if n > 1
                ),
                "tenant_hash_index": len(self._hash_page),
                "tenant_hash_dirty": len(self._hash_dirty),
            }
            if self._spliced:
                live_planes = set(self._plane_refs) | {
                    p for p, h in self._plane_holds.items() if h > 0
                }
                out["arena_subtree_planes"] = len(live_planes)
                out["arena_shared_subtrees"] = sum(
                    1 for n in self._plane_refs.values() if n > 1
                )
                out["arena_splice_rows"] = sum(
                    len(m) for m in self._tenant_splices.values()
                )
                out["splice_unsplices"] = self.counters[
                    "splice_unsplices"
                ]
                out["splice_merges"] = self.counters["splice_merges"]
            for k, v in self.counters.items():
                out[f"tenant_{k}_total"] = v
            return out

    # -- device write plumbing ----------------------------------------------

    def _warm(self) -> None:
        """Pre-compile every scatter shape the allocator can emit: the
        small-edit cap ladder per pool array, the FULL-SLAB row counts
        (tenant create/swap/compact), the fused multi-family slab
        combo, and the 1-row page-table flip — so a warm arena's whole
        tenant lifecycle is compile-free."""
        dev = self._dev
        arrays = [a for a in dev[:-1]]
        warm_scatters(arrays, self._device, max_rows=TXN_WARM_MAX_ROWS)
        # the page-table flip executable (1-row direct scatter)
        _scatter(dev.page_table, np.zeros(1, np.int64),
                 np.zeros(1, np.int32), self._device)
        # full-slab writes: one fused txn_scatter over every family
        # array at its slab row count
        entries = []
        for arr, rows in zip(arrays, self._slab_rows()):
            entries.append((
                arr, np.zeros(rows, np.int64),
                np.zeros((rows,) + tuple(arr.shape[1:]), arr.dtype),
            ))
        txn_scatter(entries, self._device)
        if self._spliced:
            # plane writes: one fused txn_scatter over the three plane
            # arrays at their per-plane row counts, plus the K-row
            # splice-bank scatter — the whole splice lifecycle (plane
            # share/unsplice/merge, splice-map update, bank flip) then
            # rides warmed executables only
            txn_scatter(
                [
                    (
                        getattr(dev, name),
                        np.zeros(rows, np.int64),
                        np.zeros(
                            (rows,) + tuple(getattr(dev, name).shape[1:]),
                            getattr(dev, name).dtype,
                        ),
                    )
                    for name, rows in zip(
                        ("nodes", "targets", "joined"), self._plane_rows()
                    )
                ],
                self._device,
            )
            K = self.spec.splice_slots
            _scatter(dev.splice, np.arange(K, dtype=np.int64),
                     np.full(K, -1, np.int32), self._device)
        # rules-only patch combo (ladder) for the hint fast path
        patchable = [self._patch_arrays(dev)]
        for group in patchable:
            nb = group[0].shape[0]
            for k in scatter_cap_ladder(nb, TXN_WARM_MAX_ROWS):
                txn_scatter(
                    [
                        (
                            a,
                            np.zeros(min(k, max(a.shape[0] // 4, 1)), np.int64),
                            np.zeros(
                                (min(k, max(a.shape[0] // 4, 1)),)
                                + tuple(a.shape[1:]),
                                a.dtype,
                            ),
                        )
                        for a in group
                    ],
                    self._device,
                )

    def _slab_rows(self):
        s = self.spec
        if s.family == "dense":
            return (s.entries, s.entries, s.entries, s.entries)
        return (s.l0_rows, s.node_rows, s.target_rows, s.joined_rows,
                s.lut_rows)

    def _array_names(self):
        if self.spec.family == "dense":
            return ("key_words", "mask_words", "mask_len", "rules")
        return ("l0", "nodes", "targets", "joined", "root_lut")

    # -- subtree plane plumbing (spliced arenas) ------------------------------

    def _plane_rows(self):
        s = self.spec
        return (s.plane_node_rows, s.plane_target_rows,
                s.plane_joined_rows)

    def _plane_base(self, ps: int):
        """(nodes, targets, joined) pool row bases of plane ``ps`` —
        the plane pool region starts where the page slabs end."""
        s = self.spec
        return (s.pages * s.node_rows + ps * s.plane_node_rows,
                s.pages * s.target_rows + ps * s.plane_target_rows,
                s.pages * s.joined_rows + ps * s.plane_joined_rows)

    def _decode_page_table(self, vals):
        """Strip the splice bank bit off page-table values (identity on
        unspliced arenas); -1 absent rows pass through."""
        vals = np.asarray(vals)
        if not self._spliced:
            return vals
        return np.where(
            vals >= 0, vals & _SPLICE_PAGE_MASK, vals
        ).astype(vals.dtype)

    def _canonical_of_plane(self, ps: int):
        """(pnodes, ptargets, pjoined, n_local) canonical form of one
        resident plane, derived from the host mirror by stripping the
        plane-slot offsets."""
        names = ("nodes", "targets", "joined")
        arrs = tuple(
            self._host[name][b: b + r]
            for name, r, b in zip(names, self._plane_rows(),
                                  self._plane_base(ps))
        )
        n_local = self._plane_nnodes.get(ps, 0)
        return _unoffset_plane_slab(self.spec, arrs, n_local, ps) + (
            n_local,
        )

    def _plane_is_shared(self, ps: int) -> bool:
        return (
            self._plane_refs.get(ps, 0) > 1
            or self._plane_holds.get(ps, 0) > 0
        )

    def _alloc_plane(self) -> int:
        if not self._plane_free:
            raise _PlaneCapacityError(
                f"arena out of subtree planes ({self.spec.plane_slots} "
                "total) — the decomposed install falls back to the "
                "whole-slab path"
            )
        return self._plane_free.pop(0)

    def _write_plane(self, ps: int, plane_arrays, n_local: int) -> None:
        """Bake one canonical plane into the pool region: mirror first,
        then ONE fused txn_scatter across nodes/targets/joined at the
        plane row counts (warmed in _warm)."""
        resident = _offset_plane_slab(self.spec, plane_arrays, n_local, ps)
        names = ("nodes", "targets", "joined")
        entries = []
        for name, rows, base, arr in zip(
            names, self._plane_rows(), self._plane_base(ps), resident
        ):
            self._host[name][base: base + rows] = arr
            entries.append((
                getattr(self._dev, name),
                base + np.arange(rows, dtype=np.int64),
                arr,
            ))
        patched = txn_scatter(entries, self._device)
        if patched is None:
            raise ArenaCapacityError(
                "plane write exceeded the scatter budget"
            )
        self._dev = self._dev._replace(**dict(zip(names, patched)))
        self._plane_nnodes[ps] = int(n_local)
        self.counters["plane_writes"] += 1
        self.node_gen += 1
        self._dirty_plane_rows.add(ps)

    def _unindex_plane(self, ps: int) -> None:
        old = self._plane_hash.pop(ps, None)
        if old is not None and self._hash_plane.get(old) == ps:
            del self._hash_plane[old]

    def _index_plane(self, ps: int, phash: bytes) -> bool:
        self._unindex_plane(ps)
        self._plane_hash_dirty.discard(ps)
        cur = self._hash_plane.get(phash)
        if cur is not None and cur != ps:
            self._plane_hash_dirty.add(ps)
            return False
        self._hash_plane[phash] = ps
        self._plane_hash[ps] = phash
        return True

    def _plane_incref(self, ps: int) -> None:
        self._plane_refs[ps] = self._plane_refs.get(ps, 0) + 1

    def _plane_decref(self, ps: int, from_unsplice: bool = False) -> None:
        """Drop one splice-row reference on a plane; the plane frees at
        zero (with no holds).  ``from_unsplice`` marks the unsplice
        path's old-plane decrement — the exact statement the injected
        spliceleak defect forgets."""
        if from_unsplice and _inject_spliceleak_bug():
            return
        n = self._plane_refs.get(ps, 0) - 1
        if n > 0:
            self._plane_refs[ps] = n
            return
        self._plane_refs.pop(ps, None)
        if self._plane_holds.get(ps, 0) == 0:
            self._release_plane(ps)

    def _release_plane(self, ps: int) -> None:
        self._unindex_plane(ps)
        self._plane_hash_dirty.discard(ps)
        if ps not in self._plane_free:
            self._plane_free.append(ps)

    def _release_plane_hold(self, ps: int) -> None:
        h = self._plane_holds.get(ps, 0)
        if h <= 0:
            return
        if h == 1:
            self._plane_holds.pop(ps, None)
        else:
            self._plane_holds[ps] = h - 1
        if (
            self._plane_refs.get(ps, 0) == 0
            and self._plane_holds.get(ps, 0) == 0
        ):
            self._release_plane(ps)

    def _acquire_plane(self, m: "_SpliceSub") -> int:
        """Content-addressed plane acquisition for one subtree meta:
        hash HIT -> refcount bump on the already-resident plane (N
        near-copy tenants cost ONE plane); miss -> alloc + warmed
        write + index.  Returns the plane id with one reference
        taken."""
        ps = self._hash_plane.get(m.phash)
        if ps is not None:
            self._plane_incref(ps)
            self.counters["plane_hits"] += 1
            return ps
        ps = self._alloc_plane()
        try:
            self._write_plane(ps, m.plane, m.n_local)
        except Exception:
            if ps not in self._plane_free:
                self._plane_free.insert(0, ps)
            raise
        self._index_plane(ps, m.phash)
        self._plane_refs[ps] = 1
        return ps

    def _write_splice_rows(self, tenant: int, slot_map: dict) -> None:
        """Write the tenant's FULL splice row block (all K slots; -1
        for unused) to the INACTIVE bank and switch the tenant's bank
        variable — the very next _flip() publishes page + bank in one
        1-row page-table scatter, so classify never pairs a new splice
        map with the old page (or vice versa)."""
        K = self.spec.splice_slots
        mt = self.spec.max_tenants
        bank = 1 - self._tenant_bank.get(tenant, 0)
        vals = np.full(K, -1, np.int32)
        for slot, ps in slot_map.items():
            vals[slot] = ps
        base = (bank * mt + tenant) * K
        self._host["splice"][base: base + K] = vals
        sp = _scatter(
            self._dev.splice,
            base + np.arange(K, dtype=np.int64),
            vals, self._device,
        )
        self._dev = self._dev._replace(splice=sp)
        self._tenant_bank[tenant] = bank

    def _clear_splice_rows(self, tenant: int) -> None:
        """Blank the tenant's ACTIVE splice bank (no bank switch) —
        used after the tenant stopped serving spliced content (whole-
        slab activate / destroy), purely for mirror hygiene: an
        untagged l0 never reads the splice table."""
        K = self.spec.splice_slots
        mt = self.spec.max_tenants
        bank = self._tenant_bank.get(tenant, 0)
        base = (bank * mt + tenant) * K
        vals = np.full(K, -1, np.int32)
        self._host["splice"][base: base + K] = vals
        sp = _scatter(
            self._dev.splice,
            base + np.arange(K, dtype=np.int64),
            vals, self._device,
        )
        self._dev = self._dev._replace(splice=sp)

    def _drop_tenant_planes(self, tenant: int) -> None:
        """Release every plane the tenant's splice rows reference and
        clear its splice state (the tenant leaves decomposed serving)."""
        smap = self._tenant_splices.pop(tenant, None)
        self._tenant_splice_meta.pop(tenant, None)
        if smap:
            self._clear_splice_rows(tenant)
            for ps in smap.values():
                self._plane_decref(ps)

    def _bake_decomposed(self, tables: CompiledTables):
        """(trunk arrays, n_nodes, trunk hash key, metas) of one tenant
        table's subtree decomposition, or None when nothing factors.
        Memoized on the tables object like _bake_canonical, so repeated
        installs of a known near-copy pay the decompose ONCE.  The
        trunk key is domain-tagged (b"T" + hash) — a trunk page never
        hash-collides with a whole slab."""
        if not self._spliced:
            return None
        cached = getattr(tables, "_arena_splice_cache", None)
        if cached is not None and cached[0] == self.spec:
            return cached[1]
        arrays, n_nodes, _chash = self._bake_canonical(tables)
        dec = _decompose_ctrie_slab(self.spec, arrays, n_nodes)
        if dec is None:
            result = None
        else:
            trunk, metas = dec
            result = (
                trunk, n_nodes,
                b"T" + slab_content_hash(trunk, n_nodes), metas,
            )
        try:
            object.__setattr__(
                tables, "_arena_splice_cache", (self.spec, result)
            )
        except Exception:
            pass
        return result

    # -- content addressing / CoW plumbing ------------------------------------

    def _is_shared(self, page: int) -> bool:
        """A page is shared when >1 page-table row references it OR a
        stage hold reserves it — either way an in-place write would
        mutate state some OTHER consumer is serving/holding, so edits
        must copy-on-write."""
        return (
            self._page_refs.get(page, 0) > 1
            or self._page_holds.get(page, 0) > 0
        )

    def _bake_canonical(self, tables: CompiledTables):
        """(canonical arrays, n_nodes, content hash) for one tenant
        table — the page-independent bake the hash index keys on.
        Memoized on the tables object (same trick as the cpoptrie host
        caches), so repeated installs of a known baseline pay the bake
        and the hash ONCE and every later tenant-create-from-content
        is a dict probe + page-table flip."""
        cached = getattr(tables, "_arena_slab_cache", None)
        if cached is not None and cached[0] == self.spec:
            return cached[1], cached[2], cached[3]
        if self.spec.family == "dense":
            arrays = _dense_slab_arrays(self.spec, tables)
            n_nodes = 0
        else:
            arrays, n_nodes = _ctrie_canonical_slab(self.spec, tables)
        chash = slab_content_hash(arrays, n_nodes)
        try:
            object.__setattr__(
                tables, "_arena_slab_cache",
                (self.spec, arrays, n_nodes, chash),
            )
        except Exception:
            pass
        return arrays, n_nodes, chash

    def _offset(self, arrays, n_nodes: int, page: int):
        """Canonical slab arrays -> the page's resident form (identity
        for the dense family: dense slabs carry no cross-row indices)."""
        if self.spec.family == "dense":
            return arrays
        return _offset_ctrie_slab(self.spec, arrays, n_nodes, page)

    def _canonical_of_page(self, page: int):
        """Canonical (page-independent) arrays of one resident page,
        derived from the host mirror by stripping the page offsets —
        the CoW clone / compaction / dedup-rehash source.  Returns
        mirror VIEWS for the dense family and page 0; callers that
        mutate must copy."""
        arrays = tuple(
            self._host[name][page * r : (page + 1) * r]
            for name, r in zip(self._array_names(), self._slab_rows())
        )
        if self.spec.family == "dense":
            return arrays
        return _unoffset_ctrie_slab(
            self.spec, arrays, self._page_nnodes.get(page, 0), page
        )

    def _unindex(self, page: int) -> None:
        """Drop a page's hash-index entry (and its inverse) if present."""
        old = self._page_hash.pop(page, None)
        if old is not None and self._hash_page.get(old) == page:
            del self._hash_page[old]

    def _index_page(self, page: int, chash: bytes) -> bool:
        """Register a page's known-current content hash.  When another
        live page already owns the hash, the index keeps pointing at it
        and this page stays hash-dirty (dedup_sweep merges the
        duplicates); returns whether the page was indexed."""
        self._unindex(page)
        self._hash_dirty.discard(page)
        cur = self._hash_page.get(chash)
        if cur is not None and cur != page:
            self._hash_dirty.add(page)
            return False
        self._hash_page[chash] = page
        self._page_hash[page] = chash
        return True

    def _mark_hash_dirty(self, page: int) -> None:
        """The page's content diverged from its registered hash (an
        in-place patch): unindex now, re-hash lazily in dedup_sweep —
        keeping the patch fast path O(dirty rows), not O(slab hash)."""
        self._unindex(page)
        self._hash_dirty.add(page)

    def _incref(self, page: int) -> None:
        self._page_refs[page] = self._page_refs.get(page, 0) + 1

    def _decref(self, page: int, from_clone: bool = False) -> None:
        """Drop one page-table reference; the page frees at zero (with
        no holds).  ``from_clone`` marks the CoW donor decrement — the
        exact statement the injected cowleak defect forgets."""
        if from_clone and _inject_cowleak_bug():
            return
        n = self._page_refs.get(page, 0) - 1
        if n > 0:
            self._page_refs[page] = n
            return
        self._page_refs.pop(page, None)
        if self._page_holds.get(page, 0) == 0:
            self._release_page(page)

    def _release_page(self, page: int) -> None:
        """Return a page to the free list: unindex its hash (a free
        page must never be a dedup hit — _alloc_page may rebake it) but
        keep the slab bytes/mirror/n_nodes, so the standby claim-back
        pattern (activate straight off the free list) keeps serving
        valid content."""
        self._unindex(page)
        self._hash_dirty.discard(page)
        if page not in self._free:
            self._free.append(page)

    def _clone_patched_canonical(self, donor_page: int, old, new, hint):
        """The CoW clone-then-patch bake: copy the DONOR page's
        canonical arrays (no table recompile — the point of the clone)
        and apply the rules-only dirty rows of ``new`` on the copy.
        Returns (arrays, n_nodes) or None when the hinted patch cannot
        express the edit (caller falls back to a full canonical bake)."""
        dirty = np.unique(np.asarray(hint.get("dense", ()), np.int64))
        dirty = dirty[(dirty >= 0) & (dirty < new.rules.shape[0])]
        arrays = [np.array(a, copy=True)
                  for a in self._canonical_of_page(donor_page)]
        n_nodes = self._page_nnodes.get(donor_page, 0)
        if self.spec.family == "dense":
            kw, mw, ml, rules, _lv, _tg, _lut, _j = _host_device_layout(
                new, pad=False, with_trie=False
            )
            if rules.dtype != np.uint16 or (
                rules.shape[1] != self.spec.rule_slots * 5
                or kw.shape[0] > self.spec.entries
            ):
                return None
            rows = dirty[dirty < kw.shape[0]]
            for arr, src in zip(arrays, (kw, mw, ml, rules)):
                arr[rows] = src[rows]
            return tuple(arrays), n_nodes
        # ctrie: structure untouched by contract (rules-only hint) —
        # only the joined plane's dirty tidx rows change
        _seed_ctrie_caches_forward(old, new, dirty)
        pr = _joined_tidx_patch_rows(new, dirty)
        if pr is None:
            return None
        pos, rows = pr
        if len(pos) and (
            int(pos.max()) >= self.spec.joined_rows
            or rows.shape[1] != arrays[3].shape[1]
        ):
            return None
        arrays[3][pos] = rows
        return tuple(arrays), n_nodes

    def _patch_arrays(self, dev):
        """The arrays a rules-only tenant edit scatters (the hint fast
        path): the dense group, or the ctrie joined plane."""
        if self.spec.family == "dense":
            return (dev.key_words, dev.mask_words, dev.mask_len, dev.rules)
        return (dev.joined,)

    def _write_slab(self, page: int, slab_arrays, n_nodes: int = 0) -> None:
        """Bake one tenant's full slab into the pools: ONE fused
        txn_scatter across every family array (whole slab rows, so a
        reused page carries no stale bytes).  Mirrors update first —
        they are the diff/bench/equivalence source of truth.
        ``n_nodes`` records the slab's real skip-node row count (what
        keeps the page's canonical form derivable from the mirror)."""
        names = self._array_names()
        entries = []
        for name, rows, arr in zip(names, self._slab_rows(), slab_arrays):
            base = page * rows
            self._host[name][base : base + rows] = arr
            entries.append((
                getattr(self._dev, name),
                base + np.arange(rows, dtype=np.int64),
                arr,
            ))
        patched = txn_scatter(entries, self._device)
        if patched is None:  # pages >= 4 makes this unreachable
            raise ArenaCapacityError("slab write exceeded the scatter budget")
        self._dev = self._dev._replace(**dict(zip(names, patched)))
        self._page_nnodes[page] = int(n_nodes)
        # a full-slab write is whole-slab content by default; the trunk
        # writer re-marks decomposed pages right after
        self._page_decomposed.discard(page)
        self.counters["slab_writes"] += 1
        self.node_gen += 1
        self._dirty_node_pages.add(page)

    def _flip(self, tenant: int, page: int, _inject: bool = False) -> None:
        """The page-table row flip — the O(1) activation that replaces
        a full re-upload.  On a spliced arena the row encodes
        ``page | bank << 30``: the tenant's splice-table bank publishes
        in the SAME scatter as its page, which is what makes a splice-
        map change atomic with the page move.  Injected defect
        (pageflip, activate-only): the device row keeps its STALE value
        while the host mirror moves on — the arena keeps serving the
        OLD slab after a swap."""
        enc = page
        if self._spliced and page >= 0:
            enc = page | (
                self._tenant_bank.get(tenant, 0) << _SPLICE_BANK_SHIFT
            )
        self._host["page_table"][tenant] = enc
        if _inject:
            self.counters["flips"] += 1
            return
        # direct 1-row scatter, NOT the capped helper: the flip is
        # always exactly one row and must not ride the nb//4 delta
        # budget (a tiny page table would refuse its own flip)
        pt = _scatter(
            self._dev.page_table,
            np.array([tenant], np.int64),
            np.array([enc], np.int32),
            self._device,
        )
        self._dev = self._dev._replace(page_table=pt)
        self.counters["flips"] += 1

    # -- tenant lifecycle ----------------------------------------------------

    def _alloc_page(self) -> int:
        if not self._free:
            raise ArenaCapacityError(
                f"arena out of pages ({self.spec.pages} total, "
                f"{len(self._page_refs)} distinct slabs live for "
                f"{len(self._tenant_page)} tenants; an edit of a SHARED "
                "slab needs a free page to copy-on-write into — size the "
                "pool with spare pages beyond the distinct-content count)"
            )
        return self._free.pop(0)

    def _check_tenant(self, tenant: int) -> None:
        if not (0 <= tenant < self.spec.max_tenants):
            raise ArenaCapacityError(
                f"tenant id {tenant} outside [0, {self.spec.max_tenants})"
            )

    def load_tenant(self, tenant: int, tables: CompiledTables,
                    hint=None, pre_flip=None) -> str:
        """Install/refresh one tenant's table.  Returns the device path
        taken:

        - "patch":   rules-only row scatter into the tenant's PRIVATE
                     resident slab (refcount 1, no holds);
        - "share":   the baked content is already resident on some page
                     (hash hit) — refcount bump + page-table flip, no
                     bake, no slab write;
        - "cow":     the tenant's page is shared and the edit forced a
                     private copy: clone-then-patch (or a full bake for
                     structural edits) into a free page, flip, donor
                     refcount decremented;
        - "rewrite": in-place full slab bake of a private page
                     (structural edit, no page change);
        - "assign":  fresh page + page-table flip.

        Spliced arenas add subtree-granular paths:

        - "patch":    additionally covers rules-only edits that land
                      inside PRIVATE planes / the private trunk;
        - "unsplice": a rules-only edit inside a SHARED subtree plane
                      repointed just that slot at a private (or
                      re-converged) plane — K splice rows + one bank
                      flip, trunk untouched;
        - "share":    trunk hash hit AND every plane hash hit (the
                      create-from-near-copy case costs the changed
                      planes only).

        ``pre_flip`` (optional callable) runs after any slab write and
        strictly BEFORE the page-table flip of paths that redirect the
        tenant to a new page — the fused-walk classifier passes its
        plane refresh here so classify never pairs a new page table
        with stale planes (new-planes/old-table is the safe pairing)."""
        self._check_tenant(tenant)
        with self._lock:
            if self._spliced:
                path = self._load_tenant_spliced(
                    tenant, tables, hint, pre_flip
                )
            else:
                path = self._load_tenant_whole(tenant, tables, hint,
                                               pre_flip)
        if _inject_cowrace_bug():
            self._finish_cowrace_pending()
        return path

    def _finish_cowrace_pending(self) -> None:
        """TEST-ONLY (cowrace defect): land the donor decref
        _cow_install deferred — OUTSIDE the allocator lock, as a plain
        read-modify-write on _page_refs with a sched_point in the
        window, so schedcheck can interleave a locked decrement
        (destroy_tenant / dedup_sweep merge) in between and demonstrate
        the lost update.  Semantics match _decref(donor,
        from_clone=True) when run serially."""
        from .. import _threads

        donor = getattr(self, "_cowrace_pending", None)
        if donor is None:
            return
        self._cowrace_pending = None
        n = self._page_refs.get(donor, 0)
        _threads.sched_point("cowrace-rmw")
        n -= 1
        if n > 0:
            self._page_refs[donor] = n
            return
        self._page_refs.pop(donor, None)
        if self._page_holds.get(donor, 0) == 0:
            self._release_page(donor)

    def _load_tenant_whole(self, tenant: int, tables: CompiledTables,
                           hint=None, pre_flip=None) -> str:
        """Whole-slab install — the pre-splice lifecycle, and the
        spliced arena's degrade-never-refuse fallback (tables that
        don't decompose, plane-pool exhaustion)."""
        with self._lock:
            page = self._tenant_page.get(tenant)
            old = self._tenant_tables.get(tenant)
            shared = page is not None and self._is_shared(page)
            if (page is not None and not shared and old is not None
                    and hint is not None):
                if self._try_patch(tenant, page, old, tables, hint):
                    self._tenant_tables[tenant] = tables
                    self.counters["patches"] += 1
                    # content diverged from the registered hash; the
                    # dedup sweep re-hashes lazily
                    self._mark_hash_dirty(page)
                    return "patch"
            if shared and old is not None and hint_trie_unchanged(hint):
                # CoW clone-then-patch: bake-free (donor canonical copy
                # + dirty rows) — skips the hash-index probe on purpose
                # (hashing would force the full bake the clone avoids;
                # re-convergence is dedup_sweep's job)
                can = self._clone_patched_canonical(page, old, tables, hint)
                if can is not None:
                    return self._cow_install(
                        tenant, page, can[0], can[1], None, tables,
                        pre_flip,
                    )
            arrays, n_nodes, chash = self._bake_canonical(tables)
            hit = self._hash_page.get(chash)
            if hit is not None:
                if hit == page:
                    # content unchanged (or a no-op edit): nothing to do
                    self._tenant_tables[tenant] = tables
                    return "share"
                self._tenant_page[tenant] = hit
                self._incref(hit)
                self._tenant_tables[tenant] = tables
                if pre_flip is not None:
                    pre_flip()
                self._flip(tenant, hit)
                if page is not None:
                    self._decref(page)
                self.counters["shared_hits"] += 1
                return "share"
            if page is None:
                new_page = self._alloc_page()
                try:
                    self._write_slab(
                        new_page, self._offset(arrays, n_nodes, new_page),
                        n_nodes=n_nodes,
                    )
                except Exception:
                    self._free.insert(0, new_page)  # never leak the page
                    raise
                self._index_page(new_page, chash)
                self._tenant_page[tenant] = new_page
                self._page_refs[new_page] = 1
                self._tenant_tables[tenant] = tables
                if pre_flip is not None:
                    pre_flip()
                self._flip(tenant, new_page)
                self.counters["assigns"] += 1
                return "assign"
            if not shared:
                self._write_slab(
                    page, self._offset(arrays, n_nodes, page),
                    n_nodes=n_nodes,
                )
                self._index_page(page, chash)
                self._tenant_tables[tenant] = tables
                self.counters["assigns"] += 1
                return "rewrite"
            # shared page + structural edit: full bake into a private
            # page (the CoW slow path)
            return self._cow_install(
                tenant, page, arrays, n_nodes, chash, tables, pre_flip,
            )

    def _write_trunk(self, page: int, trunk_arrays, n_nodes: int,
                     tkey: bytes) -> None:
        """Bake a decomposed trunk slab into ``page`` and index it
        under its domain-tagged key (b"T" + hash): trunk bytes are
        content-canonical across structurally-identical tenants, so N
        near-copies share ONE trunk page."""
        self._write_slab(
            page, self._offset(trunk_arrays, n_nodes, page),
            n_nodes=n_nodes,
        )
        self._page_decomposed.add(page)
        self._index_page(page, tkey)

    def _load_tenant_spliced(self, tenant: int, tables: CompiledTables,
                             hint, pre_flip) -> str:
        """The decomposed install: rules-only edits route through
        _splice_edit (touched subtrees only); otherwise decompose,
        acquire planes content-addressed, land/share the trunk, write
        the splice rows to the inactive bank and publish page + bank in
        one flip.  Tables that don't decompose (or plane-pool
        exhaustion) degrade to the whole-slab path."""
        page = self._tenant_page.get(tenant)
        old = self._tenant_tables.get(tenant)
        if (page is not None and old is not None and hint is not None
                and self._tenant_splices.get(tenant)
                and hint_trie_unchanged(hint)):
            r = self._splice_edit(tenant, page, old, tables, hint,
                                  pre_flip)
            if r is not None:
                return r
        dec = self._bake_decomposed(tables)
        if dec is None:
            # a previously-spliced tenant's hint describes an edit
            # against DECOMPOSED residency — never let the whole-slab
            # fast paths patch a trunk as if it were a flat slab
            if self._tenant_splices.get(tenant):
                hint = None
            r = self._load_tenant_whole(tenant, tables, hint, pre_flip)
            self._drop_tenant_planes(tenant)
            return r
        trunk_arrays, n_nodes, tkey, metas = dec
        hits0 = self.counters["plane_hits"]
        got: list = []
        try:
            for m in metas:
                got.append(self._acquire_plane(m))
        except _PlaneCapacityError:
            for ps in got:
                self._plane_decref(ps)
            r = self._load_tenant_whole(tenant, tables, None, pre_flip)
            self._drop_tenant_planes(tenant)
            return r
        all_hit = (self.counters["plane_hits"] - hits0) == len(metas)
        shared_trunk = self._hash_page.get(tkey)
        wrote = False
        try:
            if shared_trunk is not None:
                target = shared_trunk
            elif page is not None and not self._is_shared(page):
                self._write_trunk(page, trunk_arrays, n_nodes, tkey)
                target = page
                wrote = True
            else:
                target = self._alloc_page()
                try:
                    self._write_trunk(target, trunk_arrays, n_nodes, tkey)
                except Exception:
                    self._free.insert(0, target)
                    raise
                wrote = True
        except ArenaCapacityError:
            for ps in got:
                self._plane_decref(ps)
            raise
        old_map = dict(self._tenant_splices.get(tenant) or {})
        slot_map = {m.slot: ps for m, ps in zip(metas, got)}
        self._write_splice_rows(tenant, slot_map)
        self._tenant_splices[tenant] = slot_map
        self._tenant_splice_meta[tenant] = metas
        if target != page:
            self._tenant_page[tenant] = target
            self._incref(target)
        self._tenant_tables[tenant] = tables
        if pre_flip is not None:
            pre_flip()
        self._flip(tenant, target)
        if page is not None and target != page:
            self._decref(page)
        for ps in old_map.values():
            self._plane_decref(ps)
        if shared_trunk is not None and all_hit:
            self.counters["shared_hits"] += 1
            return "share"
        if page is None:
            self.counters["assigns"] += 1
            return "assign"
        if wrote and target == page:
            self.counters["assigns"] += 1
            return "rewrite"
        if target != page:
            if wrote:
                self.counters["cow_clones"] += 1
                return "cow"
            self.counters["shared_hits"] += 1
            return "share"
        # same trunk page; "unsplice" when the plane set changed
        return "unsplice" if slot_map != old_map else "share"

    def _splice_edit(self, tenant: int, page: int, old, new, hint,
                     pre_flip):
        """Rules-only edit of a spliced tenant, routed per dirty joined
        row to its owning subtree: trunk-owned rows patch the (private)
        trunk in place; plane-owned rows patch a private plane in place
        (lazy re-hash) or UNSPLICE a shared plane — repoint just that
        slot at a freshly-written private plane (or re-share an
        already-resident identical one), publish the new splice map via
        bank write + flip, and decrement the old plane's refcount (the
        spliceleak injection site).  Returns None when the edit can't
        be expressed this way (caller falls back to the decomposed full
        install)."""
        metas = self._tenant_splice_meta.get(tenant)
        cur = self._tenant_splices.get(tenant)
        if not metas or not cur:
            return None
        dirty = np.unique(np.asarray(hint.get("dense", ()), np.int64))
        dirty = dirty[(dirty >= 0) & (dirty < new.rules.shape[0])]
        _seed_ctrie_caches_forward(old, new, dirty)
        pr = _joined_tidx_patch_rows(new, dirty)
        if pr is None:
            return None
        pos, rows = pr
        if len(pos) and (
            int(pos.max()) >= self.spec.joined_rows
            or rows.shape[1] != self._dev.joined.shape[1]
        ):
            return None
        if len(pos) == 0:
            self._tenant_tables[tenant] = new
            self.counters["patches"] += 1
            return "patch"
        rowmap = {int(p): rows[j] for j, p in enumerate(pos.tolist())}
        own: dict = {}
        for i, m in enumerate(metas):
            for v in m.tidx.tolist():
                own[v] = i
        trunk_pos: list = []
        by_slot: dict = {}
        for p in pos.tolist():
            i = own.get(int(p))
            if i is None:
                trunk_pos.append(int(p))
            else:
                by_slot.setdefault(i, []).append(int(p))
        if trunk_pos and self._is_shared(page):
            # trunk CoW: route through the full decomposed install
            return None
        # plan plane actions BEFORE mutating anything, so a plane-pool
        # shortage (or a merge-target hazard) bails cleanly
        plans: list = []
        allocs = 0
        dropping: set = set()
        for i in sorted(by_slot):
            plist = by_slot[i]
            m = metas[i]
            ps = cur.get(m.slot)
            if ps is None:
                return None
            pn, pt, pj, n_local = self._canonical_of_plane(ps)
            if n_local != m.n_local:
                return None
            pj2 = np.array(pj, copy=True)
            for p in plist:
                j = int(np.searchsorted(m.tidx, p))
                if j >= len(m.tidx) or int(m.tidx[j]) != p:
                    return None
                pj2[1 + j] = rowmap[p]
            if not self._plane_is_shared(ps):
                plans.append(("patch", m, ps, plist, None, None))
                continue
            plane = (pn, pt, pj2)
            h = slab_content_hash(plane, n_local)
            tgt = self._hash_plane.get(h)
            if tgt is not None and tgt != ps:
                plans.append(("merge", m, ps, plist, tgt, None))
            else:
                plans.append(("unsplice", m, ps, plist, None, (plane, h)))
                allocs += 1
            dropping.add(ps)
        if allocs > len(self._plane_free):
            return None
        for kind, _m, _ps, _pl, tgt, _b in plans:
            if kind == "merge" and tgt in dropping:
                # the merge target is itself being dropped this edit —
                # ordering hazard; take the full-install path instead
                return None
        if trunk_pos:
            gpos = (
                page * self.spec.joined_rows
                + np.array(trunk_pos, np.int64)
            )
            vals = np.stack([rowmap[p] for p in trunk_pos])
            self._host["joined"][gpos] = vals
            joined = _capped_scatter(
                self._dev.joined, gpos, vals, self._device
            )
            if joined is None:
                return None
            self._dev = self._dev._replace(joined=joined)
            self._mark_hash_dirty(page)
        changed: dict = {}
        for kind, m, ps, plist, tgt, built in plans:
            if kind == "patch":
                jb = self._plane_base(ps)[2]
                lpos = np.array(
                    [jb + 1 + int(np.searchsorted(m.tidx, p))
                     for p in plist],
                    np.int64,
                )
                vals = np.stack([rowmap[p] for p in plist])
                self._host["joined"][lpos] = vals
                joined = _capped_scatter(
                    self._dev.joined, lpos, vals, self._device
                )
                if joined is None:
                    return None
                self._dev = self._dev._replace(joined=joined)
                self._unindex_plane(ps)
                self._plane_hash_dirty.add(ps)
            elif kind == "merge":
                self._plane_incref(tgt)
                changed[m.slot] = tgt
                self.counters["splice_merges"] += 1
                self._plane_decref(ps, from_unsplice=True)
            else:
                plane, h = built
                nps = self._alloc_plane()
                try:
                    self._write_plane(nps, plane, m.n_local)
                except Exception:
                    if nps not in self._plane_free:
                        self._plane_free.insert(0, nps)
                    raise
                self._index_plane(nps, h)
                self._plane_refs[nps] = 1
                changed[m.slot] = nps
                self.counters["splice_unsplices"] += 1
                self._plane_decref(ps, from_unsplice=True)
        if changed:
            newmap = dict(cur)
            newmap.update(changed)
            self._write_splice_rows(tenant, newmap)
            self._tenant_splices[tenant] = newmap
            if pre_flip is not None:
                pre_flip()
            self._flip(tenant, page)
        self._tenant_tables[tenant] = new
        self.counters["patches"] += 1
        return "unsplice" if changed else "patch"

    @must_precede("pre_flip", "_flip")
    def _cow_install(self, tenant, donor, arrays, n_nodes, chash,
                     tables, pre_flip) -> str:
        """The CoW landing sequence: write the private copy into a free
        page (ONE warmed full-slab fused scatter — the clone and the
        patch land together), refresh planes (pre_flip), flip the
        editing tenant's page-table row, and only then decrement the
        donor's refcount — every other sharer serves the untouched
        donor slab throughout (no serving gap)."""
        new_page = self._alloc_page()
        try:
            self._write_slab(
                new_page, self._offset(arrays, n_nodes, new_page),
                n_nodes=n_nodes,
            )
        except Exception:
            self._free.insert(0, new_page)
            raise
        if chash is not None:
            self._index_page(new_page, chash)
        else:
            # clone-then-patch: content hash unknown (computing it
            # would cost the O(slab) pass the clone skipped) — the
            # dedup sweep re-hashes in the background
            self._hash_dirty.add(new_page)
        self._tenant_page[tenant] = new_page
        self._page_refs[new_page] = 1
        self._tenant_tables[tenant] = tables
        if pre_flip is not None:
            pre_flip()
        self._flip(tenant, new_page)
        if _inject_cowrace_bug():
            # TEST-ONLY (cowrace defect): defer the donor decref past
            # the lock release — load_tenant lands it unlocked
            self._cowrace_pending = donor
        else:
            self._decref(donor, from_clone=True)
        self.counters["cow_clones"] += 1
        return "cow"

    def _try_patch(self, tenant, page, old, new, hint) -> bool:
        """Rules-only per-slab patch (the Map.Update analogue inside
        one slab): hinted dense rows / dirty joined tidx rows scatter
        at slab-base-offset positions through the shared fused
        executable.  False -> caller falls back to the slab rewrite."""
        if not hint_trie_unchanged(hint):
            return False
        dirty = np.unique(np.asarray(hint.get("dense", ()), np.int64))
        dirty = dirty[(dirty >= 0) & (dirty < new.rules.shape[0])]
        if self.spec.family == "dense":
            kw, mw, ml, rules, _lv, _tg, _lut, _j = _host_device_layout(
                new, pad=False, with_trie=False
            )
            if rules.dtype != np.uint16 or (
                rules.shape[1] != self.spec.rule_slots * 5
                or kw.shape[0] > self.spec.entries
            ):
                return False
            base = page * self.spec.entries
            rows = dirty[dirty < kw.shape[0]]
            entries = []
            for name, src in zip(
                ("key_words", "mask_words", "mask_len", "rules"),
                (kw, mw, ml, rules),
            ):
                vals = src[rows]
                self._host[name][base + rows] = vals
                entries.append((getattr(self._dev, name), base + rows, vals))
            patched = txn_scatter(entries, self._device)
            if patched is None:
                return False
            self._dev = self._dev._replace(
                **dict(zip(("key_words", "mask_words", "mask_len", "rules"),
                           patched))
            )
            return True
        # ctrie family: seed caches forward, then scatter the dirty
        # joined rows at the slab base
        _seed_ctrie_caches_forward(old, new, dirty)
        pr = _joined_tidx_patch_rows(new, dirty)
        if pr is None:
            return False
        pos, rows = pr
        if len(pos) and (
            int(pos.max()) >= self.spec.joined_rows
            or rows.shape[1] != self._dev.joined.shape[1]
        ):
            return False
        if len(pos) == 0:
            return True
        gpos = page * self.spec.joined_rows + pos
        self._host["joined"][gpos] = rows
        joined = _capped_scatter(self._dev.joined, gpos, rows, self._device)
        if joined is None:
            return False
        self._dev = self._dev._replace(joined=joined)
        return True

    def stage(self, tables: CompiledTables) -> int:
        """Content-addressed staging: hash the canonical bake and, on
        an index HIT, reserve the ALREADY-RESIDENT page (a hold — no
        bake, no device write; N stages of the same baseline cost one
        slab).  On a miss, bake into a free page and index it.  Returns
        the staged page id (reserved until activate/release)."""
        with self._lock:
            if self._spliced:
                dec = self._bake_decomposed(tables)
                if dec is not None:
                    try:
                        return self._stage_spliced(dec)
                    except _PlaneCapacityError:
                        pass  # degrade to whole-slab staging
            arrays, n_nodes, chash = self._bake_canonical(tables)
            hit = self._hash_page.get(chash)
            if hit is not None:
                self._page_holds[hit] = self._page_holds.get(hit, 0) + 1
                self.counters["shared_hits"] += 1
                return hit
            page = self._alloc_page()
            try:
                self._write_slab(
                    page, self._offset(arrays, n_nodes, page),
                    n_nodes=n_nodes,
                )
            except Exception:
                self._free.insert(0, page)
                raise
            self._index_page(page, chash)
            self._page_holds[page] = self._page_holds.get(page, 0) + 1
            return page

    def _stage_spliced(self, dec) -> int:
        """Decomposed staging: hold the shared planes (writing the
        missing ones) plus the trunk page, and record the splice PLAN
        on the page's stack — activate() rederives the plan from the
        tables and consumes it (holds become refs), release() pops it.
        Raises _PlaneCapacityError (rolled back) for the whole-slab
        fallback."""
        trunk_arrays, n_nodes, tkey, metas = dec
        got: list = []
        try:
            for m in metas:
                ps = self._hash_plane.get(m.phash)
                if ps is None:
                    ps = self._alloc_plane()
                    try:
                        self._write_plane(ps, m.plane, m.n_local)
                    except Exception:
                        if ps not in self._plane_free:
                            self._plane_free.insert(0, ps)
                        raise
                    self._index_plane(ps, m.phash)
                else:
                    self.counters["plane_hits"] += 1
                self._plane_holds[ps] = self._plane_holds.get(ps, 0) + 1
                got.append(ps)
        except _PlaneCapacityError:
            for ps in got:
                self._release_plane_hold(ps)
            raise
        hit = self._hash_page.get(tkey)
        if hit is not None:
            self._page_holds[hit] = self._page_holds.get(hit, 0) + 1
            self.counters["shared_hits"] += 1
            page = hit
        else:
            page = self._alloc_page()
            try:
                self._write_trunk(page, trunk_arrays, n_nodes, tkey)
            except Exception:
                self._free.insert(0, page)
                for ps in got:
                    self._release_plane_hold(ps)
                raise
            self._page_holds[page] = self._page_holds.get(page, 0) + 1
        self._stage_plans.setdefault(page, []).append(
            tuple((m.slot, ps) for m, ps in zip(metas, got))
        )
        return page

    def _take_stage_plan(self, page: int, tables):
        """Match + pop the staged splice plan for (page, tables):
        (plan, metas) when this page was splice-staged for these
        tables, else None (whole-slab activate).  The memoized
        decompose plus the held planes' stable hash index make the
        rederivation exact."""
        if not self._spliced or tables is None:
            return None
        plans = self._stage_plans.get(page)
        if not plans:
            return None
        dec = self._bake_decomposed(tables)
        if dec is None:
            return None
        _trunk, _nn, _tkey, metas = dec
        want = tuple(
            (m.slot, self._hash_plane.get(m.phash)) for m in metas
        )
        if any(ps is None for _slot, ps in want) or want not in plans:
            return None
        plans.remove(want)
        if not plans:
            self._stage_plans.pop(page, None)
        return want, metas

    def _activate_spliced(self, tenant: int, page: int, tables,
                          plan, metas) -> None:
        """Activate a splice-staged page: consume the plane holds into
        splice-row references, write the tenant's splice rows to the
        inactive bank, and publish page + bank in ONE flip — the
        spliced hot-swap stays O(1) page-table scatter + K splice
        rows."""
        if page in self._free:
            self._free.remove(page)
            self._hash_dirty.add(page)
        h = self._page_holds.get(page, 0)
        if h:  # consume one stage reservation
            if h == 1:
                self._page_holds.pop(page, None)
            else:
                self._page_holds[page] = h - 1
        old_page = self._tenant_page.get(tenant)
        old_map = dict(self._tenant_splices.get(tenant) or {})
        slot_map: dict = {}
        for slot, ps in plan:
            self._plane_incref(ps)
            self._release_plane_hold(ps)
            slot_map[slot] = ps
        self._write_splice_rows(tenant, slot_map)
        self._tenant_splices[tenant] = slot_map
        self._tenant_splice_meta[tenant] = metas
        self._tenant_page[tenant] = page
        self._tenant_tables[tenant] = tables
        if old_page != page:
            self._incref(page)
        self._flip(
            tenant, page,
            _inject=_inject_pageflip_bug() and old_page is not None,
        )
        if old_page is not None and old_page != page:
            self._decref(old_page)
        for ps in old_map.values():
            self._plane_decref(ps)
        self.counters["swaps"] += 1

    def release(self, page: int) -> None:
        """Drop one staged-but-never-activated reservation; the page
        frees when no references and no other holds remain.  On a
        spliced arena a splice-staged reservation also releases its
        plan's plane holds (plans pop LIFO per page)."""
        with self._lock:
            plans = self._stage_plans.get(page)
            if plans:
                plan = plans.pop()
                if not plans:
                    self._stage_plans.pop(page, None)
                for _slot, ps in plan:
                    self._release_plane_hold(ps)
            h = self._page_holds.get(page, 0)
            if h <= 0:
                return
            if h == 1:
                self._page_holds.pop(page, None)
            else:
                self._page_holds[page] = h - 1
            if (
                self._page_refs.get(page, 0) == 0
                and self._page_holds.get(page, 0) == 0
            ):
                self._release_page(page)

    def activate(self, tenant: int, page: int,
                 tables: Optional[CompiledTables] = None) -> None:
        """Hot-swap: flip the tenant's page-table row to a staged (or
        shared) page — O(1) scatter — bump its refcount, and decrement
        the previous slab's.  THE measured swap path of bench_tenant.
        Activating a page live for ANOTHER tenant is sharing, not an
        error: both tenants' rows reference one refcounted slab."""
        self._check_tenant(tenant)
        with self._lock:
            taken = self._take_stage_plan(page, tables)
            if taken is not None:
                return self._activate_spliced(
                    tenant, page, tables, taken[0], taken[1]
                )
            # a re-activated page may sit on the free list (the
            # ping-pong standby pattern drops the previous page to
            # refcount 0 on each flip): claim it back — the slab bytes
            # persisted — and mark it for a dedup re-hash
            if page in self._free:
                self._free.remove(page)
                self._hash_dirty.add(page)
            h = self._page_holds.get(page, 0)
            if h:  # consume one stage reservation
                if h == 1:
                    self._page_holds.pop(page, None)
                else:
                    self._page_holds[page] = h - 1
            old_page = self._tenant_page.get(tenant)
            self._tenant_page[tenant] = page
            if tables is not None:
                self._tenant_tables[tenant] = tables
            else:
                # the previous table no longer describes the slab now
                # serving; a stale record would let a later CoW patch
                # apply against the PRE-swap ruleset — drop it (the
                # canonical mirror keeps the page movable regardless)
                self._tenant_tables.pop(tenant, None)
            if old_page != page:
                self._incref(page)
            # the injected pageflip defect fires ONLY on the swap of an
            # already-resident tenant — the exact transition the
            # statecheck acceptance gate must prove is covered
            self._flip(
                tenant, page,
                _inject=_inject_pageflip_bug() and old_page is not None,
            )
            if old_page is not None and old_page != page:
                self._decref(old_page)
            if self._spliced and self._tenant_splices.get(tenant):
                # the tenant now serves whole-slab content; its splice
                # rows are unread (untagged l0) — release the planes
                self._drop_tenant_planes(tenant)
            self.counters["swaps"] += 1

    def swap_tenant(self, tenant: int, tables: CompiledTables) -> None:
        """stage + activate in one call (the non-prestaged swap)."""
        page = self.stage(tables)
        self.activate(tenant, page, tables)

    def destroy_tenant(self, tenant: int) -> None:
        """Flip the tenant's row to -1 and drop its reference: a page
        SHARED with other tenants survives (they keep serving it); a
        private page frees."""
        self._check_tenant(tenant)
        with self._lock:
            page = self._tenant_page.pop(tenant, None)
            self._tenant_tables.pop(tenant, None)
            self._flip(tenant, -1)
            if page is not None:
                self._decref(page)
            if self._spliced:
                self._drop_tenant_planes(tenant)
                self._tenant_bank.pop(tenant, None)
            self.counters["destroys"] += 1

    def compact(self) -> int:
        """Repack live slabs into the lowest-numbered pages so a long
        create/destroy churn leaves the occupied region contiguous.
        Each move rebakes the page from its CANONICAL mirror (no tenant
        tables needed — shared and tables-less pages move too), then
        flips EVERY sharer's page-table row; the donor page is
        reclaimed only after the last row has flipped, so there is no
        serving gap (rows flip one warmed scatter at a time, but both
        pages hold identical content throughout the window).  Staged
        pages (live holds) are pinned: their page id is a reservation
        some caller will activate.  Returns tenant rows moved."""
        moved = 0
        with self._lock:
            while True:
                live = sorted(
                    p for p in self._page_refs
                    if self._page_holds.get(p, 0) == 0
                )
                src = tgt = None
                for p in reversed(live):
                    lower = [f for f in self._free if f < p]
                    if lower:
                        src, tgt = p, min(lower)
                        break
                if src is None:
                    break
                arrays = tuple(
                    np.array(a, copy=True)
                    for a in self._canonical_of_page(src)
                )
                n_nodes = self._page_nnodes.get(src, 0)
                self._free.remove(tgt)
                self._write_slab(
                    tgt, self._offset(arrays, n_nodes, tgt),
                    n_nodes=n_nodes,
                )
                # transfer refcount + hash-index identity to the new
                # page BEFORE the flips (bookkeeping must never lag the
                # device rows)
                self._page_refs[tgt] = self._page_refs.pop(src)
                if src in self._page_decomposed:
                    # the moved slab is a trunk; the flag (like
                    # _page_nnodes) persists on src for claim-back
                    self._page_decomposed.add(tgt)
                chash = self._page_hash.pop(src, None)
                if chash is not None and self._hash_page.get(chash) == src:
                    self._hash_page[chash] = tgt
                    self._page_hash[tgt] = chash
                elif src in self._hash_dirty:
                    self._hash_dirty.discard(src)
                    self._hash_dirty.add(tgt)
                sharers = sorted(
                    t for t, p in self._tenant_page.items() if p == src
                )
                for t in sharers:
                    self._tenant_page[t] = tgt
                    self._flip(t, tgt)
                    moved += 1
                # every sharer's row has flipped; only now reclaim
                if src not in self._free:
                    self._free.append(src)
            self._free.sort()
            if moved:
                self.counters["compactions"] += 1
        return moved

    def dedup_sweep(self, limit: Optional[int] = None) -> dict:
        """Background re-merge (the lazy half of content addressing):
        re-hash pages whose content hash went stale (in-place patch,
        CoW clone, free-list claim-back), re-index them, and MERGE
        pages whose content re-converged with an already-indexed page —
        every tenant of the duplicate flips onto the canonical page
        (warmed 1-row scatters, old slab serves until its row flips),
        then the duplicate frees.  Staged pages re-index but never
        merge away (their page id is a live reservation).  Compile-free
        by construction.  Returns {"hashed", "merged", "moved"} —
        ``moved`` lists tenant ids whose physical page changed, so the
        classifier wrapper can re-steer flow slabs."""
        hashed = 0
        moved: list = []
        with self._lock:
            dirty = sorted(self._hash_dirty)
            if limit is not None:
                dirty = dirty[: max(int(limit), 0)]
            for page in dirty:
                if (
                    self._page_refs.get(page, 0) == 0
                    and self._page_holds.get(page, 0) == 0
                ):
                    self._hash_dirty.discard(page)
                    continue
                chash = slab_content_hash(
                    self._canonical_of_page(page),
                    self._page_nnodes.get(page, 0),
                )
                if page in self._page_decomposed:
                    # trunk slabs hash in their own domain: a trunk
                    # must never dedup against a whole slab of
                    # coincidentally-equal bytes (their l0 tags mean
                    # different things)
                    chash = b"T" + chash
                hashed += 1
                cur = self._hash_page.get(chash)
                if cur is None or cur == page:
                    self._index_page(page, chash)
                    continue
                if self._page_holds.get(page, 0):
                    self._hash_dirty.discard(page)
                    continue
                sharers = sorted(
                    t for t, p in self._tenant_page.items() if p == page
                )
                for t in sharers:
                    self._tenant_page[t] = cur
                    self._incref(cur)
                    self._flip(t, cur)
                    self._decref(page)
                    moved.append(t)
                self._hash_dirty.discard(page)
                if sharers:
                    self.counters["dedup_merges"] += 1
            plane_merged = 0
            if self._spliced:
                plane_merged = self._dedup_planes(limit)
        rep = {"hashed": hashed, "merged": len(moved), "moved": moved}
        if self._spliced:
            rep["plane_merged"] = plane_merged
        return rep

    def _dedup_planes(self, limit: Optional[int] = None) -> int:
        """The plane half of dedup_sweep: re-hash hash-dirty planes
        (in-place plane patches), re-index them, and MERGE planes whose
        content re-converged — every splice row of the duplicate
        repoints at the canonical plane (K-row bank write + 1-row flip
        per affected tenant, old plane serves until its rows flip),
        then the duplicate frees.  Held planes re-index but never merge
        away.  Returns planes merged."""
        merged = 0
        pdirty = sorted(self._plane_hash_dirty)
        if limit is not None:
            pdirty = pdirty[: max(int(limit), 0)]
        for ps in pdirty:
            if (
                self._plane_refs.get(ps, 0) == 0
                and self._plane_holds.get(ps, 0) == 0
            ):
                self._plane_hash_dirty.discard(ps)
                continue
            pn, pt, pj, n_local = self._canonical_of_plane(ps)
            h = slab_content_hash((pn, pt, pj), n_local)
            cur = self._hash_plane.get(h)
            if cur is None or cur == ps:
                self._index_plane(ps, h)
                continue
            if self._plane_holds.get(ps, 0):
                self._plane_hash_dirty.discard(ps)
                continue
            affected = sorted(
                t for t, smap in self._tenant_splices.items()
                if ps in smap.values()
            )
            for t in affected:
                smap = self._tenant_splices[t]
                newmap: dict = {}
                for slot, v in smap.items():
                    if v == ps:
                        self._plane_incref(cur)
                        newmap[slot] = cur
                    else:
                        newmap[slot] = v
                self._write_splice_rows(t, newmap)
                self._tenant_splices[t] = newmap
                self._flip(t, self._tenant_page[t])
                for v in smap.values():
                    if v == ps:
                        self._plane_decref(ps)
            self._plane_hash_dirty.discard(ps)
            if affected:
                merged += 1
                self.counters["splice_merges"] += 1
        return merged


# === stateful flow tier (device-resident connection tracking) ================
#
# The exact-match verdict cache in front of the LPM + rule scan (ISSUE-11,
# the SDN flow-table pattern): a W-way set-associative hash table in fixed
# -shape JAX tensors, keyed by the FULL set of verdict-relevant packet
# fields (tenant, ifindex, source IP words, proto, dst_port, icmp
# type/code, kind, l4_ok), so a hit can serve the cached res16 verdict
# with bit-identical semantics to the stateless path — the invariant the
# flow statecheck configs and bench_flow gate on.  Layout is one packed
# matrix (a row window per field, C = pages * slab_entries slots) with
# per-tenant SLABS: the per-packet tenant column steers the slot range
# exactly the way the arena page table steers classification, and the
# key embeds the tenant id so a paging bug can never serve one tenant's
# verdict to another (isolation is key-level, not just slab-level).
#
# Mutations are all deterministic scatter forms (add / max / min / set at
# per-slot-unique winner lanes), so the numpy host model
# (infw.flow.HostFlowModel) replays them bit-exactly — the model-checker
# compares device columns against the model after every settled op.
#
# Invalidation is GENERATIONAL: every entry records the per-tenant
# ruleset generation at insert time and a hit requires it to still match
# ``gens[tenant]`` — a patch transaction, tenant swap or full reload
# bumps the generation (backend/tpu.py load_tables / tenant lifecycle)
# and every resident flow verdict of that tenant goes stale at once,
# with no O(table) flush on the mutation path.

#: TCP flag bits of the optional per-packet flags column (PacketBatch
#: .tcp_flags); 0 (the default when the ingest source carries no flags)
#: degrades the TCP model to established-on-first-packet.
TCP_FIN = 0x01
TCP_SYN = 0x02
TCP_RST = 0x04
TCP_ACK = 0x10

#: flow entry states (the TCP-state bitmap column): EMPTY slots are
#: free; NEW = TCP flow that has only shown a pure SYN (tracked but NOT
#: serve-eligible — SYN floods never graduate into the fast path); EST
#: and FIN short-circuit classification.
FLOW_EMPTY = 0
FLOW_NEW = 1
FLOW_EST = 2
FLOW_FIN = 3

FLOW_KEY_WORDS = 8


#: rows of the packed flow-state matrix (FlowTable.state): the key
#: words [tenant, ifindex, ip0..3, m0, m1] in rows 0-7, then the
#: mutable fields, each a row window, then one pad row that stays 0
FLOW_SE = 8    # [FLOW_* state, last-seen epoch]
FLOW_VG = 10   # [cached res16 verdict, tenant gen]
FLOW_CNT = 12  # [pkts, sum(len>>8), sum(len&0xFF)]
FLOW_ROWS = 16

#: field -> (first row, end row) of the packed matrix; the host side
#: (FlowTier.flow_columns, HostFlowModel) keeps one (C, k) column each
FLOW_FIELDS = {
    "keys": (0, FLOW_KEY_WORDS),
    "se": (FLOW_SE, FLOW_SE + 2),
    "vg": (FLOW_VG, FLOW_VG + 2),
    "cnt": (FLOW_CNT, FLOW_CNT + 3),
}


class FlowTable(NamedTuple):
    """Device-resident flow state (C = pages * slab_entries slots): ONE
    (FLOW_ROWS, C) int32 matrix, stored slot-minor, with the row map of
    FLOW_FIELDS (the key words are bitcast uint32).  A probe or insert
    reads a lane's candidate slots with one way-gather, yielding
    (FLOW_ROWS, W, B) planes with the batch on the minor axis, and
    writes a slot's whole column with one scatter: a TPU gather or
    scatter is paid per index far more than per row it moves, so the
    op count is what the flow tier's cost scales with."""

    state: jax.Array  # (FLOW_ROWS, C) int32


def flow_key_words(batch: DeviceBatch, tenant: jax.Array) -> jax.Array:
    """(8, B) uint32 exact-match key planes covering every field the
    verdict depends on (pkt_len only feeds statistics, never the
    verdict)."""
    m0 = (
        (batch.proto.astype(jnp.uint32) & 0xFF)
        | ((batch.dst_port.astype(jnp.uint32) & 0xFFFF) << 8)
        | ((batch.kind.astype(jnp.uint32) & 3) << 24)
        | ((batch.l4_ok.astype(jnp.uint32) & 1) << 26)
    )
    m1 = (batch.icmp_type.astype(jnp.uint32) & 0xFF) | (
        (batch.icmp_code.astype(jnp.uint32) & 0xFF) << 8
    )
    return jnp.stack(
        [
            tenant.astype(jnp.uint32),
            batch.ifindex.astype(jnp.uint32),
            batch.ip_words[:, 0].astype(jnp.uint32),
            batch.ip_words[:, 1].astype(jnp.uint32),
            batch.ip_words[:, 2].astype(jnp.uint32),
            batch.ip_words[:, 3].astype(jnp.uint32),
            m0,
            m1,
        ]
    )


def _flow_hash(keys: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """FNV-1a over the 8 key planes -> (h1, h2) uint32; h2 is forced odd
    so the double-hash probe sequence visits distinct slots in a pow2
    slab.  Pure wrapping u32 arithmetic — the numpy model computes the
    identical values."""
    h = jnp.full(keys.shape[1:], 0x811C9DC5, jnp.uint32)
    for w in range(FLOW_KEY_WORDS):
        h = (h ^ keys[w]) * jnp.uint32(0x01000193)
    return h, (h >> 16) | jnp.uint32(1)


def _flow_slots(
    keys: jax.Array, page: jax.Array, *, slab_entries: int, ways: int
) -> jax.Array:
    """(W, B) int32 global candidate slot ids (page-slab-local double
    hashing); ``slab_entries`` must be a power of two."""
    h1, h2 = _flow_hash(keys)
    base = jnp.clip(page, 0) * slab_entries
    mask = jnp.uint32(slab_entries - 1)
    return jnp.stack([
        base + ((h1 + jnp.uint32(w) * h2) & mask).astype(jnp.int32)
        for w in range(ways)
    ])


def _pack_bits32(mask: jax.Array) -> jax.Array:
    """(B,) bool -> (ceil(B/32),) int32 LSB-first bitmap words."""
    b = mask.shape[0]
    nw = -(-b // 32)
    m = jnp.zeros(nw * 32, jnp.uint32).at[: b].set(mask.astype(jnp.uint32))
    words = jnp.sum(
        m.reshape(nw, 32) << jnp.arange(32, dtype=jnp.uint32)[None, :],
        axis=1, dtype=jnp.uint32,
    )
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def unpack_bits32_host(words: np.ndarray, b: int) -> np.ndarray:
    """Host inverse of _pack_bits32 -> (b,) bool."""
    u = np.asarray(words).view(np.uint32)
    bits = (u[:, None] >> np.arange(32, dtype=np.uint32)[None, :]) & 1
    return bits.reshape(-1)[:b].astype(bool)


def _flow_rows(lo: int, vals: jax.Array, fill: int) -> jax.Array:
    """A (FLOW_ROWS, B) scatter update: ``vals`` (k, B) in rows lo..lo+k
    and ``fill``, the combiner's identity, in every other row.  The TPU
    scatters a slot's whole column natively, but expands a window of
    some of its rows into a loop over the indices."""
    k = vals.shape[0]
    return jnp.pad(vals.astype(jnp.int32), ((lo, FLOW_ROWS - lo - k), (0, 0)),
                   constant_values=fill)


def _flow_counts(ln: jax.Array) -> jax.Array:
    """A packet's counter contributions [1, len>>8, len&0xFF] in the
    counter rows of a (FLOW_ROWS, B) add-scatter update."""
    return _flow_rows(FLOW_CNT, jnp.stack(
        [jnp.ones_like(ln), (ln >> 8) & 0xFFFFFF, ln & 0xFF]), 0)


def _flow_probe_parts(
    flow: FlowTable, gens: jax.Array, page_table: jax.Array,
    batch: DeviceBatch, tenant: jax.Array, tflags: jax.Array,
    epoch_now: jax.Array, max_age: jax.Array,
    *, slab_entries: int, ways: int,
):
    """The shared probe body -> (served u32 verdicts, hit mask, stale
    mask, updated FlowTable) — un-fused so the resident serving
    step (jitted_resident_step) can compose the probe with the stateless
    classify and the miss insert inside ONE device program; the classic
    probe dispatch (_flow_probe_core) packs these parts into its fused
    readback buffer bit-identically.

    A hit requires: eligible lane (real IP, l4 parsed, tenant mapped to
    a flow slab), exact 8-word key match, serve-eligible state (>= EST),
    matching tenant generation, and a last-seen epoch within ``max_age``
    of ``epoch_now``.  Hits update per-flow counters/epoch in-kernel and
    apply the RST/FIN teardown transitions; a key match failing ONLY the
    generation check counts as a stale reject (the invalidation metric).
    Per-ruleId statistics for the served lanes derive HOST-side from the
    returned res16 + pkt_len (the wire8 readback contract), so the probe
    ships no stats tensor."""
    C = flow.state.shape[1]
    page = _arena_pages(page_table, tenant)
    keyw = flow_key_words(batch, tenant)
    is_ip = (batch.kind == KIND_IPV4) | (batch.kind == KIND_IPV6)
    elig = is_ip & (batch.l4_ok != 0) & (page >= 0)
    cand = _flow_slots(keyw, page, slab_entries=slab_entries, ways=ways)
    rows = jnp.take(flow.state, cand, axis=1, mode="clip")  # (16, W, B)
    ek = jax.lax.bitcast_convert_type(rows[:FLOW_KEY_WORDS], jnp.uint32)
    ese = rows[FLOW_SE : FLOW_SE + 2]
    evg = rows[FLOW_VG : FLOW_VG + 2]
    match = jnp.all(ek == keyw[:, None, :], axis=0) & elig[None, :]
    live = ese[0] >= FLOW_EST
    mygen = jnp.take(gens, jnp.clip(tenant, 0, gens.shape[0] - 1),
                     mode="clip")
    gen_ok = evg[1] == mygen[None, :]
    fresh = (epoch_now - ese[1]) <= max_age
    hit_w = match & live & gen_ok & fresh
    stale_w = match & live & fresh & ~gen_ok
    W = ways
    widx = jnp.arange(W, dtype=jnp.int32)[:, None]
    first = jnp.min(jnp.where(hit_w, widx, W), axis=0)
    hit = first < W
    at_first = widx == first[None, :]
    sel = jnp.sum(jnp.where(at_first, cand, 0), axis=0)
    stale = jnp.any(stale_w, axis=0) & ~hit
    slot = jnp.where(hit, sel, C)  # C = dropped by scatter mode="drop"

    served = jnp.where(
        hit, jnp.sum(jnp.where(at_first, evg[0], 0), axis=0), 0
    ).astype(jnp.uint32)

    state = flow.state.at[:, slot].add(_flow_counts(batch.pkt_len),
                                       mode="drop")
    is_tcp = batch.proto == IPPROTO_TCP
    fin = is_tcp & ((tflags & TCP_FIN) != 0)
    rst = is_tcp & ((tflags & TCP_RST) != 0)
    # ONE max-scatter carries both the FIN half-close transition and the
    # last-seen refresh (epoch_now >= any stored epoch by monotonicity);
    # one min-scatter applies RST teardown
    i32 = np.iinfo(np.int32)
    state = state.at[:, slot].max(
        _flow_rows(FLOW_SE, jnp.stack([
            jnp.where(hit & fin, FLOW_FIN, -1).astype(jnp.int32),
            jnp.broadcast_to(epoch_now, slot.shape).astype(jnp.int32),
        ]), i32.min),
        mode="drop",
    )
    state = state.at[:, jnp.where(hit & rst, slot, C)].min(
        _flow_rows(FLOW_SE, jnp.stack([
            jnp.full_like(slot, FLOW_EMPTY), jnp.full_like(slot, i32.max),
        ]), i32.max),
        mode="drop",
    )
    return served, hit, stale, FlowTable(state=state)


def _flow_probe_core(
    flow: FlowTable, gens: jax.Array, page_table: jax.Array,
    batch: DeviceBatch, tenant: jax.Array, tflags: jax.Array,
    epoch_now: jax.Array, max_age: jax.Array,
    *, slab_entries: int, ways: int,
):
    """The classic probe dispatch: _flow_probe_parts packed into the
    fused readback buffer -> (fused output, updated FlowTable)."""
    served, hit, stale, updated = _flow_probe_parts(
        flow, gens, page_table, batch, tenant, tflags, epoch_now,
        max_age, slab_entries=slab_entries, ways=ways,
    )
    fused = jnp.concatenate([
        _pack_res16(served.astype(jnp.uint16)),
        _pack_bits32(hit),
        jnp.stack([
            jnp.sum(hit.astype(jnp.int32)),
            jnp.sum(stale.astype(jnp.int32)),
        ]),
    ])
    return fused, updated


def split_flow_probe_outputs(
    arr: np.ndarray, b: int
) -> Tuple[np.ndarray, np.ndarray, int, int]:
    """Host inverse of the probe's fused buffer -> (res16[b], hit mask
    (b,) bool, hits, stale)."""
    nw = (b + 1) // 2
    res16 = unpack_res16_host(arr[:nw], b)
    nh = -(-b // 32)
    hit = unpack_bits32_host(arr[nw : nw + nh], b)
    hits, stale = int(arr[nw + nh]), int(arr[nw + nh + 1])
    return res16, hit, hits, stale


@functools.lru_cache(maxsize=None)
def jitted_flow_probe(slab_entries: int, ways: int):
    """The fused flow-probe dispatch: serve cached verdicts + update
    per-flow state in ONE launch.  Cache keyed on the pool geometry
    statics only — batch shape, occupancy, tenant count and generation
    churn never re-specialize (the zero-recompile flow lifecycle)."""
    def f(flow, gens, page_table, wire, tenant, tflags, epoch_now, max_age):
        return _flow_probe_core(
            flow, gens, page_table, unpack_wire(wire), tenant, tflags,
            epoch_now, max_age, slab_entries=slab_entries, ways=ways,
        )

    return jax.jit(f)


def _flow_insert_core(
    flow: FlowTable, gens: jax.Array, page_table: jax.Array,
    batch: DeviceBatch, tenant: jax.Array, tflags: jax.Array,
    verdict16: jax.Array, epoch_now: jax.Array,
    *, slab_entries: int, ways: int, lane_ok: Optional[jax.Array] = None,
):
    """Batch insert of miss-lane verdicts -> (updated FlowTable, (4,)
    int32 [inserts, evictions, promotes, 0]).

    Way choice per lane: an existing slot holding the SAME key (any
    state/generation — re-insert refreshes verdict+generation), else the
    first EMPTY way, else the way with the OLDEST last-seen epoch (LRU
    eviction, counted when it overwrites a different live key).  One
    WINNER lane per slot (the last eligible lane in batch order) does
    the .set() writes, so duplicate-slot scatters stay deterministic;
    per-flow counters initialize from segment sums over ALL eligible
    lanes that chose the slot.

    ``lane_ok`` (the resident fused step) restricts eligibility to a
    caller-provided lane mask — the in-program form of the host-side
    miss compaction: the classic multi-dispatch path compacts the miss
    lanes into a pow2 bucket before this kernel sees them, the fused
    step instead masks the hit lanes out.  Eligible-lane identity and
    relative order are the same either way, so winner selection and the
    counter segment sums stay bit-identical."""
    C = flow.state.shape[1]
    page = _arena_pages(page_table, tenant)
    keyw = flow_key_words(batch, tenant)
    is_ip = (batch.kind == KIND_IPV4) | (batch.kind == KIND_IPV6)
    is_tcp = batch.proto == IPPROTO_TCP
    syn = is_tcp & ((tflags & TCP_SYN) != 0)
    ack = is_tcp & ((tflags & TCP_ACK) != 0)
    fin = is_tcp & ((tflags & TCP_FIN) != 0)
    rst = is_tcp & ((tflags & TCP_RST) != 0)
    elig = is_ip & (batch.l4_ok != 0) & (page >= 0) & ~rst
    if lane_ok is not None:
        elig = elig & lane_ok
    cand = _flow_slots(keyw, page, slab_entries=slab_entries, ways=ways)
    rows = jnp.take(flow.state, cand, axis=1, mode="clip")  # (16, W, B)
    ek = jax.lax.bitcast_convert_type(rows[:FLOW_KEY_WORDS], jnp.uint32)
    est = rows[FLOW_SE]
    eep = rows[FLOW_SE + 1]
    match_w = jnp.all(ek == keyw[:, None, :], axis=0) & (est > 0)
    empty_w = est == 0
    W = ways
    widx = jnp.arange(W, dtype=jnp.int32)[:, None]
    m_first = jnp.min(jnp.where(match_w, widx, W), axis=0)
    e_first = jnp.min(jnp.where(empty_w, widx, W), axis=0)
    oldest = jnp.argmin(eep, axis=0).astype(jnp.int32)
    way = jnp.where(
        m_first < W, m_first, jnp.where(e_first < W, e_first, oldest)
    )
    at_way = widx == way[None, :]
    slot = jnp.sum(jnp.where(at_way, cand, 0), axis=0)
    matched = m_first < W
    old_state = jnp.sum(jnp.where(at_way, est, 0), axis=0)

    # per slot, in the state's row map (column C takes the ineligible
    # lanes): row 0 the last eligible lane, which wins the .set()
    # writes; the counter rows the seeds, batch contributions summed
    # over ALL eligible lanes (the probe's counter update, reused)
    lane = jnp.arange(slot.shape[0], dtype=jnp.int32)
    idx_e = jnp.where(elig, slot, C)
    acc = jnp.concatenate([jnp.full((1, C + 1), -1, jnp.int32),
                           jnp.zeros((FLOW_ROWS - 1, C + 1), jnp.int32)])
    acc = acc.at[:, idx_e].max(
        _flow_rows(0, lane[None], np.iinfo(np.int32).min), mode="drop")
    acc = acc.at[:, idx_e].add(_flow_counts(batch.pkt_len), mode="drop")
    at_slot = jnp.take(acc, jnp.clip(slot, 0, C), axis=1, mode="clip")
    win = elig & (at_slot[0] == lane)
    idx_w = jnp.where(win, slot, C)

    state_val = jnp.where(
        fin, FLOW_FIN,
        jnp.where(is_tcp & syn & ~ack, FLOW_NEW, FLOW_EST),
    ).astype(jnp.int32)
    mygen = jnp.take(gens, jnp.clip(tenant, 0, gens.shape[0] - 1),
                     mode="clip")
    # every row of the winner's slot in one .set: the key, se and vg
    # rows from this lane, the counter rows (and the pad) from the seeds
    head = jnp.concatenate([
        jax.lax.bitcast_convert_type(keyw, jnp.int32),
        jnp.stack([
            state_val,
            jnp.broadcast_to(epoch_now, slot.shape).astype(jnp.int32),
            (verdict16.astype(jnp.int32)) & 0xFFFF,
            mygen,
        ]),
    ])
    state = flow.state.at[:, idx_w].set(
        jnp.where((jnp.arange(FLOW_ROWS) < FLOW_CNT)[:, None],
                  _flow_rows(0, head, 0), at_slot),
        mode="drop",
    )

    evict = win & ~matched & (old_state > 0)
    promote = win & matched & (old_state == FLOW_NEW) & (
        state_val == FLOW_EST
    )
    counts = jnp.stack([
        jnp.sum(win.astype(jnp.int32)),
        jnp.sum(evict.astype(jnp.int32)),
        jnp.sum(promote.astype(jnp.int32)),
        jnp.int32(0),
    ])
    return FlowTable(state=state), counts


@functools.lru_cache(maxsize=None)
def jitted_flow_insert(slab_entries: int, ways: int):
    def f(flow, gens, page_table, wire, tenant, tflags, verdict16,
          epoch_now):
        return _flow_insert_core(
            flow, gens, page_table, unpack_wire(wire), tenant, tflags,
            verdict16, epoch_now, slab_entries=slab_entries, ways=ways,
        )

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def jitted_flow_age():
    """Epoch-based age sweep over the flow state: entries last seen
    strictly before ``cutoff`` free their slot.  Returns (new
    FlowTable, aged count)."""
    def f(flow, cutoff):
        st, ep = flow.state[FLOW_SE], flow.state[FLOW_SE + 1]
        expire = (st > 0) & (ep < cutoff)
        return (
            FlowTable(state=flow.state.at[FLOW_SE].set(
                jnp.where(expire, FLOW_EMPTY, st))),
            jnp.sum(expire.astype(jnp.int32)),
        )

    return jax.jit(f)


@functools.lru_cache(maxsize=None)
def jitted_flow_occupancy():
    return jax.jit(
        lambda flow: jnp.sum((flow.state[FLOW_SE] > 0).astype(jnp.int32)))


# === resident serving step (zero-copy donated-buffer loop, ISSUE-12) =========
#
# ONE fused device program per admission: wire decode + flow probe +
# stateless classify + verdict merge + device stats + miss insert — the
# in-program composition of the probe-then-classify multi-dispatch plan
# (backend/tpu.py _launch_flow), which pays three launches and two
# blocking host round-trips per admission.  The flow state matrix and
# the epoch scalar are DONATED (jax.jit donate_argnums input-output
# aliasing): XLA writes the updated state back into the very buffers
# the previous dispatch produced, so the steady-state loop performs zero
# flow-state device allocations and the epoch never crosses the link —
# the program increments it on device and hands the aliased buffer to
# the next dispatch.
#
# Bit-identity contract (gated by statecheck's `resident` config, the
# bench_resident oracle gate and tests/test_resident.py): the merged
# verdict vector, the statistics and the post-dispatch flow columns are
# bit-identical to what the multi-dispatch plan produces for the same
# wire chunk — the probe/insert bodies are the SAME functions
# (_flow_probe_parts / _flow_insert_core), the stateless classify is the
# same forward pass over every lane (the hit lanes' results fall out of
# the merge instead of being skipped by host compaction), and the insert
# masks hit lanes via lane_ok instead of host-compacting the misses
# (same eligible-lane set and order -> same winner scatters).


def _resident_step_core(
    flow: FlowTable, gens: jax.Array, page_table: jax.Array,
    epoch: jax.Array, tdev, wire: jax.Array, tenant: jax.Array,
    tflags: jax.Array, max_age: jax.Array, ov=None, sk=None, sc=None,
    model=None, tparams=None, pay=None, plen=None, ptrans=None,
    pmatch=None, pmode=None,
    *, slab_entries: int, ways: int, path: str, v4_only: bool,
    depth: Optional[int], d_max: int, sketch=None, score=None,
    payload=None,
):
    with jax.named_scope("wire_decode"):
        batch = unpack_wire(wire)
    with jax.named_scope("flow_probe"):
        e1 = (epoch + jnp.int32(1)).astype(jnp.int32)
        served, hit, stale, flow1 = _flow_probe_parts(
            flow, gens, page_table, batch, tenant, tflags, e1, max_age,
            slab_entries=slab_entries, ways=ways,
        )
    # stateless classify of EVERY lane against the SAME table snapshot:
    # the hit lanes' stateless results are discarded by the merge below
    # (at the small-batch rungs the extra lanes are far cheaper than a
    # second launch + host compaction round-trip)
    if path == "ctrie":
        if ov is not None:
            res, _x, _s = classify_ctrie_with_overlay(
                tdev, ov, batch, d_max=d_max
            )
        else:
            res, _x, _s = classify_ctrie(tdev, batch, d_max=d_max)
    else:
        t = tdev
        use_trie = path == "trie"
        if use_trie:
            t = t._replace(trie_levels=t.trie_levels[
                : walk_levels(len(t.trie_levels), v4_only, depth)])
        if ov is not None:
            res, _x, _s = classify_with_overlay(t, ov, batch,
                                                use_trie=use_trie)
        else:
            res, _x, _s = classify(t, batch, use_trie=use_trie)
    # the wire contract (check_wire_ruleids at plan time) guarantees the
    # stateless result fits 16 bits, exactly like the fused wire path
    with jax.named_scope("merge"):
        merged = jnp.where(hit, served, res & 0xFFFF).astype(jnp.uint32)
    sc2 = score_out = anom = None
    if score is not None:
        # MXU anomaly scoring (ISSUE-14): the feature update + forest/
        # MLP inference + per-tenant policy ride the SAME device
        # program, on the merged RULE verdicts (pre-policy — features
        # never read their own rewrites).  In enforce mode ``merged2``
        # carries the rewritten verdicts, and it is what the miss
        # insert below caches — mitigation sticks to the flow, and a
        # model swap invalidates it through the very generation stamps
        # a rule patch uses.
        from . import mxu_score as mxu_score_mod

        with jax.named_scope("score"):
            sc2, score_out, anom, merged2 = mxu_score_mod._score_update_core(
                sc, batch, tenant, tflags, merged, model, tparams, spec=score,
            )
            merged2 = merged2.astype(jnp.uint32)
    else:
        merged2 = merged
    pay_hit = pay_rw = None
    if payload is not None:
        # payload-matching tier (ISSUE-19): the Aho-Corasick DFA walk
        # over the ring-sliced payload-prefix column rides the SAME
        # device program, as the FOURTH verdict-merge tier — after the
        # score rewrite, with the same guardrails (failsafe lanes and
        # existing rule Denies are never overridden).  The automaton
        # operands (ptrans/pmatch) and the shadow/enforce scalar are
        # persistent VALUE operands — a pattern hot-swap replaces them
        # whole with spec-fixed shapes, so swapping never recompiles
        # and never disturbs the donation aliasing of the state that
        # precedes them in the operand order.
        from . import acmatch as acmatch_mod

        with jax.named_scope("payload"):
            bitmap = acmatch_mod._acmatch_core(
                ptrans, pmatch, pay, plen, spec=payload
            )
            merged3, pay_hit, pay_rw = acmatch_mod._payload_merge_core(
                merged2, bitmap, pmode, batch.proto, batch.dst_port
            )
            merged3 = merged3.astype(jnp.uint32)
    else:
        merged3 = merged2
    with jax.named_scope("flow_insert"):
        flow2, counts = _flow_insert_core(
            flow1, gens, page_table, batch, tenant, tflags, merged3, e1,
            slab_entries=slab_entries, ways=ways, lane_ok=~hit,
        )
    # res16-only readback (the wire8 contract): per-ruleId statistics
    # derive HOST-side from the merged verdicts + the pkt_len column
    # that never left the host — shipping the (1024, 6) stats tensor
    # would cost ~24 KB per admission, dwarfing the ~100 B the resident
    # loop actually needs back
    with jax.named_scope("pack_out"):
        parts = [
            _pack_res16(merged3.astype(jnp.uint16)),
            _pack_bits32(hit),
            jnp.stack([
                jnp.sum(hit.astype(jnp.int32)),
                jnp.sum(stale.astype(jnp.int32)),
            ]),
            counts,
        ]
        if score is not None:
            # scoring extension of the fused readback: the anomaly
            # bitmap (b/32 words) and the int16-saturated per-lane
            # scores (b/2 words) — what shadow records, the precision/
            # recall legs and the cross-path identity gate read;
            # internal state stays exact int32 on device
            s16 = jnp.clip(score_out, -32768, 32767).astype(jnp.int16)
            parts.append(_pack_bits32(anom))
            parts.append(_pack_res16(s16.astype(jnp.uint16)))
        if payload is not None:
            # payload extension of the fused readback: the matched-lane
            # and rewritten-lane bitmaps (b/32 words each) — the
            # counters and the classic-path identity gate read these;
            # the FULL (b, PW) match bitmap never crosses the link on
            # the resident path (the standalone jitted_acmatch launch
            # serves statecheck's bit-identity compare instead)
            parts.append(_pack_bits32(pay_hit))
            parts.append(_pack_bits32(pay_rw))
        fused = jnp.concatenate(parts)
    if sketch is not None:
        # device-resident telemetry (ISSUE-13): the sketch update rides
        # the SAME device program as the verdicts — count-min + top-K +
        # tenant-counter scatters over the SERVED res16 (post-policy,
        # so telemetry counts what the dataplane actually did), donated
        # like the flow columns, nothing read back (the decimated drain
        # is the only D2H the telemetry plane ever pays)
        from . import sketch as sketch_mod

        with jax.named_scope("sketch"):
            sk2 = sketch_mod._sketch_update_core(
                sk, batch, tenant, tflags, merged3, spec=sketch,
            )
        if score is not None:
            return flow2, e1, sk2, sc2, fused
        return flow2, e1, sk2, fused
    if score is not None:
        return flow2, e1, sc2, fused
    return flow2, e1, fused


#: the resident step's stages: each is a jax.named_scope of
#: _resident_step_core (lpm and rule_scan inside classify and its
#: twins), so each device op of the compiled step carries one in its
#: op_name metadata; score, payload and sketch exist only where those
#: tiers ride the step.  The trie walks split their lpm into lpm_root
#: (the DIR-16 root gather), lpm_level (each level's node-row gather
#: and rank) and lpm_resolve (the target or joined-row gather); the
#: dense compare stays in lpm alone
RESIDENT_STAGES = (
    "wire_decode", "flow_probe", "lpm", "lpm_root", "lpm_level",
    "lpm_resolve", "rule_scan", "merge", "score", "payload", "flow_insert",
    "pack_out", "sketch",
)

#: HLO module text: a computation's header, one of its instructions (and
#: the name it defines), a name it refers to, the computation a fusion
#: calls, and the op_name of an instruction's metadata
_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s+(ROOT\s+)?%?([\w.\-]+)\s+=\s")
_HLO_REF = re.compile(r"%([\w.\-]+)")
_HLO_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_HLO_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="((?:[^"\\]|\\.)*)"')


def _op_name_stage(op_name: str) -> Optional[str]:
    for part in reversed(op_name.split("/")):
        if part in RESIDENT_STAGES:
            return part
    return None


def hlo_stage_map(hlo_text: str) -> dict:
    """{instruction name: stage} for every instruction of an optimized
    HLO module's text (``compiled.as_text()``, or a module the profiler
    recorded).  Instruction names are the op names a device trace shows,
    so this ties device time to stages.

    An instruction's stage is the innermost scope of its op_name that
    names one of RESIDENT_STAGES.  The compiler makes some instructions
    with no metadata (layout copies, the fusions of some rewrites): a
    fusion takes its fused computation's stage (its root's, else the
    most common one inside); any other takes the stage of the first of
    its users that has one, else of the first of its operands.  An
    instruction left with none maps to ``"other"``."""
    own, comp_of, refs, calls, roots, order = {}, {}, {}, {}, {}, []
    comps = set()
    comp = None
    for line in hlo_text.splitlines():
        m = _HLO_INSTRUCTION.match(line)
        if m is None:
            h = _HLO_COMPUTATION.match(line)
            if h is not None:
                comp = h.group(1)
                comps.add(comp)
            continue
        name, rest = m.group(2), line[m.end():]
        order.append(name)
        comp_of[name] = comp
        if m.group(1):
            roots[comp] = name
        op = _HLO_OP_NAME.search(rest)
        own[name] = _op_name_stage(op.group(1)) if op else None
        c = _HLO_CALLS.search(rest)
        calls[name] = c.group(1) if c else None
        refs[name] = _HLO_REF.findall(rest.split(", metadata=")[0])
    stage = dict(own)
    members: dict = {}
    for name in order:
        members.setdefault(comp_of[name], []).append(name)
    for name in order:
        callee = calls[name]
        if stage[name] is None and callee in members:
            inner = [own[i] for i in members[callee] if own[i]]
            stage[name] = own.get(roots.get(callee)) or (
                max(set(inner), key=inner.count) if inner else None)
    operands = {n: [r for r in refs[n] if r in own and r not in comps]
                for n in order}
    users: dict = {}
    caller: dict = {}  # called computation -> an instruction that calls it
    for name in order:
        for r in operands[name]:
            users.setdefault(r, []).append(name)
        for r in refs[name]:
            if r in comps:
                caller.setdefault(r, name)
    # text order is a topological order: users resolve back to front,
    # operands front to back; what is left inside a called computation
    # (a fusion's body, a reducer) takes its caller's stage
    for _ in range(len(comps) + 1):
        for name in reversed(order):
            if stage[name] is None:
                stage[name] = next(
                    (stage[u] for u in users.get(name, ()) if stage[u]), None)
        for name in order:
            if stage[name] is None:
                stage[name] = next(
                    (stage[o] for o in operands[name] if stage[o]), None)
        left = [n for n in order if stage[n] is None
                and stage.get(caller.get(comp_of[n]))]
        if not left:
            break
        for name in left:
            stage[name] = stage[caller[comp_of[name]]]
    return {n: stage[n] or "other" for n in order}


def split_resident_outputs(arr: np.ndarray, b: int):
    """Host inverse of the resident step's fused buffer -> (res16[b],
    hit mask, hits, stale, (inserts, evictions, promotes)).  ~100 B per
    admission — statistics derive host-side (the wire8 contract)."""
    nw = (b + 1) // 2
    nh = -(-b // 32)
    res16 = unpack_res16_host(arr[:nw], b)
    hit = unpack_bits32_host(arr[nw : nw + nh], b)
    hits, stale = int(arr[nw + nh]), int(arr[nw + nh + 1])
    counts = tuple(int(x) for x in arr[nw + nh + 2 : nw + nh + 5])
    return res16, hit, hits, stale, counts


def split_resident_score_outputs(arr: np.ndarray, b: int):
    """Host inverse of the SCORING resident step's fused buffer ->
    (res16[b] — policy-rewritten in enforce mode, hit mask, hits,
    stale, (inserts, evictions, promotes), anom mask[b], scores[b]
    int32 from the int16-saturated readback)."""
    nw = (b + 1) // 2
    nh = -(-b // 32)
    res16, hit, hits, stale, counts = split_resident_outputs(
        arr[: nw + nh + 6], b
    )
    base = nw + nh + 6
    anom = unpack_bits32_host(arr[base : base + nh], b)
    s16 = unpack_res16_host(arr[base + nh : base + nh + nw], b)
    scores = s16.astype(np.uint16).astype(np.int16).astype(np.int32)
    return res16, hit, hits, stale, counts, anom, scores


def split_resident_payload_outputs(arr: np.ndarray, b: int,
                                   score: bool = False):
    """Host inverse of the PAYLOAD resident step's fused buffer: the
    base (or scoring) tuple with the matched-lane and rewritten-lane
    bitmaps appended -> (..., pay_hit[b], pay_rewrote[b]).  The payload
    extension is the LAST 2*ceil(b/32) words regardless of which other
    tiers ride the program, so the slice anchors from the end."""
    arr = np.asarray(arr)
    nh = -(-b // 32)
    base, tail = arr[: arr.shape[0] - 2 * nh], arr[arr.shape[0] - 2 * nh:]
    head = (
        split_resident_score_outputs(base, b) if score
        else split_resident_outputs(base, b)
    )
    pay_hit = unpack_bits32_host(tail[:nh], b)
    pay_rw = unpack_bits32_host(tail[nh:], b)
    return head + (pay_hit, pay_rw)


#: donated operand positions of the resident step — the flow state
#: (FlowTable) and the device epoch scalar; declared here so the entrypoint
#: registry and the jaxcheck donation lint share one source of truth
RESIDENT_DONATE_ARGNUMS = (0, 3)

#: the telemetry variant additionally donates the sketch tensors
#: (operand 4, right after the epoch) — telemetry state is rewritten in
#: place every admission exactly like the flow columns
RESIDENT_SKETCH_DONATE_ARGNUMS = (0, 3, 4)

#: the anomaly-scoring variant donates the score state at position 4
#: (or 5 when the sketch tensors are present too); the model value and
#: tparams operands that follow it are persistent, NOT donated
RESIDENT_SCORE_DONATE_ARGNUMS = (0, 3, 4)
RESIDENT_SKETCH_SCORE_DONATE_ARGNUMS = (0, 3, 4, 5)


def resident_donate_argnums(sketch: bool, score: bool) -> tuple:
    """The donated positions for a (sketch?, score?) resident variant —
    one source of truth for the factory below, the entrypoint registry
    and the jaxcheck donation lint."""
    donate = [0, 3]
    pos = 4
    if sketch:
        donate.append(pos)
        pos += 1
    if score:
        donate.append(pos)
    return tuple(donate)


@functools.lru_cache(maxsize=None)
def jitted_resident_step(
    slab_entries: int, ways: int, path: str, v4_only: bool = False,
    depth: Optional[int] = None, d_max: int = 0, overlay: bool = False,
    sketch=None, score=None, payload=None,
):
    """The resident fused executable, cache-keyed on (flow geometry,
    layout path, wire format specialization, sketch/score geometry) —
    batch shape and the trie level count specialize through jit's
    shape/pytree keying, so a warmed ladder serves every admission with
    zero recompiles (the same contract as every other serving factory,
    test-pinned).

    Operand order: f(flow, gens, page_table, epoch, [sk], [sc, model,
    tparams], tables[, overlay], wire, tenant, tflags, max_age) ->
    (flow', epoch', [sk'], [sc'], fused).  ``flow``, ``epoch`` and the
    optional sketch/score states are DONATED: the returned tensors
    alias the input buffers in place (XLA input_output_alias; the
    jaxcheck donation lint fails if a donated buffer is silently
    copied), so the caller must treat the inputs as consumed and chain
    the returned arrays into the next dispatch.  The score model/
    tparams operands are persistent device arrays — a model hot swap
    replaces them whole with spec-fixed shapes, so swapping never
    recompiles.

    The payload variant (``payload`` = an acmatch.AcSpec) extends the
    order to f(flow, gens, page_table, epoch, [sk], [sc, model,
    tparams], [ptrans, pmatch, pmode], tables[, overlay], wire, pay,
    plen, tenant, tflags, max_age): the automaton operands sit AFTER
    every donated position, so the fourth tier never perturbs the
    aliasing contract, and a pattern hot-swap is a value-operand
    replacement exactly like a score-model swap."""
    kw = dict(slab_entries=slab_entries, ways=ways, path=path,
              v4_only=v4_only, depth=depth, d_max=d_max, sketch=sketch,
              score=score, payload=payload)
    has_sk = sketch is not None
    has_sc = score is not None
    has_pay = payload is not None

    def resident_step(*args):
        flow, gens, page_table, epoch = args[:4]
        i = 4
        sk = sc = model = tparams = None
        ptrans = pmatch = pmode = None
        if has_sk:
            sk = args[i]
            i += 1
        if has_sc:
            sc, model, tparams = args[i], args[i + 1], args[i + 2]
            i += 3
        if has_pay:
            ptrans, pmatch, pmode = args[i], args[i + 1], args[i + 2]
            i += 3
        tdev = args[i]
        i += 1
        ov = None
        if overlay:
            ov = args[i]
            i += 1
        if has_pay:
            wire, pay, plen, tenant, tflags, max_age = args[i : i + 6]
        else:
            wire, tenant, tflags, max_age = args[i : i + 4]
            pay = plen = None
        return _resident_step_core(
            flow, gens, page_table, epoch, tdev, wire, tenant, tflags,
            max_age, ov=ov, sk=sk, sc=sc, model=model, tparams=tparams,
            pay=pay, plen=plen, ptrans=ptrans, pmatch=pmatch,
            pmode=pmode, **kw,
        )

    # the function's name is the compiled module's: jit_resident_step
    return jax.jit(resident_step,
                   donate_argnums=resident_donate_argnums(has_sk, has_sc))


# === resident superbatch: the device-side epoch loop (ISSUE-16) ==============
#
# K stacked admissions chewed through in ONE device program: the fused
# step's body runs under a lax.scan (an XLA while loop with stacked
# outs), the donated flow columns / epoch scalar / sketch / score state
# chained through the loop CARRY — no intermediate host round-trips, no
# per-admission Python dispatch.  Bit-identity is by construction: the
# scan body IS _resident_step_core, the same integer-deterministic
# function K sequential jitted_resident_step dispatches run, applied to
# the same carry chain in the same order — verdicts, statistics, flow
# columns and sketch/score state all land bit-identical (pinned by the
# statecheck `pipeline` config and the bench_pipeline identity gate).
# The fused readbacks stack to one (K, L) buffer: the host splits rows
# with resident_fused_host and drains the model mirrors per admission
# in device-epoch order exactly as on the single-step path.


def resident_fused_host(fused) -> np.ndarray:
    """Host view of ONE admission's fused readback: either a bare
    fused buffer (single-step dispatch) or a ``(stack, row)`` pair
    referencing one row of a superbatch's stacked (K, L) readback.
    np.asarray blocks until the dispatch lands — the mirror-queue
    drain's ordering contract."""
    if isinstance(fused, tuple):
        stack, row = fused
        return np.asarray(stack)[int(row)]
    return np.asarray(fused)


@functools.lru_cache(maxsize=None)
def jitted_resident_superbatch(
    slab_entries: int, ways: int, path: str, v4_only: bool = False,
    depth: Optional[int] = None, d_max: int = 0, overlay: bool = False,
    sketch=None, score=None, payload=None,
):
    """The K-admission device epoch program, cache-keyed exactly like
    jitted_resident_step (K and the batch shape specialize through
    jit's shape keying — a warmed (K, B, W) shape recompiles never).

    Operand order matches the single-step factory with the wire/tenant/
    tflags operands STACKED along a leading K axis: f(flow, gens,
    page_table, epoch, [sk], [sc, model, tparams], tables[, overlay],
    wire (K, B, W), tenant (K, B), tflags (K, B), max_age) -> (flow',
    epoch', [sk'], [sc'], fused (K, L)).  Donation is identical to the
    single step (flow, epoch, sketch, score) — XLA aliases the carry
    in place through the while loop, verified against the compiled
    HLO by the jaxcheck donation lint.

    The payload variant stacks the pay/plen columns with the wire:
    f(..., [ptrans, pmatch, pmode], tables[, overlay], wire (K, B, W),
    pay (K, B, L), plen (K, B), tenant, tflags, max_age) — the
    automaton operands stay loop-INVARIANT (closed over by the scan
    body), so K admissions walk one resident copy of the transition
    tensors."""
    kw = dict(slab_entries=slab_entries, ways=ways, path=path,
              v4_only=v4_only, depth=depth, d_max=d_max, sketch=sketch,
              score=score, payload=payload)
    has_sk = sketch is not None
    has_sc = score is not None
    has_pay = payload is not None

    def resident_superbatch(*args):
        flow, gens, page_table, epoch = args[:4]
        i = 4
        sk = sc = model = tparams = None
        ptrans = pmatch = pmode = None
        if has_sk:
            sk = args[i]
            i += 1
        if has_sc:
            sc, model, tparams = args[i], args[i + 1], args[i + 2]
            i += 3
        if has_pay:
            ptrans, pmatch, pmode = args[i], args[i + 1], args[i + 2]
            i += 3
        tdev = args[i]
        i += 1
        ov = None
        if overlay:
            ov = args[i]
            i += 1
        if has_pay:
            wire, pay, plen, tenant, tflags, max_age = args[i : i + 6]
            xs = (wire, pay, plen, tenant, tflags)
        else:
            wire, tenant, tflags, max_age = args[i : i + 4]
            xs = (wire, tenant, tflags)

        def body(carry, xs_row):
            fl, ep, skc, scc = carry
            if has_pay:
                w, py, pl, tn, tf = xs_row
            else:
                w, tn, tf = xs_row
                py = pl = None
            out = _resident_step_core(
                fl, gens, page_table, ep, tdev, w, tn, tf, max_age,
                ov=ov, sk=skc, sc=scc, model=model, tparams=tparams,
                pay=py, plen=pl, ptrans=ptrans, pmatch=pmatch,
                pmode=pmode, **kw,
            )
            fl2, ep2 = out[0], out[1]
            j = 2
            sk2 = sc2 = None
            if has_sk:
                sk2 = out[j]
                j += 1
            if has_sc:
                sc2 = out[j]
                j += 1
            return (fl2, ep2, sk2, sc2), out[-1]

        (flow2, e2, sk2, sc2), fused = jax.lax.scan(
            body, (flow, epoch, sk, sc), xs
        )
        outs = [flow2, e2]
        if has_sk:
            outs.append(sk2)
        if has_sc:
            outs.append(sc2)
        outs.append(fused)
        return tuple(outs)

    return jax.jit(resident_superbatch,
                   donate_argnums=resident_donate_argnums(has_sk, has_sc))
