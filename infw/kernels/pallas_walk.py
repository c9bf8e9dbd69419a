"""Fused Pallas deep-walk kernel: the entire deep v6 poptrie descent —
level walk, popcount-rank child step, joined-targets rules tail — in ONE
Pallas grid pass with the deep-tail working set VMEM-resident.

Why: the XLA trie path (jaxpath.trie_walk_joined) issues one HBM gather
excursion per 8-bit level; full-depth v6 classes run at 19-23 M class/s
vs ~50 M/s for v4, and every deep-heavy adversarial mix converges to that
floor (round-5 verdict weak #3/#4).  The reference hot path's defining
property is ONE lookup with no second excursion
(/root/reference/bpf/ingress_node_firewall_kernel.c:218-258); the
analogues named by PAPERS.md are keeping the whole lookup structure
resident next to the compute (CRAM-lens IP lookup, arxiv 2503.03003) and
fusing the match+action stages in one pass (hXDP, arxiv 2010.14145).

Design (mirrors pallas_dense's proven Mosaic idioms):

- The DIR-16 root level stays an XLA direct-indexed gather
  (_root_stage): it is a single fused gather that beats any in-kernel
  form, and keeping it outside lets the (large, ~0-60%% dense) root array
  stay in HBM.  Everything AFTER the root — the deep descent — runs in
  the kernel.
- Each deep level's poptrie node rows ([child_base, target_base,
  child_bitmap x8, target_bitmap x8] as 72 little-endian bytes, padded
  to one 128-lane tile) are held VMEM-resident as int8 byte planes
  (biased -128 so [0,255] fits s8, the pallas_dense trick).  The
  per-packet node-row fetch is a one-hot s8 MXU matmul — the MXU plays
  the role of the per-level HBM gather; u32 words are rebuilt in-kernel
  from the exact byte sums.
- The popcount-rank child step (implicit poptrie numbering: child id =
  child_base + rank(nib)) is ~60 VPU ops per level, SWAR popcount on
  int32 lanes, statically unrolled over the level count.
- The rules tail reuses the joined-targets layout (jaxpath.build_joined
  positions): the walk's winning POSITION one-hot-gathers a field-major
  byte-plane row of the rule table (rid/act/proto/icmp/port planes, one
  128-wide tile per field) and the ordered first-match scan runs
  in-kernel — match+action fused, nothing between the root gather and
  the final (result, position) leaves the chip.

Deep-tail compression (the VMEM-fit story at the 1M tier):

The kernel serves the depth-steered FULL-DEPTH class (the throughput
floor), so build_walk_tables can extract just that class's working set:
root slots whose depth-LUT requirement exceeds the steering threshold,
plus the complete subtree closure beneath them (whole child ranges are
kept, so the poptrie's implicit contiguous-children numbering — and the
affine position arithmetic of the joined tail — survive renumbering
unchanged).  Levels left empty by the extraction are dropped (the level
count is static, so the kernel unrolls shorter), and the joined rows
compact to the reachable positions.  Measured on the bench tables the
deep-class closure is a small fraction of the full structure — that is
precisely the point: the packets that pay 14 HBM excursions on the XLA
path are the ones whose working set fits VMEM.

Fallback contract: build_walk_tables returns None whenever the layout
cannot hold (wide int32 rules, rule width > 128, joined inactive, VMEM
budget exceeded) and callers keep using the XLA walk — never a refusal,
never a wrong verdict.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..compiler import CompiledTables
from ..constants import (
    IPPROTO_ICMP,
    IPPROTO_ICMPV6,
    IPPROTO_SCTP,
    IPPROTO_TCP,
    IPPROTO_UDP,
    KIND_IPV4,
    KIND_OTHER,
)
from .jaxpath import (
    DeviceBatch,
    _crange_concat,
    build_cpoptrie,
    build_depth_lut,
    build_poptrie,
    finalize,
    fuse_wire_outputs,
    joined_by_tidx,
    joined_layout,
    unpack_wire,
)

BLOCK_B = 256        # packets per grid step
RULE_STRIDE = 128    # field-major rule plane stride (MAX_RULES_PER_TARGET=100)
NUM_FIELDS = 9       # rid, act, proto, itype, icode, ps_hi, ps_lo, pe_hi, pe_lo
LEVEL_ROW_BYTES = 72  # child_base(4) + target_base(4) + cb(32) + tb(32)
LEVEL_ROW_PAD = 128   # one lane tile
#: default VMEM budget for the resident operands (levels + joined planes);
#: v5e scoped VMEM is ~16MB and the kernel needs headroom for the one-hot
#: transients ((Bb, n_l) and (Bb, P) int8) and the (Bb, NUM_FIELDS*128)
#: int32 row block.
DEFAULT_VMEM_BUDGET = 10 * 1024 * 1024


class WalkTables(NamedTuple):
    """Fused-walk device operands.

    ``l0`` is the (possibly extraction-remapped) DIR-16 root level in the
    joined form (target column = joined position), gathered by the XLA
    pre-stage; ``levels`` are the VMEM-resident deep-level byte planes
    ((n_l_pad, 128) int8, biased -128).

    Two tail modes, statically discriminated by ``joined.shape[0]``:

    - **fused tail** (``joined.shape[0] > 1``): ``joined`` holds the
      field-major rule byte-plane matrix ((P_pad, NUM_FIELDS *
      RULE_STRIDE) int8, biased -128) VMEM-resident, and the ordered
      scan runs inside the kernel; ``joined_u16`` is a (1, 1)
      placeholder.
    - **positions tail** (``joined.shape[0] == 1`` placeholder): the
      RULE_STRIDE padding would blow the VMEM budget (wide tables /
      large deep tails — the 1M tier), so the kernel fuses the level
      walk + popcount-rank descent only and emits the winning POSITION;
      the tail is the one XLA fat-row gather from ``joined_u16``
      ((P, 3 + R*5) u16 in HBM, the compacted joined layout) feeding
      jaxpath.rule_scan — still one excursion total, vs one per level.

    The tuple length of ``levels`` and the static joined shapes are part
    of the pytree structure, so jit specializes per depth and mode."""

    l0: jax.Array                     # (n0*65536, 2) int32
    root_lut: jax.Array               # (max_if+1,) int32
    levels: Tuple[jax.Array, ...]     # per level (n_l_pad, 128) int8
    joined: jax.Array                 # byte planes | (1, 1) placeholder
    joined_u16: jax.Array             # (P, 3+R*5) u16 | (1, 1) placeholder


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def _split_level_rows(rows: np.ndarray) -> np.ndarray:
    """(n, 18) u32 poptrie node rows -> (n_pad, 128) int8 biased byte
    planes (72 LE bytes used)."""
    n = rows.shape[0]
    n_pad = _round_up(max(n, 1), 128)
    raw = np.zeros((n_pad, LEVEL_ROW_PAD), np.uint8)
    if n:
        raw[:n, :LEVEL_ROW_BYTES] = np.ascontiguousarray(
            rows.astype("<u4")
        ).view(np.uint8).reshape(n, LEVEL_ROW_BYTES)
    return (raw.astype(np.int16) - 128).astype(np.int8)


def _split_joined_rows(joined_u16: np.ndarray) -> Optional[np.ndarray]:
    """(P, 3 + R*5) u16 joined rows -> (P_pad, NUM_FIELDS*RULE_STRIDE)
    int8 biased field-major byte planes, or None when R > RULE_STRIDE."""
    P = joined_u16.shape[0]
    R = (joined_u16.shape[1] - 3) // 5
    if R > RULE_STRIDE:
        return None
    rr = joined_u16[:, 3:].reshape(P, R, 5).astype(np.int32)
    planes = [
        rr[..., 0] & 0xFF,          # rid
        rr[..., 0] >> 8,            # act
        rr[..., 1] & 0xFF,          # proto
        rr[..., 1] >> 8,            # icmpType
        rr[..., 2] & 0xFF,          # icmpCode
        rr[..., 3] >> 8,            # portStart hi
        rr[..., 3] & 0xFF,          # portStart lo
        rr[..., 4] >> 8,            # portEnd hi
        rr[..., 4] & 0xFF,          # portEnd lo
    ]
    P_pad = _round_up(max(P, 1), 128)
    raw = np.zeros((P_pad, NUM_FIELDS * RULE_STRIDE), np.uint8)
    for f, v in enumerate(planes):
        raw[:P, f * RULE_STRIDE : f * RULE_STRIDE + R] = v
    return (raw.astype(np.int16) - 128).astype(np.int8)


def _extract_deep_tail(l0, deep_levels, joined_u16, lut, min_depth):
    """Restrict the walk structure to the subtree closure of root slots
    whose depth-LUT requirement exceeds ``min_depth`` (the full-depth
    steering class).  Whole child/target ranges of kept nodes are kept,
    so the implicit poptrie numbering and the affine joined-position
    arithmetic survive the compaction; all other l0 slots zero out (a
    mis-steered packet deterministically reads the UNDEF sentinel, the
    same invalidated-lane policy as the XLA walk's OOB masks).

    Returns (l0_remapped, levels_u32, keep_pos_mask)."""
    n_pos = joined_u16.shape[0]
    keep_pos = np.zeros(n_pos, bool)
    keep_pos[0] = True  # UNDEF sentinel row
    keep_slot = lut > min_depth
    slot_idx = np.nonzero(keep_slot)[0]

    # kept level-1 nodes: children of deep root slots
    child0 = l0[:, 0].astype(np.int64)
    kept_children = np.unique(child0[slot_idx])
    kept_children = kept_children[kept_children > 0] - 1

    # root-target joined positions of kept slots stay reachable
    pos0 = l0[:, 1].astype(np.int64)
    kp = np.unique(pos0[slot_idx])
    keep_pos[kp[(kp > 0) & (kp < n_pos)]] = True

    new_levels = []
    l0_child_map = None  # old level-1 id -> new id (or -1)
    keep_next = None
    for li, rows in enumerate(deep_levels):
        n_l = rows.shape[0]
        keep = np.zeros(n_l, bool)
        if li == 0:
            keep[kept_children[kept_children < n_l]] = True
        elif keep_next is not None:
            keep[keep_next[keep_next < n_l]] = True
        kept = np.nonzero(keep)[0]
        if len(kept) == 0:
            new_levels.append(np.zeros((0, 18), np.uint32))
            keep_next = np.zeros(0, np.int64)
            if li == 0:
                l0_child_map = np.full(n_l, -1, np.int64)
            continue
        sub = rows[kept].astype(np.int64)
        cb_words = sub[:, 2:10].astype(np.uint32)
        tb_words = sub[:, 10:18].astype(np.uint32)
        ccount = _popcount_np(cb_words).sum(axis=1)
        tcount = _popcount_np(tb_words).sum(axis=1)
        # children of kept nodes (whole contiguous ranges) survive
        keep_next = _crange_concat(sub[:, 0], ccount)
        # target ranges of kept nodes stay reachable positions
        tr = _crange_concat(sub[:, 1], tcount)
        keep_pos[tr[(tr >= 0) & (tr < n_pos)]] = True
        # renumber: kept nodes in old order; child_base = exclusive
        # cumsum of kept children counts (ranges are disjoint + ordered)
        new_cb = np.zeros(len(kept), np.int64)
        np.cumsum(ccount[:-1], out=new_cb[1:])
        sub[:, 0] = new_cb
        new_levels.append(sub)  # target_base rewritten after posmap below
        if li == 0:
            l0_child_map = np.full(n_l, -1, np.int64)
            l0_child_map[kept] = np.arange(len(kept))

    # drop empty trailing levels (static unroll shrinks with them)
    while new_levels and new_levels[-1].shape[0] == 0:
        new_levels.pop()

    posmap = np.cumsum(keep_pos) - 1  # old pos -> new pos (valid if kept)
    for sub in new_levels:
        if sub.shape[0] and sub.dtype != np.uint32:
            tb = sub[:, 1]
            sub[:, 1] = np.where(
                (tb >= 0) & (tb < n_pos), posmap[np.clip(tb, 0, n_pos - 1)], 0
            )
    levels_u32 = [
        (s.astype(np.uint32) if s.dtype != np.uint32 else s)
        for s in new_levels
    ]

    l0_new = np.zeros_like(l0)
    if len(slot_idx):
        ch = child0[slot_idx]
        mapped = np.where(
            ch > 0, l0_child_map[np.clip(ch - 1, 0, len(l0_child_map) - 1)], -1
        ) if l0_child_map is not None and len(l0_child_map) else np.full(
            len(slot_idx), -1, np.int64
        )
        l0_new[slot_idx, 0] = np.where(mapped >= 0, mapped + 1, 0).astype(np.int32)
        p = pos0[slot_idx]
        l0_new[slot_idx, 1] = np.where(
            (p > 0) & (p < n_pos), posmap[np.clip(p, 0, n_pos - 1)], 0
        ).astype(np.int32)
    return l0_new, levels_u32, keep_pos


def _popcount_np(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.uint32)
    x = x - ((x >> 1) & np.uint32(0x55555555))
    x = (x & np.uint32(0x33333333)) + ((x >> 2) & np.uint32(0x33333333))
    x = (x + (x >> 4)) & np.uint32(0x0F0F0F0F)
    return ((x * np.uint32(0x01010101)) >> 24).astype(np.int64)


def walk_vmem_bytes(level_bytes, joined_bytes=None,
                    block_b: int = BLOCK_B) -> int:
    """Resident + transient VMEM estimate for the fused kernel
    (``joined_bytes=None``: positions-tail mode — levels only)."""
    resident = sum(a.size for a in level_bytes)
    rows = [a.shape[0] for a in level_bytes] + [1]
    if joined_bytes is not None:
        resident += joined_bytes.size
        rows.append(joined_bytes.shape[0])
        # int32 row block of the in-kernel scan
        resident += block_b * NUM_FIELDS * RULE_STRIDE * 4
    transient = block_b * max(rows)  # int8 one-hot
    return resident + transient


def build_walk_tables_meta(
    tables: CompiledTables,
    min_depth: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    device=None,
):
    """Host transform CompiledTables -> (WalkTables, meta), or None when
    the fused layout cannot serve this table (wide int32 rules / rule
    width > 128 / VMEM budget exceeded) — the caller keeps the XLA walk.

    ``min_depth`` enables deep-tail extraction: the walk tables then
    cover ONLY packets whose root slot needs more than ``min_depth``
    deep levels (the depth-steered full-depth class); other packets
    deterministically resolve to UNDEF.  ``None`` builds the full
    structure (correct for every packet).

    ``meta``: {"min_depth", "tidx_sorted" (sorted unique target indices
    whose rule bytes are baked into the resident joined planes — the
    classifier's staleness check for rules-only edits), "vmem_bytes"}."""
    joined_u16, l0j, t_vals = joined_layout(tables)
    if joined_u16.dtype != np.uint16:
        return None  # wide int32 rules: wire path is off anyway
    levels, _targets = build_poptrie(tables)
    deep = [np.asarray(l, np.uint32) for l in levels[1:]]
    l0 = np.asarray(l0j, np.int32)

    if min_depth is not None and min_depth >= 0 and deep:
        lut = build_depth_lut(tables)
        l0, deep, keep_pos = _extract_deep_tail(
            l0, deep, joined_u16, lut, min_depth
        )
        joined_u16 = joined_u16[keep_pos]
        t_vals = t_vals[keep_pos]

    level_bytes = [_split_level_rows(d) for d in deep]
    joined_bytes = _split_joined_rows(joined_u16)
    tail = "fused"
    vmem = (walk_vmem_bytes(level_bytes, joined_bytes)
            if joined_bytes is not None else vmem_budget + 1)
    if joined_bytes is None or vmem > vmem_budget:
        # the RULE_STRIDE-padded byte planes don't fit (or rule width >
        # RULE_STRIDE): keep the level walk fused and fall back to the
        # one-XLA-gather positions tail for the rules
        tail = "positions"
        vmem = walk_vmem_bytes(level_bytes)
        if vmem > vmem_budget:
            return None

    put = lambda a: jax.device_put(jnp.asarray(a), device)
    placeholder = np.zeros((1, 1), np.int8)
    wt = WalkTables(
        l0=put(l0),
        root_lut=put(np.asarray(tables.root_lut, np.int32)),
        levels=tuple(put(b) for b in level_bytes),
        joined=put(joined_bytes if tail == "fused" else placeholder),
        joined_u16=put(
            joined_u16 if tail == "positions"
            else np.zeros((1, 1), np.uint16)
        ),
    )
    meta = {
        "min_depth": min_depth,
        "tidx_sorted": np.unique(t_vals[t_vals > 0] - 1),
        "t_vals": t_vals,  # kept position -> tidx+1 (patch_walk_joined)
        "vmem_bytes": vmem,
        "tail": tail,
    }
    return wt, meta


def build_walk_tables(
    tables: CompiledTables,
    min_depth: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    device=None,
) -> Optional[WalkTables]:
    """build_walk_tables_meta without the meta (tests/bench convenience)."""
    built = build_walk_tables_meta(tables, min_depth, vmem_budget, device)
    return None if built is None else built[0]


def patch_walk_joined(
    wt: WalkTables, meta, tables: CompiledTables, dirty_tidx, device=None
) -> Optional[WalkTables]:
    """RULES-ONLY incremental update of the resident joined byte planes:
    rewrite exactly the rows whose target's rule bytes changed (device
    scatter, kilobytes) instead of rebuilding the whole walk — the
    Map.Update analogue for the fused path.  The caller guarantees the
    trie is untouched (dirty hint), so levels/l0/root_lut carry over.
    Returns the patched WalkTables, ``wt`` itself when no resident row
    is dirty, or None when the packed layout changed (caller rebuilds)."""
    from .jaxpath import _packed_rules_flat

    t_vals = meta.get("t_vals")
    if t_vals is None:
        return None
    dirty = np.unique(np.asarray(dirty_tidx, np.int64))
    hit = np.isin(t_vals - 1, dirty) & (t_vals > 0)
    pos = np.nonzero(hit)[0]
    if len(pos) == 0:
        return wt
    rules_flat = _packed_rules_flat(tables)
    if rules_flat.dtype != np.uint16:
        return None
    R = rules_flat.shape[1] // 5
    t = t_vals[pos]
    tidx = np.minimum(t - 1, rules_flat.shape[0] - 1)
    ml = np.maximum(tables.mask_len, 0)
    rows = np.empty((len(pos), 3 + R * 5), np.uint16)
    rows[:, 0] = t & 0xFFFF
    rows[:, 1] = (t >> 16) & 0xFFFF
    rows[:, 2] = np.minimum(ml[tidx], 0xFFFF)
    rows[:, 3:] = rules_flat[tidx]
    # Scatter through the shared capped executable (jaxpath._scatter_cap)
    # — warmed at walk-build time by warm_walk_patch_scatters, so the
    # FIRST fused-path rules edit doesn't pay a scatter-jit compile, and
    # every small patch of one array shape reuses one compile (the
    # previous per-nnz `.at[pos].set` compiled a fresh executable per
    # distinct dirty-row count).  An oversized delta falls back to the
    # full rebuild, same as the jaxpath patch contract.
    from .jaxpath import _capped_scatter

    if wt.joined.shape[0] > 1:  # fused tail: patch the byte planes
        byte_rows = _split_joined_rows(rows)
        if byte_rows is None or byte_rows.shape[1] != wt.joined.shape[1]:
            return None
        joined = _capped_scatter(
            wt.joined, pos, byte_rows[: len(pos)], device
        )
        return None if joined is None else wt._replace(joined=joined)
    if rows.shape[1] != wt.joined_u16.shape[1]:
        return None
    joined_u16 = _capped_scatter(wt.joined_u16, pos, rows, device)
    return None if joined_u16 is None else wt._replace(joined_u16=joined_u16)


def warm_walk_patch_scatters(wt: WalkTables, device=None) -> None:
    """Pre-compile the capped scatter executables for the resident walk's
    patchable joined planes (the fused-path half of
    jaxpath.warm_patch_scatters): one warm per (shape, dtype) per
    dirty-row cap ladder step, so the first rules-only edit — single-key
    or a multi-edit transaction flush up to TXN_WARM_MAX_ROWS dirty
    rows — ships without paying a scatter-jit compile."""
    from .jaxpath import TXN_WARM_MAX_ROWS, warm_scatters

    warm_scatters((wt.joined, wt.joined_u16), device,
                  max_rows=TXN_WARM_MAX_ROWS)


# --- XLA pre-stage: the DIR-16 root gather -------------------------------


def _root_stage(l0: jax.Array, root_lut: jax.Array, batch: DeviceBatch):
    """Level 0 of trie_walk_joined, verbatim semantics: one direct-indexed
    gather; returns (node, alive, best0_position) for the kernel."""
    lut_size = root_lut.shape[0]
    if_ok = (batch.ifindex >= 0) & (batch.ifindex < lut_size)
    root = jnp.where(
        if_ok, jnp.take(root_lut, jnp.clip(batch.ifindex, 0, lut_size - 1)), 0
    )
    nib0 = (batch.ip_words[:, 0] >> np.uint32(16)).astype(jnp.int32)
    e0 = root * 65536 + nib0
    in0 = (e0 >= 0) & (e0 < l0.shape[0])
    rows0 = jnp.take(l0, e0, axis=0, mode="clip")
    best0 = jnp.where(in0 & (rows0[:, 1] > 0), rows0[:, 1], 0)
    alive = in0 & (rows0[:, 0] > 0)
    node = jnp.where(alive, rows0[:, 0] - 1, -1)
    return node, alive.astype(jnp.int32), best0


# --- the fused kernel ----------------------------------------------------


def _pc32(x: jax.Array) -> jax.Array:
    """SWAR popcount on int32 lanes (logical shifts keep the bit algebra
    identical to the uint32 XLA version)."""
    x = x - (jax.lax.shift_right_logical(x, 1) & 0x55555555)
    x = (x & 0x33333333) + (jax.lax.shift_right_logical(x, 2) & 0x33333333)
    x = (x + jax.lax.shift_right_logical(x, 4)) & 0x0F0F0F0F
    return jax.lax.shift_right_logical(x * 0x01010101, 24)


def _le_u32(rows: jax.Array, c: int) -> jax.Array:
    """The little-endian u32 word in byte lanes c..c+3 of an int32 byte
    matrix.  Multiplies, not shifts: on the TPU, Mosaic returns wrong
    words for the shift-left form over single-lane slices, which interpret
    mode does not show (wrapping int32 multiplies give the same bits)."""
    return (
        rows[:, c : c + 1]
        + rows[:, c + 1 : c + 2] * 256
        + rows[:, c + 2 : c + 3] * 65536
        + rows[:, c + 3 : c + 4] * 16777216
    )


def _make_walk_kernel(n_levels: int, fused_tail: bool):
    def kernel(meta_ref, words_ref, *refs):
        level_refs = refs[:n_levels]
        joined_ref = refs[n_levels] if fused_tail else None
        out_ref = refs[-1]
        Bb = meta_ref.shape[0]

        node = meta_ref[:, 0:1]            # -1 = dead lane
        alive = meta_ref[:, 1:2]           # {0, 1}
        win = meta_ref[:, 2:3]             # joined position (0 = none)
        kind = meta_ref[:, 3:4]
        proto = meta_ref[:, 4:5]
        dport = meta_ref[:, 5:6]
        itype = meta_ref[:, 6:7]
        icode = meta_ref[:, 7:8]
        cap = jnp.where(kind == KIND_IPV4, 32, 128)
        node = jnp.where(alive > 0, node, -1)

        dn = (((1,), (0,)), ((), ()))
        for l, lref in enumerate(level_refs):
            bit_start = 16 + 8 * l
            bit_end = bit_start + 8
            w32 = bit_start // 32
            shift = 24 - (bit_start % 32)
            nib = (
                jax.lax.shift_right_logical(words_ref[:, w32 : w32 + 1], shift)
                & 0xFF
            )
            n_l = lref.shape[0]
            iota_n = jax.lax.broadcasted_iota(jnp.int32, (Bb, n_l), 1)
            # node == -1 for dead lanes -> all-zero one-hot -> zero row;
            # identical to the XLA walk's invalidated-lane UNDEF policy
            onehot = (iota_n == node).astype(jnp.int8)
            live = node >= 0
            rowb = jax.lax.dot_general(
                onehot, lref[:, :], dn, preferred_element_type=jnp.int32
            ) + jnp.where(live, 128, 0)  # un-bias; dead rows stay zero

            def u32(c, _r=rowb):
                return _le_u32(_r, c)

            child_base = u32(0)
            target_base = u32(4)
            w = nib >> 5
            bit = nib & 31
            below = jnp.left_shift(1, bit) - 1
            prefix = jnp.zeros((Bb, 1), jnp.int32)
            tprefix = jnp.zeros((Bb, 1), jnp.int32)
            cw = jnp.zeros((Bb, 1), jnp.int32)
            tw = jnp.zeros((Bb, 1), jnp.int32)
            for j in range(8):
                cb_j = u32(8 + 4 * j)
                tb_j = u32(40 + 4 * j)
                prefix = prefix + jnp.where(w > j, _pc32(cb_j), 0)
                tprefix = tprefix + jnp.where(w > j, _pc32(tb_j), 0)
                cw = jnp.where(w == j, cb_j, cw)
                tw = jnp.where(w == j, tb_j, tw)
            tbit = jax.lax.shift_right_logical(tw, bit) & 1
            ok_t = (tbit > 0) & (cap >= bit_end)
            win = jnp.where(
                ok_t, target_base + tprefix + _pc32(tw & below), win
            )
            cbit = jax.lax.shift_right_logical(cw, bit) & 1
            node = jnp.where(
                cbit > 0, child_base + prefix + _pc32(cw & below), -1
            )
            # dead lanes keep node == -1 (zero rows -> cbit == 0)

        if not fused_tail:
            # positions tail: the rules planes live in HBM; emit the
            # winning position for the caller's one XLA fat-row gather
            out_ref[:, 0:1] = jnp.zeros((Bb, 1), jnp.int32)
            out_ref[:, 1:2] = win
            return

        # --- joined-targets rules tail (one-hot fetch + ordered scan) ----
        P = joined_ref.shape[0]
        pos = win
        pos_sel = jnp.where(pos > 0, pos, -1)  # row 0 is the UNDEF sentinel
        matched = pos_sel >= 0
        iota_p = jax.lax.broadcasted_iota(jnp.int32, (Bb, P), 1)
        ohp = (iota_p == pos_sel).astype(jnp.int8)
        rowj = jax.lax.dot_general(
            ohp, joined_ref[:, :], dn, preferred_element_type=jnp.int32
        ) + jnp.where(matched, 128, 0)

        R = RULE_STRIDE
        rid = rowj[:, 0 * R : 1 * R]
        act = rowj[:, 1 * R : 2 * R]
        rproto = rowj[:, 2 * R : 3 * R]
        it = rowj[:, 3 * R : 4 * R]
        ic = rowj[:, 4 * R : 5 * R]
        ps = rowj[:, 5 * R : 6 * R] * 256 + rowj[:, 6 * R : 7 * R]
        pe = rowj[:, 7 * R : 8 * R] * 256 + rowj[:, 8 * R : 9 * R]

        valid = rid != 0
        proto_eq = (rproto != 0) & (rproto == proto)
        is_transport = (
            (rproto == IPPROTO_TCP)
            | (rproto == IPPROTO_UDP)
            | (rproto == IPPROTO_SCTP)
        )
        pe_zero = pe == 0
        port_hit = (pe_zero & (dport == ps)) | (
            jnp.logical_not(pe_zero) & (dport >= ps) & (dport < pe)
        )
        fam = jnp.where(kind == KIND_IPV4, IPPROTO_ICMP, IPPROTO_ICMPV6)
        icmp_hit = (rproto == fam) & (it == itype) & (ic == icode)
        hit = valid & (
            (proto_eq & ((is_transport & port_hit) | icmp_hit)) | (rproto == 0)
        )

        iota_r = jax.lax.broadcasted_iota(jnp.int32, (Bb, R), 1)
        first = jnp.min(jnp.where(hit, iota_r, R), axis=1, keepdims=True)
        any_hit = first < R
        oh2 = (iota_r == first).astype(jnp.int32)
        rid_f = jnp.sum(rid * oh2, axis=1, keepdims=True)
        act_f = jnp.sum(act * oh2, axis=1, keepdims=True)
        result = jnp.where(any_hit, (rid_f << 8) | act_f, 0)

        out_ref[:, 0:1] = result
        out_ref[:, 1:2] = pos

    return kernel


def _walk_scan(
    meta: jax.Array, words: jax.Array, wt: WalkTables, interpret: bool,
    block_b: int,
) -> jax.Array:
    B = meta.shape[0]
    n_levels = len(wt.levels)
    fused_tail = wt.joined.shape[0] > 1
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    operands = [meta, words, *wt.levels]
    in_specs = [
        pl.BlockSpec((block_b, 8), lambda i: (i, 0)),
        pl.BlockSpec((block_b, 4), lambda i: (i, 0)),
        *[full(l) for l in wt.levels],
    ]
    if fused_tail:
        operands.append(wt.joined)
        in_specs.append(full(wt.joined))
    return pl.pallas_call(
        _make_walk_kernel(n_levels, fused_tail),
        out_shape=jax.ShapeDtypeStruct((B, 2), jnp.int32),
        grid=(B // block_b,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, 2), lambda i: (i, 0)),
        interpret=interpret,
    )(*operands)


def classify_walk(
    wt: WalkTables, batch: DeviceBatch, interpret: bool = False,
    block_b: int = BLOCK_B,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full forward pass via the fused walk; returns (results, xdp,
    stats) identical to jaxpath.classify(use_trie=True) for every packet
    the walk tables cover (all packets when built with min_depth=None;
    the deep steering class when built with extraction)."""
    B = batch.kind.shape[0]
    node, alive, best0 = _root_stage(wt.l0, wt.root_lut, batch)
    meta = jnp.stack(
        [
            node,
            alive,
            best0,
            batch.kind,
            batch.proto,
            batch.dst_port,
            batch.icmp_type,
            batch.icmp_code,
        ],
        axis=1,
    ).astype(jnp.int32)
    words = batch.ip_words.astype(jnp.int32)  # bit patterns; shifts logical
    Bp = _round_up(max(B, 1), block_b)
    if Bp != B:
        pad = Bp - B
        pad_meta = jnp.zeros((pad, 8), jnp.int32)
        pad_meta = pad_meta.at[:, 0].set(-1).at[:, 3].set(KIND_OTHER)
        meta = jnp.concatenate([meta, pad_meta], axis=0)
        words = jnp.concatenate([words, jnp.zeros((pad, 4), jnp.int32)], axis=0)
    out = _walk_scan(meta, words, wt, interpret, block_b)[:B]
    if wt.joined.shape[0] > 1:
        raw = out[:, 0].astype(jnp.uint32)
    else:
        # positions tail: ONE XLA fat-row gather + the shared ordered
        # scan (identical to the XLA walk's joined tail, minus the
        # per-level gather excursions the kernel just absorbed)
        from .jaxpath import joined_rule_rows, rule_scan

        pos = out[:, 1]
        P = wt.joined_u16.shape[0]
        in_p = (pos > 0) & (pos < P)
        rows = jnp.take(
            wt.joined_u16, jnp.clip(pos, 0, P - 1), axis=0, mode="clip"
        )
        rows = jnp.where(in_p[:, None], rows, 0)
        raw = rule_scan(joined_rule_rows(rows), batch)
    return finalize(raw, batch)


@functools.lru_cache(maxsize=None)
def jitted_classify_walk(interpret: bool, block_b: int = BLOCK_B):
    return jax.jit(
        functools.partial(classify_walk, interpret=interpret, block_b=block_b)
    )


def classify_walk_wire(
    wt: WalkTables, wire: jax.Array, interpret: bool = False,
    block_b: int = BLOCK_B,
) -> Tuple[jax.Array, jax.Array]:
    """Wire-format fused-walk pass (see jaxpath.classify_wire): packed
    descriptors in, (results_u16, stats) out; the unpack fuses into the
    XLA root stage feeding the kernel."""
    res, _xdp, stats = classify_walk(
        wt, unpack_wire(wire), interpret=interpret, block_b=block_b
    )
    return res.astype(jnp.uint16), stats


@functools.lru_cache(maxsize=None)
def jitted_classify_walk_wire_fused(interpret: bool, block_b: int = BLOCK_B):
    """Single-buffer output (jaxpath.fuse_wire_outputs): one D2H RPC per
    chunk, same contract as the XLA wire path."""

    def f(wt: WalkTables, wire: jax.Array) -> jax.Array:
        return fuse_wire_outputs(
            *classify_walk_wire(wt, wire, interpret=interpret, block_b=block_b)
        )

    return jax.jit(f)


# --- fused COMPRESSED walk (skip-node descent over the merged cpoptrie) -----
#
# The compressed layout (jaxpath.build_cpoptrie) merges every deep level
# into one node array with path-compressed skip nodes, so the deep
# descent is d_max steps (5-7 on the 1M adversarial tables vs 14
# levels) over ONE VMEM-resident byte-plane matrix.  Each step must
# track a DYNAMIC per-lane bit position (skips advance lanes unevenly),
# so the nibble extraction is select-based in-kernel math over the 4 ip
# words — the same formulation the XLA walk uses (extract_ip_bits).
#
# Tail mode is POSITIONS-only: the kernel emits the winning flat target
# position; the rules tail is one XLA targets resolve + one fat-row
# gather from the per-tidx joined matrix in HBM (no duplication, so the
# matrix is exactly T+1 rows) feeding the shared ordered scan.  A fused
# in-kernel tail would need the (T+1)-row joined planes VMEM-resident —
# the wrong trade at the 1M/10M tiers this layout exists for.

CNODE_ROW_BYTES = 80  # 20 u32: bases, skip, 8+8 bitmap words


class CWalkTables(NamedTuple):
    """Fused compressed-walk device operands.  ``d_max`` travels in the
    builder meta / the jitted-factory cache key (static unroll)."""

    l0: jax.Array         # (n0*65536, 2) int32 (extraction-remapped)
    root_lut: jax.Array   # (max_if+1,) int32
    nodes: jax.Array      # (N_pad, 128) int8 biased byte planes
    targets: jax.Array    # (1 + n_tgt,) int32 tidx+1 values
    joined: jax.Array     # (T+1, 3+R*5) uint16 per-tidx rows (HBM)


def _split_cnode_rows(rows: np.ndarray) -> np.ndarray:
    """(n, 20) u32 skip-node rows -> (n_pad, 128) int8 biased byte
    planes (80 LE bytes used)."""
    n = rows.shape[0]
    n_pad = _round_up(max(n, 1), 128)
    raw = np.zeros((n_pad, LEVEL_ROW_PAD), np.uint8)
    if n:
        raw[:n, :CNODE_ROW_BYTES] = np.ascontiguousarray(
            rows.astype("<u4")
        ).view(np.uint8).reshape(n, CNODE_ROW_BYTES)
    return (raw.astype(np.int16) - 128).astype(np.int8)


def _extract_cwalk_tail(l0, nodes, targets, lut, min_depth):
    """Deep-class extraction on the MERGED node array: keep the subtree
    closure of root slots whose depth-LUT requirement exceeds
    ``min_depth``.  Children of kept nodes are whole contiguous ranges
    and consecutive in the BFS numbering, so compaction is one
    cumsum-renumber; target ranges compact the same way.  Unkept l0
    slots zero out (mis-steered packets deterministically read UNDEF).
    Returns (l0_new, nodes_new, targets_new, d_max_new)."""
    N = nodes.shape[0]
    keep = np.zeros(N, bool)
    c0 = l0[:, 0].astype(np.int64)
    slot_keep = lut > min_depth
    slot_idx = np.nonzero(slot_keep)[0]
    roots = c0[slot_idx]
    roots = roots[roots > 0] - 1
    frontier = np.unique(roots[roots < N])
    cb = nodes[:, 0].astype(np.int64)
    cc = _popcount_np(nodes[:, 4:12].astype(np.uint32)).sum(axis=1)
    tb = nodes[:, 1].astype(np.int64)
    tc = _popcount_np(nodes[:, 12:20].astype(np.uint32)).sum(axis=1)
    d_max = 0
    while len(frontier):
        d_max += 1
        keep[frontier] = True
        nxt = _crange_concat(cb[frontier], cc[frontier])
        nxt = nxt[(nxt >= 0) & (nxt < N)]
        frontier = nxt  # BFS ranges are disjoint: no re-visit possible
    kept = np.nonzero(keep)[0]
    node_map = np.cumsum(keep) - 1  # old id -> new id (valid where kept)
    # target compaction: kept nodes' ranges, plus the position-0 sentinel
    n_t = targets.shape[0]
    keep_t = np.zeros(n_t, bool)
    keep_t[0] = True
    tr = _crange_concat(tb[kept], tc[kept])
    keep_t[tr[(tr >= 0) & (tr < n_t)]] = True
    t_map = np.cumsum(keep_t) - 1
    nodes_new = nodes[kept].copy() if len(kept) else np.zeros(
        (1, 20), np.uint32
    )
    if len(kept):
        nodes_new[:, 0] = np.where(
            cc[kept] > 0,
            node_map[np.clip(cb[kept], 0, N - 1)],
            0,
        ).astype(np.uint32)
        nodes_new[:, 1] = t_map[np.clip(tb[kept], 0, n_t - 1)].astype(
            np.uint32
        )
    targets_new = targets[keep_t]
    l0_new = np.zeros_like(l0)
    if len(slot_idx):
        ch = c0[slot_idx]
        ok = (ch > 0) & (ch <= N)
        mapped = np.where(
            ok & keep[np.clip(ch - 1, 0, N - 1)],
            node_map[np.clip(ch - 1, 0, N - 1)] + 1,
            0,
        )
        l0_new[slot_idx, 0] = mapped.astype(np.int32)
        l0_new[slot_idx, 1] = l0[slot_idx, 1]
    return l0_new, nodes_new, targets_new, d_max


def build_cwalk_tables_meta(
    tables: CompiledTables,
    min_depth: Optional[int] = None,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    device=None,
):
    """Host transform CompiledTables -> (CWalkTables, meta) for the
    fused compressed walk, or None when the layout cannot serve this
    table (wide int32 rules, VMEM budget exceeded even after path
    compression) — callers fall back to the XLA compressed walk, then
    to the level walk (never a refusal, never a wrong verdict).

    ``min_depth`` enables deep-class extraction exactly like
    build_walk_tables_meta; the depth LUT is in LEVEL terms, which is
    conservative for the compressed structure (compression only shrinks
    the step count under a root slot, never grows it)."""
    joined = joined_by_tidx(tables)
    if joined is None:
        return None
    l0, nodes, targets, d_max = build_cpoptrie(tables)
    l0 = np.asarray(l0, np.int32)
    if min_depth is not None and min_depth >= 0:
        lut = build_depth_lut(tables)
        l0, nodes, targets, d_max = _extract_cwalk_tail(
            l0, nodes, targets, lut, min_depth
        )
    node_bytes = _split_cnode_rows(nodes)
    # resident: node planes; transient: the (Bb, N_pad) int8 one-hot
    vmem = node_bytes.size + BLOCK_B * max(node_bytes.shape[0], 1)
    if vmem > vmem_budget:
        return None
    put = lambda a: jax.device_put(jnp.asarray(a), device)
    wt = CWalkTables(
        l0=put(l0),
        root_lut=put(np.asarray(tables.root_lut, np.int32)),
        nodes=put(node_bytes),
        targets=put(np.asarray(targets, np.int32)),
        joined=put(joined),
    )
    meta = {
        "min_depth": min_depth,
        "d_max": int(d_max),
        "vmem_bytes": int(vmem),
        "tail": "positions",
        "tidx_sorted": np.unique(targets[targets > 0] - 1),
    }
    return wt, meta


def patch_cwalk_joined(
    wt: CWalkTables, meta, tables: CompiledTables, dirty_tidx, device=None
) -> Optional[CWalkTables]:
    """RULES-ONLY incremental update of the per-tidx joined matrix:
    positions are dirty_tidx + 1 by construction (no position map
    needed — the tidx indexing is the whole point), through the shared
    capped scatter.  Returns the patched CWalkTables or None when the
    packed layout changed (caller rebuilds)."""
    from .jaxpath import _capped_scatter, _joined_tidx_patch_rows

    dirty = np.unique(np.asarray(dirty_tidx, np.int64))
    pr = _joined_tidx_patch_rows(tables, dirty)
    if pr is None:
        return None
    pos, rows = pr
    if len(pos) == 0:
        return wt
    if int(pos.max()) >= wt.joined.shape[0]:
        return None
    if rows.shape[1] != wt.joined.shape[1]:
        return None
    joined = _capped_scatter(wt.joined, pos, rows, device)
    return None if joined is None else wt._replace(joined=joined)


def warm_cwalk_patch_scatters(wt: CWalkTables, device=None) -> None:
    """warm_walk_patch_scatters for the compressed walk: the per-tidx
    joined matrix is its only patchable plane (trie edits rebuild).
    Ladder-warmed so transaction flushes of up to TXN_WARM_MAX_ROWS
    dirty rows stay compile-free."""
    from .jaxpath import TXN_WARM_MAX_ROWS, warm_scatters

    warm_scatters((wt.joined,), device, max_rows=TXN_WARM_MAX_ROWS)


def _make_cwalk_kernel(d_max: int):
    def kernel(meta_ref, words_ref, nodes_ref, out_ref):
        Bb = meta_ref.shape[0]
        node = meta_ref[:, 0:1]            # -1 = dead lane
        alive = meta_ref[:, 1:2]           # {0, 1}
        kind = meta_ref[:, 3:4]
        cap = jnp.where(kind == KIND_IPV4, 32, 128)
        node = jnp.where(alive > 0, node, -1)
        pos = jnp.full((Bb, 1), 16, jnp.int32)
        win = jnp.zeros((Bb, 1), jnp.int32)
        zeros = jnp.zeros((Bb, 1), jnp.int32)

        def extract(p, n):
            """n bits at dynamic bit offset p of the 128-bit address
            (select-based word pick; logical shifts on int32 lanes)."""
            w = jax.lax.shift_right_logical(p, 5)
            lo = zeros
            hi = zeros
            for k in range(4):
                wc = words_ref[:, k : k + 1]
                lo = jnp.where(w == k, wc, lo)
                hi = jnp.where(w + 1 == k, wc, hi)
            off = p & 31
            hi_part = jnp.where(
                off == 0, 0, jax.lax.shift_right_logical(hi, 32 - off)
            )
            top32 = jax.lax.shift_left(lo, off) | hi_part
            return jnp.where(
                n == 0, 0, jax.lax.shift_right_logical(top32, 32 - n)
            )

        dn = (((1,), (0,)), ((), ()))
        n_nodes = nodes_ref.shape[0]
        for _step in range(d_max):
            iota_n = jax.lax.broadcasted_iota(jnp.int32, (Bb, n_nodes), 1)
            onehot = (iota_n == node).astype(jnp.int8)
            live = node >= 0
            rowb = jax.lax.dot_general(
                onehot, nodes_ref[:, :], dn, preferred_element_type=jnp.int32
            ) + jnp.where(live, 128, 0)

            def u32(c, _r=rowb):
                return _le_u32(_r, c)

            child_base = u32(0)
            target_base = u32(4)
            skip_len = u32(8)
            skip_bits = u32(12)
            # (skip_len == 0) | match, not where(.., bool, True): Mosaic
            # cannot lower a select whose operands are i1 vectors.
            skip_ok = (skip_len == 0) | (extract(pos, skip_len) == skip_bits)
            live = live & skip_ok
            pos = pos + skip_len
            nib = extract(pos, jnp.full((Bb, 1), 8, jnp.int32))
            pos = pos + 8
            w = nib >> 5
            bit = nib & 31
            below = jnp.left_shift(1, bit) - 1
            prefix = zeros
            tprefix = zeros
            cw = zeros
            tw = zeros
            for j in range(8):
                cb_j = u32(16 + 4 * j)
                tb_j = u32(48 + 4 * j)
                prefix = prefix + jnp.where(w > j, _pc32(cb_j), 0)
                tprefix = tprefix + jnp.where(w > j, _pc32(tb_j), 0)
                cw = jnp.where(w == j, cb_j, cw)
                tw = jnp.where(w == j, tb_j, tw)
            tbit = jax.lax.shift_right_logical(tw, bit) & 1
            ok_t = live & (tbit > 0) & (cap >= pos)
            win = jnp.where(
                ok_t, target_base + tprefix + _pc32(tw & below), win
            )
            cbit = jax.lax.shift_right_logical(cw, bit) & 1
            node = jnp.where(
                live & (cbit > 0),
                child_base + prefix + _pc32(cw & below),
                -1,
            )

        out_ref[:, 0:1] = zeros
        out_ref[:, 1:2] = win

    return kernel


def _cwalk_scan(
    meta: jax.Array, words: jax.Array, nodes, d_max: int,
    interpret: bool, block_b: int,
) -> jax.Array:
    """The fused skip-node descent grid pass over ONE merged int8
    byte-plane node array — shared by the single-table compressed walk
    (CWalkTables.nodes) and the multi-tenant paged arena walk (the
    whole node POOL's planes): slab paging bakes page-global node ids
    at write time, so the kernel body is page-agnostic."""
    if hasattr(nodes, "nodes"):  # CWalkTables convenience
        nodes = nodes.nodes
    B = meta.shape[0]
    full = lambda a: pl.BlockSpec(a.shape, lambda i: (0, 0))
    return pl.pallas_call(
        _make_cwalk_kernel(d_max),
        out_shape=jax.ShapeDtypeStruct((B, 2), jnp.int32),
        grid=(B // block_b,),
        in_specs=[
            pl.BlockSpec((block_b, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 4), lambda i: (i, 0)),
            full(nodes),
        ],
        out_specs=pl.BlockSpec((block_b, 2), lambda i: (i, 0)),
        interpret=interpret,
    )(meta, words, nodes)


def classify_cwalk(
    wt: CWalkTables, batch: DeviceBatch, *, d_max: int,
    interpret: bool = False, block_b: int = BLOCK_B,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full forward pass via the fused compressed walk; identical to
    jaxpath.classify_ctrie for every packet the walk tables cover (all
    packets when built with min_depth=None; the deep steering class
    under extraction)."""
    from .jaxpath import joined_rule_rows, rule_scan

    B = batch.kind.shape[0]
    node, alive, best0 = _root_stage(wt.l0, wt.root_lut, batch)
    meta = jnp.stack(
        [
            node, alive, best0, batch.kind,
            jnp.zeros_like(node), jnp.zeros_like(node),
            jnp.zeros_like(node), jnp.zeros_like(node),
        ],
        axis=1,
    ).astype(jnp.int32)
    words = batch.ip_words.astype(jnp.int32)
    Bp = _round_up(max(B, 1), block_b)
    if Bp != B:
        pad = Bp - B
        pad_meta = jnp.zeros((pad, 8), jnp.int32)
        pad_meta = pad_meta.at[:, 0].set(-1).at[:, 3].set(KIND_OTHER)
        meta = jnp.concatenate([meta, pad_meta], axis=0)
        words = jnp.concatenate([words, jnp.zeros((pad, 4), jnp.int32)], axis=0)
    out = _cwalk_scan(meta, words, wt, d_max, interpret, block_b)[:B]
    win = out[:, 1]
    n_t = wt.targets.shape[0]
    in_w = (win >= 0) & (win < n_t)
    tval = jnp.where(
        in_w, jnp.take(wt.targets, jnp.clip(win, 0), mode="clip"), 0
    )
    sel = jnp.where(tval > 0, tval, best0)  # tidx+1
    P = wt.joined.shape[0]
    in_j = (sel > 0) & (sel < P)
    rows = jnp.take(
        wt.joined, jnp.clip(sel, 0, P - 1), axis=0, mode="clip"
    )
    rows = jnp.where(in_j[:, None], rows, 0)
    raw = rule_scan(joined_rule_rows(rows), batch)
    return finalize(raw, batch)


@functools.lru_cache(maxsize=None)
def jitted_classify_cwalk(d_max: int, interpret: bool,
                          block_b: int = BLOCK_B):
    return jax.jit(
        functools.partial(
            classify_cwalk, d_max=d_max, interpret=interpret, block_b=block_b
        )
    )


def classify_cwalk_wire(
    wt: CWalkTables, wire: jax.Array, *, d_max: int,
    interpret: bool = False, block_b: int = BLOCK_B,
) -> Tuple[jax.Array, jax.Array]:
    res, _xdp, stats = classify_cwalk(
        wt, unpack_wire(wire), d_max=d_max, interpret=interpret,
        block_b=block_b,
    )
    return res.astype(jnp.uint16), stats


@functools.lru_cache(maxsize=None)
def jitted_classify_cwalk_wire_fused(d_max: int, interpret: bool,
                                     block_b: int = BLOCK_B):
    def f(wt: CWalkTables, wire: jax.Array) -> jax.Array:
        return fuse_wire_outputs(
            *classify_cwalk_wire(
                wt, wire, d_max=d_max, interpret=interpret, block_b=block_b
            )
        )

    return jax.jit(f)


# --- paged arena walk (multi-tenant, ISSUE-10) ------------------------------
#
# The paged compressed walk: the arena's merged skip-node POOL becomes
# the kernel's one VMEM-resident byte-plane array (slab writes bake
# page-global ids, so _make_cwalk_kernel runs unmodified), and the
# tenant-steered entry (jaxpath._arena_ctrie_entry) replaces the
# single-table _root_stage.  The rules tail gathers the POOLED per-tidx
# joined matrix from HBM by global position — no leaf-push duplication,
# no per-tenant specialization, one executable for the whole arena.


def arena_cwalk_vmem_bytes(node_pool_rows: int,
                           block_b: int = BLOCK_B) -> int:
    """Resident + transient VMEM estimate of the paged walk: the int8
    node planes plus the (block_b, N_pad) one-hot operand — the same
    accounting build_cwalk_tables_meta gates on."""
    n_pad = _round_up(max(node_pool_rows, 1), 128)
    return n_pad * LEVEL_ROW_PAD + block_b * n_pad


def build_arena_cwalk_planes(
    nodes_pool: np.ndarray,
    vmem_budget: int = DEFAULT_VMEM_BUDGET,
    device=None,
):
    """(P*SN, 20) u32 pool -> (P*SN, 128) int8 biased byte planes for
    the fused paged walk, or None when the pool exceeds the VMEM budget
    (callers serve from the XLA arena walk — the usual fallback
    contract).  SN is a multiple of 128 by ArenaSpec construction, so
    plane rows map 1:1 to pool rows and a slab rewrite can re-derive
    exactly its own rows."""
    if arena_cwalk_vmem_bytes(nodes_pool.shape[0]) > vmem_budget:
        return None
    return jax.device_put(
        jnp.asarray(_split_cnode_rows(np.asarray(nodes_pool, np.uint32))),
        device,
    )


def classify_arena_cwalk(
    ca, planes: jax.Array, batch: DeviceBatch, tenant: jax.Array, *,
    pages: int, d_max: int, interpret: bool = False,
    block_b: int = BLOCK_B, spec=None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Mixed-tenant forward pass via the fused paged walk; verdicts
    bit-identical to jaxpath.classify_arena_ctrie on the same arena.

    With a spliced ``spec`` the entry stage resolves SPLICE_TAG l0
    slots through the tenant's splice rows into the shared plane-pool
    region appended to the node pool — plane slab writes bake
    pool-global child/target ids, so the kernel body and the rules
    tail run unmodified over spliced and residual rows alike."""
    from .jaxpath import (
        _arena_ctrie_entry, joined_rule_rows, rule_scan,
    )

    B = batch.kind.shape[0]
    node, alive, best0 = _arena_ctrie_entry(
        ca, batch, tenant, pages=pages, spec=spec
    )
    node = jnp.where(alive, node, -1)
    meta = jnp.stack(
        [
            node, alive.astype(jnp.int32), best0, batch.kind,
            jnp.zeros_like(node), jnp.zeros_like(node),
            jnp.zeros_like(node), jnp.zeros_like(node),
        ],
        axis=1,
    ).astype(jnp.int32)
    words = batch.ip_words.astype(jnp.int32)
    Bp = _round_up(max(B, 1), block_b)
    if Bp != B:
        pad = Bp - B
        pad_meta = jnp.zeros((pad, 8), jnp.int32)
        pad_meta = pad_meta.at[:, 0].set(-1).at[:, 3].set(KIND_OTHER)
        meta = jnp.concatenate([meta, pad_meta], axis=0)
        words = jnp.concatenate(
            [words, jnp.zeros((pad, 4), jnp.int32)], axis=0
        )
    out = _cwalk_scan(meta, words, planes, d_max, interpret, block_b)[:B]
    win = out[:, 1]
    n_t = ca.targets.shape[0]
    in_w = (win >= 0) & (win < n_t)
    tval = jnp.where(
        in_w, jnp.take(ca.targets, jnp.clip(win, 0), mode="clip"), 0
    )
    sel = jnp.where(tval > 0, tval, best0)  # global joined position
    P = ca.joined.shape[0]
    in_j = (sel > 0) & (sel < P)
    rows = jnp.take(
        ca.joined, jnp.clip(sel, 0, P - 1), axis=0, mode="clip"
    )
    rows = jnp.where(in_j[:, None], rows, 0)
    raw = rule_scan(joined_rule_rows(rows), batch)
    return finalize(raw, batch)


@functools.lru_cache(maxsize=None)
def jitted_classify_arena_cwalk_wire_fused(
    pages: int, d_max: int, interpret: bool, block_b: int = BLOCK_B,
    spec=None,
):
    """The paged-walk wire launch: (arena, planes, wire, tenant) ->
    fused (res16, stats) — keyed on the pool geometry statics only
    (plus the ArenaSpec when splicing is on), so tenant lifecycle
    never re-specializes."""
    def f(ca, planes, wire, tenant):
        res, _x, stats = classify_arena_cwalk(
            ca, planes, unpack_wire(wire), tenant,
            pages=pages, d_max=d_max, interpret=interpret, block_b=block_b,
            spec=spec,
        )
        return fuse_wire_outputs(res.astype(jnp.uint16), stats)

    return jax.jit(f)
