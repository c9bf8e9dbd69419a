"""Multi-chip classification: shard_map over a ("data", "rules") mesh.

The reference's parallelism is per-node DaemonSets plus per-CPU hot-path
maps (SURVEY.md §2 parallelism table).  The TPU-native equivalents:

- **data axis**: the packet batch is sharded across chips (the analogue of
  per-CPU XDP processing); per-shard statistics are combined with psum over
  ICI (the analogue of the userspace per-CPU stats aggregation,
  /root/reference/pkg/metrics/statistics.go:126-157).
- **rules axis**: the rule table itself is sharded across chips ("tensor
  parallelism" over targets).  Each chip computes the longest-prefix match
  over its local entries; the global winner is selected with a pmax over
  the match score (mask_len+1 — globally unique among matching entries
  because equal-length matching prefixes are deduplicated at compile time),
  and only the winning chip contributes the scanned verdict via psum.

Rule tensors are broadcast/resharded with jax.device_put under the mesh —
the ICI/DCN replacement for the reference's per-node BPF map writes.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compiler import CompiledTables
from ..kernels import jaxpath
from ..kernels.jaxpath import DeviceBatch, DeviceTables


def validate_mesh_axes(
    n_devices: int, rules_shards: int, available: int, what: str = "devices"
) -> None:
    """Shared axis validation for make_mesh and multihost.make_global_mesh
    (previously each carried its own partial checks: make_mesh silently
    truncated to the first n devices — and reshape-crashed when asked for
    MORE than exist — while make_global_mesh re-stated the divisibility
    rule with a different message).  One rule set, one wording:

    - both axis factors must be positive,
    - rules_shards must not exceed n_devices (a rules group cannot span
      more chips than the mesh has),
    - rules_shards must divide n_devices exactly,
    - n_devices must not exceed the available pool."""
    if n_devices < 1 or rules_shards < 1:
        raise ValueError(
            f"mesh axes must be positive, got n_devices={n_devices} "
            f"rules_shards={rules_shards}"
        )
    if rules_shards > n_devices:
        raise ValueError(
            f"rules_shards={rules_shards} exceeds n_devices={n_devices}: "
            "the rules axis cannot be wider than the mesh"
        )
    if n_devices % rules_shards != 0:
        raise ValueError(
            f"{n_devices} {what} not divisible into {rules_shards} "
            "rule shards"
        )
    if n_devices > available:
        raise ValueError(
            f"mesh wants {n_devices} {what} but only {available} are "
            "visible"
        )


def make_mesh(n_devices: Optional[int] = None, rules_shards: int = 1) -> Mesh:
    """("data", "rules") mesh over the FIRST ``n_devices`` visible devices
    (n_devices=None takes all of them).  Axis shapes are validated by
    validate_mesh_axes — asking for more devices than exist, or a rules
    axis that does not divide (or exceeds) the device count, raises
    instead of truncating or crashing in the reshape."""
    devices = jax.devices()
    n = n_devices or len(devices)
    validate_mesh_axes(n, rules_shards, len(devices))
    arr = np.array(devices[:n]).reshape(n // rules_shards, rules_shards)
    return Mesh(arr, ("data", "rules"))


def _pad_tables_for_shards(tables: CompiledTables, shards: int) -> CompiledTables:
    """Pad the target axis to a multiple of the rules-shard count; padding
    rows carry the mask_len == -1 sentinel."""
    T = tables.key_words.shape[0]
    Tp = ((max(T, 1) + shards - 1) // shards) * shards
    if Tp == T:
        t = tables
        pad = 0
    else:
        pad = Tp - T

    def padrow(a, fill=0):
        widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths, constant_values=fill)

    mask_len = tables.mask_len.copy()
    mask_len[tables.num_entries :] = -1
    return CompiledTables(
        rule_width=tables.rule_width,
        num_entries=tables.num_entries,
        key_words=padrow(tables.key_words),
        mask_words=padrow(tables.mask_words),
        mask_len=padrow(mask_len, -1),
        rules=padrow(tables.rules),
        trie_levels=tables.trie_levels,
        root_lut=tables.root_lut,
        content=tables.content,
    )


def shard_tables(tables: CompiledTables, mesh: Mesh) -> DeviceTables:
    """Place compiled tables on the mesh: dense arrays sharded along the
    target axis over "rules", trie arrays replicated."""
    shards = mesh.shape["rules"]
    padded = _pad_tables_for_shards(tables, shards)

    def put(a, spec):
        return jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))

    mask_len = padded.mask_len
    return DeviceTables(
        key_words=put(padded.key_words.astype(np.uint32), P("rules", None)),
        mask_words=put(padded.mask_words.astype(np.uint32), P("rules", None)),
        mask_len=put(mask_len, P("rules")),
        rules=put(padded.rules, P("rules", None, None)),
        # The dense sharded step never walks the trie; don't ship or
        # replicate the (potentially large) level arrays.
        trie_levels=(),
        trie_targets=put(np.zeros(1, np.int32), P()),
        joined=put(np.zeros((1, 1), np.uint16), P()),
        root_lut=put(padded.root_lut, P()),
        num_entries=put(np.int32(padded.num_entries), P()),
    )


def shard_batch(batch, mesh: Mesh) -> DeviceBatch:
    """Place a packet batch sharded along the data axis (pad the batch to a
    multiple of the data-shard count first, packets.PacketBatch.pad_to)."""
    def put(a, spec):
        return jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))

    return DeviceBatch(
        kind=put(batch.kind, P("data")),
        l4_ok=put(batch.l4_ok, P("data")),
        ifindex=put(batch.ifindex, P("data")),
        ip_words=put(batch.ip_words.astype(np.uint32), P("data", None)),
        proto=put(batch.proto, P("data")),
        dst_port=put(batch.dst_port, P("data")),
        icmp_type=put(batch.icmp_type, P("data")),
        icmp_code=put(batch.icmp_code, P("data")),
        pkt_len=put(batch.pkt_len, P("data")),
    )


def _local_dense_partial(tables: DeviceTables, batch: DeviceBatch):
    """Per-shard LPM over local entries: returns (local best score, raw
    scan result restricted to the local winner).  Match semantics come
    from the shared jaxpath.lpm_dense_scores — one implementation for
    single-chip and mesh."""
    score = jaxpath.lpm_dense_scores(tables, batch)
    best = jnp.max(score, axis=1)
    tidx = jnp.argmax(score, axis=1)
    rows = jnp.take(tables.rules, tidx, axis=0)
    rows = jnp.where((best > 0)[:, None, None], rows, 0)
    raw = jaxpath.rule_scan(rows, batch)
    return best.astype(jnp.int32), raw


def _combine_and_finalize(best, raw, batch: DeviceBatch):
    """Cross-shard winner selection + finalize, shared by the dense and
    trie sharded steps: the longest-prefix winner is unique (masked-
    identity dedup at compile time), so pmax over scores + psum of the
    winner's raw result reconstructs the single-chip verdict."""
    gbest = jax.lax.pmax(best, "rules")
    winner = (best == gbest) & (best > 0)
    raw = jnp.where(winner, raw, 0)
    raw = jax.lax.psum(raw, "rules")  # only the winning shard contributes
    results, xdp, stats = jaxpath.finalize(raw.astype(jnp.uint32), batch)
    # Stats: identical across the rules group (post-selection), so count
    # them once per data shard, then reduce across the whole mesh.
    stats = jnp.where(jax.lax.axis_index("rules") == 0, stats, 0)
    stats = jax.lax.psum(stats, ("data", "rules"))
    return results, xdp, stats


def _sharded_step(tables: DeviceTables, batch: DeviceBatch):
    """The full distributed step, to be wrapped in shard_map."""
    best, raw = _local_dense_partial(tables, batch)
    return _combine_and_finalize(best, raw, batch)


#: the one DeviceBatch partition-spec literal ("data"-sharded packets) —
#: every shard_map factory below consumes this instead of restating the
#: 9-field spec
_BATCH_SPECS = DeviceBatch(
    kind=P("data"), l4_ok=P("data"), ifindex=P("data"),
    ip_words=P("data", None), proto=P("data"), dst_port=P("data"),
    icmp_type=P("data"), icmp_code=P("data"), pkt_len=P("data"),
)


@functools.lru_cache(maxsize=None)
def make_sharded_classifier(mesh: Mesh, n_trie_levels: int = 0):
    """jit-compiled multi-chip classify: batch sharded over "data", dense
    tables sharded over "rules"; returns (results, xdp, stats) with
    results/xdp sharded over "data" and stats fully replicated.
    ``n_trie_levels`` must match the table's trie depth (the replicated
    trie arrays are part of the pytree structure)."""
    table_specs = DeviceTables(
        key_words=P("rules", None),
        mask_words=P("rules", None),
        mask_len=P("rules"),
        rules=P("rules", None, None),
        trie_levels=tuple(P() for _ in range(n_trie_levels)),
        trie_targets=P(),
        joined=P(),
        root_lut=P(),
        num_entries=P(),
    )
    fn = shard_map(
        _sharded_step,
        mesh=mesh,
        in_specs=(table_specs, _BATCH_SPECS),
        out_specs=(P("data"), P("data"), P()),
        check_vma=False,
    )
    return jax.jit(fn)


# --- trie sharding over "rules": 1M-rule scale -------------------------------
#
# Above single-chip trie capacity, the LPM entries themselves are
# partitioned across the "rules" axis: each chip compiles a trie over its
# own entry subset, walks it locally, and the global longest-prefix winner
# is selected with pmax over (mask_len + 1) scores.  Winner uniqueness
# holds because two distinct entries of equal mask length that both match
# one packet would have identical masked prefixes — which the compile-time
# masked-identity dedup forbids.


class ShardedTrieTables(NamedTuple):
    """Per-shard trie state stacked on a leading "rules" axis (levels in
    the poptrie device form, jaxpath.build_poptrie)."""

    trie_levels: Tuple[jax.Array, ...]  # (R, rows_0, 2) i32, then (R, n_l, 18) u32
    trie_targets: jax.Array             # (R, Tt) int32
    root_lut: jax.Array                 # (R, L) int32
    mask_len: jax.Array                 # (R, T) int32, -1 padding
    rules: jax.Array                    # (R, T, W, 7) int32


def build_trie_shards(tables: CompiledTables, shards: int) -> ShardedTrieTables:
    """Partition the table's content round-robin into ``shards`` subsets,
    compile each to the same static trie depth, and pad/stack the
    per-shard arrays (host-side; call shard_tables_trie to place them)."""
    from ..compiler import (
        compile_tables_from_content,
        trie_levels_for_mask,
    )

    # Partition the DEDUPED entry set: keys aliasing by masked identity
    # must collapse before the split, or two shards could hold equal-length
    # matching prefixes and the psum winner combine would double-count.
    dedup = {}
    for k, v in tables.content.items():
        dedup[k.masked_identity()] = (k, v)
    items = list(dedup.values())
    n_levels = max(
        trie_levels_for_mask(max((k.mask_len for k, _ in items), default=0)), 1
    )
    subs = [
        compile_tables_from_content(
            {k: v for k, v in items[i::shards]},
            rule_width=tables.rule_width,
            min_trie_levels=n_levels,
        )
        for i in range(shards)
    ]

    def pad_to(a: np.ndarray, rows: int, fill=0) -> np.ndarray:
        widths = [(0, rows - a.shape[0])] + [(0, 0)] * (a.ndim - 1)
        return np.pad(a, widths, constant_values=fill)

    # per-shard poptrie transforms (padding rows are zero = empty nodes /
    # sentinel targets, unreachable by construction)
    pops = [jaxpath.build_poptrie(s) for s in subs]
    levels = []
    for l in range(n_levels):
        rows = max(p[0][l].shape[0] for p in pops)
        stacked = np.stack([pad_to(p[0][l], rows) for p in pops])
        levels.append(stacked)
    t_len = max(p[1].shape[0] for p in pops)
    trie_targets = np.stack([pad_to(p[1], t_len) for p in pops])
    lut_len = max(s.root_lut.shape[0] for s in subs)
    root_lut = np.stack([pad_to(s.root_lut, lut_len) for s in subs])
    T = max(s.mask_len.shape[0] for s in subs)
    mask_len = np.stack(
        [
            pad_to(np.where(np.arange(s.mask_len.shape[0]) < s.num_entries,
                            s.mask_len, -1), T, fill=-1)
            for s in subs
        ]
    )
    rules = np.stack([pad_to(s.rules, T) for s in subs])
    return ShardedTrieTables(
        trie_levels=tuple(levels),
        trie_targets=trie_targets.astype(np.int32),
        root_lut=root_lut.astype(np.int32),
        mask_len=mask_len.astype(np.int32),
        rules=rules.astype(np.int32),
    )


def shard_tables_trie(tables: CompiledTables, mesh: Mesh) -> ShardedTrieTables:
    """Place the per-shard tries on the mesh, leading axis over "rules"."""
    shards = mesh.shape["rules"]
    host = build_trie_shards(tables, shards)

    def put(a, spec):
        return jax.device_put(jnp.asarray(a), NamedSharding(mesh, spec))

    return ShardedTrieTables(
        trie_levels=tuple(put(t, P("rules", None, None)) for t in host.trie_levels),
        trie_targets=put(host.trie_targets, P("rules", None)),
        root_lut=put(host.root_lut, P("rules", None)),
        mask_len=put(host.mask_len, P("rules", None)),
        rules=put(host.rules, P("rules", None, None, None)),
    )


def _trie_shard_partial(
    tables: ShardedTrieTables, batch: DeviceBatch,
    v4_only: bool = False, depth: Optional[int] = None,
):
    """Per-shard trie walk + score: the local half of the sharded trie
    step, shared by the DeviceBatch and wire serving paths.  ``v4_only``
    and ``depth`` apply the same level truncation as the single-chip
    classify_wire (jaxpath): safe per shard because each shard's trie
    holds a SUBSET of the global entries, so a slot's per-shard depth
    requirement never exceeds the global LUT value the steering used."""
    local_levels = tuple(t[0] for t in tables.trie_levels)  # drop shard dim
    if v4_only:
        local_levels = local_levels[: jaxpath.v4_trie_depth(len(local_levels))]
    elif depth is not None:
        local_levels = local_levels[: 1 + depth]
    tidx = jaxpath.trie_walk(
        local_levels, tables.trie_targets[0], tables.root_lut[0], batch
    )
    matched = tidx >= 0
    safe = jnp.clip(tidx, 0)
    best = jnp.where(
        matched, jnp.take(tables.mask_len[0], safe) + 1, 0
    ).astype(jnp.int32)
    rows = jnp.take(tables.rules[0], safe, axis=0)
    rows = jnp.where(matched[:, None, None], rows, 0)
    raw = jaxpath.rule_scan(rows, batch)
    return best, raw


def _sharded_trie_step(tables: ShardedTrieTables, batch: DeviceBatch):
    """Distributed trie step inside shard_map: local walk + one mask_len
    gather for the score, then the same pmax/psum winner selection as the
    dense path."""
    best, raw = _trie_shard_partial(tables, batch)
    return _combine_and_finalize(best, raw, batch)


@functools.lru_cache(maxsize=None)
def make_sharded_trie_classifier(mesh: Mesh, n_trie_levels: int):
    """jit-compiled multi-chip trie classify: batch over "data", LPM
    entries partitioned over "rules" as per-shard tries."""
    table_specs = ShardedTrieTables(
        trie_levels=tuple(P("rules", None, None) for _ in range(n_trie_levels)),
        trie_targets=P("rules", None),
        root_lut=P("rules", None),
        mask_len=P("rules", None),
        rules=P("rules", None, None, None),
    )
    fn = shard_map(
        _sharded_trie_step,
        mesh=mesh,
        in_specs=(table_specs, _BATCH_SPECS),
        out_specs=(P("data"), P("data"), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def classify_on_mesh_trie(
    mesh: Mesh,
    tables: CompiledTables,
    batch,
    placed: Optional[ShardedTrieTables] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Convenience wrapper for the trie-sharded path.

    Building/placing the per-shard tries is the expensive part at scale —
    callers classifying a stream of batches against one ruleset should
    call shard_tables_trie ONCE and pass the handle via ``placed``; only
    the batch is shipped per call."""
    data_shards = mesh.shape["data"]
    b = len(batch)
    bp = ((b + data_shards - 1) // data_shards) * data_shards
    padded = batch.pad_to(bp)
    dt = placed if placed is not None else shard_tables_trie(tables, mesh)
    db = shard_batch(padded, mesh)
    results, xdp, stats = make_sharded_trie_classifier(
        mesh, len(dt.trie_levels)
    )(dt, db)
    return (
        np.asarray(results)[:b],
        np.asarray(xdp)[:b],
        np.asarray(stats),
    )


# --- wire-format serving steps (backend/mesh.py MeshTpuClassifier) ----------
#
# The production dispatch contract of backend/tpu.py — packed wire
# descriptors in, ONE fused D2H buffer out — lifted onto the mesh: the
# wire is sharded over "data" (per-shard H2D staging starts at
# device_put time, so the daemon's double-buffered prepare/launch split
# overlaps per-chip transfers with in-flight classifies), each shard
# classifies its rows with the SAME kernels as the single chip (XLA trie
# walk, fused Pallas deep walk, int8 Pallas dense), and statistics are
# combined on device with one psum — the host reads one merged stats
# array instead of N per-chip copies.
#
# Output layout (split_fused_wire_outputs): out_spec P("data") over the
# per-shard concat(packed res16, psum'd stats flat), i.e. globally
# (data_shards * (nw + S),) int32 with nw = per-shard ceil(rows/2) result
# words and S = MAX_TARGETS*STATS_COLS.  Per-shard row counts must be
# EVEN (callers pad the wire to a multiple of 2*data_shards) so the u16
# pair packing never straddles a shard boundary; the stats block repeats
# per shard (identical post-psum copies, ~24KB each) to preserve the
# one-materialization-per-chunk contract (each readback pays the
# host-device sync floor once).


def _guarded_stats_psum(stats):
    """Mesh-wide stats reduction for REPLICATED-table steps: along
    "rules" every shard computed identical stats (same packets, same
    tables), so count one replica per data shard, then one psum over the
    whole mesh — the device-side replacement for N host-side merges."""
    stats = jnp.where(jax.lax.axis_index("rules") == 0, stats, 0)
    return jax.lax.psum(stats, ("data", "rules"))


def _fused_wire_out(res16, stats):
    """Per-shard single-buffer output: packed u16 results then the
    (replicated) stats — see jaxpath.fuse_wire_outputs for why one D2H
    buffer matters."""
    return jnp.concatenate(
        [jaxpath._pack_res16(res16), stats.reshape(-1).astype(jnp.int32)]
    )


def split_fused_wire_outputs(
    arr: np.ndarray, n: int, data_shards: int, with_stats: bool = True
) -> Tuple[np.ndarray, Optional[np.ndarray]]:
    """Host inverse of the mesh fused output: (results_u16[n], stats) —
    stats from the first shard's block (post-psum replicas are
    identical), None for the stats-less wire8 layout."""
    from ..constants import MAX_TARGETS

    blocks = np.asarray(arr).reshape(data_shards, -1)
    s = MAX_TARGETS * jaxpath.STATS_COLS if with_stats else 0
    nw = blocks.shape[1] - s
    res16 = jaxpath.unpack_res16_host(
        np.ascontiguousarray(blocks[:, :nw]).reshape(-1), 2 * nw * data_shards
    )
    stats = (
        blocks[0, nw:].reshape(MAX_TARGETS, jaxpath.STATS_COLS)
        if with_stats else None
    )
    return res16[:n], stats


#: (mesh, variant, treedefs, statics) -> jitted shard_map program.  jit
#: itself re-specializes per shape; this cache only pins the shard_map
#: wrapping so repeated builds return the SAME jitted object (the
#: factory-identity half of the recompile lint).
_SERVE_CACHE: dict = {}


def _replicated_specs(tree):
    return jax.tree.map(lambda _: P(), tree)


def _sharded_specs(tree):
    """Partition specs read back from how the arrays were placed
    (shard_tables / shard_tables_trie place every leaf with an explicit
    NamedSharding, so .sharding.spec is authoritative)."""
    return jax.tree.map(lambda a: a.sharding.spec, tree)


def jitted_mesh_wire(
    mesh: Mesh, variant: str, dev, *, v4_only: bool = False,
    depth: Optional[int] = None, interpret: bool = False,
    block_b: Optional[int] = None, overlay=None,
):
    """jit-compiled mesh wire classify, one fused output buffer.

    Variants (``dev`` is the matching device pytree):
      - "trie":          replicated DeviceTables, XLA walk (v4_only /
                         depth truncation like the single chip)
      - "trie-overlay":  + replicated dense overlay side-table combine
      - "trie-sharded":  ShardedTrieTables, per-shard tries over "rules",
                         pmax/psum winner combine
      - "dense-sharded": DeviceTables target-sharded over "rules"
      - "pallas-dense":  replicated PallasTables, int8 MXU kernel per
                         shard (the single-chip headline kernel under
                         shard_map)
      - "walk":          replicated WalkTables, fused Pallas deep walk
                         per shard"""
    tdef = jax.tree_util.tree_structure(dev)
    odef = None if overlay is None else jax.tree_util.tree_structure(overlay)
    key = ("wire", mesh, variant, tdef, odef, v4_only, depth, interpret,
           block_b)
    cached = _SERVE_CACHE.get(key)
    if cached is not None:
        return cached
    from ..kernels import pallas_dense, pallas_walk

    if variant == "trie":
        def body(t, wire):
            res16, stats = jaxpath.classify_wire(
                t, wire, use_trie=True, v4_only=v4_only, depth=depth
            )
            return _fused_wire_out(res16, _guarded_stats_psum(stats))

        in_specs = (_replicated_specs(dev), P("data", None))
    elif variant == "trie-overlay":
        def body(t, ov, wire):
            res16, stats = jaxpath.classify_wire_overlay(
                t, ov, wire, use_trie=True, v4_only=v4_only, depth=depth
            )
            return _fused_wire_out(res16, _guarded_stats_psum(stats))

        in_specs = (
            _replicated_specs(dev), _replicated_specs(overlay),
            P("data", None),
        )
    elif variant == "trie-sharded":
        def body(t, wire):
            batch = jaxpath.unpack_wire(wire)
            best, raw = _trie_shard_partial(
                t, batch, v4_only=v4_only, depth=depth
            )
            results, _xdp, stats = _combine_and_finalize(best, raw, batch)
            return _fused_wire_out(results.astype(jnp.uint16), stats)

        in_specs = (_sharded_specs(dev), P("data", None))
    elif variant == "dense-sharded":
        def body(t, wire):
            batch = jaxpath.unpack_wire(wire)
            best, raw = _local_dense_partial(t, batch)
            results, _xdp, stats = _combine_and_finalize(best, raw, batch)
            return _fused_wire_out(results.astype(jnp.uint16), stats)

        in_specs = (_sharded_specs(dev), P("data", None))
    elif variant == "pallas-dense":
        bb = block_b if block_b is not None else pallas_dense.BLOCK_B

        def body(t, wire):
            res16, stats = pallas_dense.classify_pallas_wire(
                t, wire, interpret=interpret, block_b=bb
            )
            return _fused_wire_out(res16, _guarded_stats_psum(stats))

        in_specs = (_replicated_specs(dev), P("data", None))
    elif variant == "walk":
        def body(t, wire):
            res16, stats = pallas_walk.classify_walk_wire(
                t, wire, interpret=interpret
            )
            return _fused_wire_out(res16, _guarded_stats_psum(stats))

        in_specs = (_replicated_specs(dev), P("data", None))
    else:
        raise ValueError(f"unknown mesh wire variant {variant!r}")
    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=P("data"),
        check_vma=False,
    ))
    _SERVE_CACHE[key] = fn
    return fn


def jitted_mesh_wire8(mesh: Mesh, dev, *, overlay=None):
    """Mesh wire8 classify: (B, 2) wire sharded over "data", replicated
    ifindex dictionary; packed res16-only output (statistics derive
    host-side from the verdicts — the wire8 readback contract)."""
    tdef = jax.tree_util.tree_structure(dev)
    odef = None if overlay is None else jax.tree_util.tree_structure(overlay)
    key = ("wire8", mesh, tdef, odef)
    cached = _SERVE_CACHE.get(key)
    if cached is not None:
        return cached
    if overlay is None:
        def body(t, wire, ifmap):
            return jaxpath.classify_wire8(t, wire, ifmap, v4_only=True)

        in_specs = (_replicated_specs(dev), P("data", None), P())
    else:
        def body(t, ov, wire, ifmap):
            return jaxpath.classify_wire8(t, wire, ifmap, ov, v4_only=True)

        in_specs = (
            _replicated_specs(dev), _replicated_specs(overlay),
            P("data", None), P(),
        )
    fn = jax.jit(shard_map(
        body, mesh=mesh, in_specs=in_specs, out_specs=P("data"),
        check_vma=False,
    ))
    _SERVE_CACHE[key] = fn
    return fn


def jitted_mesh_classify(
    mesh: Mesh, variant: str, dev, *, interpret: bool = False,
    block_b: Optional[int] = None,
):
    """u32-results mesh classify (results, xdp, stats) for the paths the
    2B wire result cannot carry (wide ruleIds) and for the bench's
    chained throughput loops.  Variants: "trie" (replicated
    DeviceTables), "pallas-dense" (replicated PallasTables)."""
    tdef = jax.tree_util.tree_structure(dev)
    key = ("u32", mesh, variant, tdef, interpret, block_b)
    cached = _SERVE_CACHE.get(key)
    if cached is not None:
        return cached
    from ..kernels import pallas_dense

    if variant == "trie":
        def body(t, batch):
            res, xdp, stats = jaxpath.classify(t, batch, use_trie=True)
            return res, xdp, _guarded_stats_psum(stats)
    elif variant == "pallas-dense":
        bb = block_b if block_b is not None else pallas_dense.BLOCK_B

        def body(t, batch):
            res, xdp, stats = pallas_dense.classify_pallas(
                t, batch, interpret=interpret, block_b=bb
            )
            return res, xdp, _guarded_stats_psum(stats)
    else:
        raise ValueError(f"unknown mesh u32 variant {variant!r}")
    fn = jax.jit(shard_map(
        body, mesh=mesh,
        in_specs=(_replicated_specs(dev), _BATCH_SPECS),
        out_specs=(P("data"), P("data"), P()),
        check_vma=False,
    ))
    _SERVE_CACHE[key] = fn
    return fn


def classify_on_mesh(
    mesh: Mesh, tables: CompiledTables, batch
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Convenience wrapper: shard, classify, fetch host results."""
    data_shards = mesh.shape["data"]
    b = len(batch)
    bp = ((b + data_shards - 1) // data_shards) * data_shards
    padded = batch.pad_to(bp)
    dt = shard_tables(tables, mesh)
    db = shard_batch(padded, mesh)
    results, xdp, stats = make_sharded_classifier(mesh, len(dt.trie_levels))(dt, db)
    return (
        np.asarray(results)[:b],
        np.asarray(xdp)[:b],
        np.asarray(stats),
    )


# --- multi-tenant paged arena on the mesh (ISSUE-10) ------------------------
#
# The slab-family partition rules, declared ONCE per family (the
# SNIPPETS.md NamedSharding pytree-spec pattern) and reused across
# every tenant: pool arrays are row-sharded over the "rules" axis in
# WHOLE-SLAB blocks (pages % rules_shards == 0, so no slab straddles a
# shard) — capacity scales with the axis — while the tenant -> page
# table replicates.  Dispatch needs no arena-specific shard_map: the
# pool placement engages GSPMD under the SAME jitted classify
# factories the single chip uses (jaxpath.jitted_classify_arena_wire_
# fused), with the wire/tenant operands sharded over "data".
#
# Content-addressed CoW sharing (ISSUE-15) composes with these rules
# for free: a SHARED page is still exactly one whole-slab block on one
# "rules" shard — refcounts and the hash index are host bookkeeping
# GSPMD never sees, sharing flips are the same replicated 1-row
# page-table scatter as a private swap, and a CoW clone lands through
# the same replicated full-slab write as a bake.  Nothing here is
# per-tenant, so 100K page-table rows referencing 100 slabs place
# identically to 100 rows referencing 100 slabs.

ARENA_PARTITION_RULES = {
    "dense": {
        "key_words": P("rules", None),
        "mask_words": P("rules", None),
        "mask_len": P("rules"),
        "rules": P("rules", None),
        "page_table": P(),
    },
    "ctrie": {
        "l0": P("rules", None),
        "nodes": P("rules", None),
        "targets": P("rules"),
        "joined": P("rules", None),
        "root_lut": P("rules"),
        # splice rows steer per packet like the page table: replicated
        "splice": P(),
        "page_table": P(),
    },
}


def arena_shardings(mesh: Mesh, family: str, pages: int,
                    spliced: bool = False):
    """Per-pool-array NamedShardings for an arena on ``mesh``.  Pages
    shard over "rules" when they divide the axis; otherwise everything
    replicates (capacity does not scale, correctness never at risk) —
    the usual degrade-never-refuse posture.  A SPLICED ctrie arena
    appends the shared subtree plane pool to the node/target/joined
    pools, so rows are no longer whole-page blocks: replicate the lot
    (the plane pool IS the compressed form — capacity already scaled)."""
    rules = mesh.shape["rules"]
    if family not in ARENA_PARTITION_RULES:
        raise ValueError(f"unknown arena family {family!r}")
    specs = ARENA_PARTITION_RULES[family]
    if spliced or (rules > 1 and pages % rules != 0):
        specs = {k: P() for k in specs}
    return {k: NamedSharding(mesh, s) for k, s in specs.items()}


def arena_replicated(mesh: Mesh) -> NamedSharding:
    """The placement for arena scatter payloads / page-table flips —
    broadcast to every chip in one staging pass, exactly like the
    replicated txn-scatter path."""
    return NamedSharding(mesh, P())


# --- stateful flow tier (ISSUE-11) ------------------------------------------
#
# The flow slab family's partition rules, declared once like the arena
# pools: flow columns row-shard over "rules" when the row count divides
# the axis (capacity scales with it; the probe/insert gathers and
# scatters engage GSPMD under the SAME jitted factories the single chip
# uses), while the small per-tenant steering state (generation vector,
# flow page table) replicates like the arena page table.

FLOW_PARTITION_RULES = {
    # the FlowTable columns (jaxpath.FlowTable: keys / vg / se / cnt)
    "keys": P("rules", None),
    "vg": P("rules", None),
    "se": P("rules", None),
    "cnt": P("rules", None),
    # per-tenant steering state: replicated like the arena page table
    "gens": P(),
    "page_table": P(),
    "max_age": P(),
}


def flow_shardings(mesh: Mesh, capacity: int):
    """Per-column NamedShardings for a flow tier on ``mesh``: rows over
    "rules" when the capacity divides the axis, else fully replicated —
    the degrade-never-refuse posture of the arena placement."""
    rules = mesh.shape["rules"]
    specs = FLOW_PARTITION_RULES
    if rules > 1 and capacity % rules != 0:
        specs = {k: P() for k in specs}
    return {k: NamedSharding(mesh, s) for k, s in specs.items()}


def arena_data_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P("data", None))
