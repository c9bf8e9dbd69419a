"""Zero-copy resident serving pool (ISSUE-12).

The host floor the SLO tier measured — Python -> pack -> encode -> H2D ->
launch(probe) -> launch(classify) -> launch(insert) -> D2H with fresh
buffer construction at every hop — collapses to: write the wire into a
preallocated slot, start ONE async H2D, launch ONE fused device program
(kernels.jaxpath.jitted_resident_step: decode + flow probe + stateless
classify + merge + stats + miss insert), read ONE fused buffer back.
The mutable flow columns and the epoch scalar are donated, so XLA
rewrites them in place across dispatches (input-output aliasing, checked
by the jaxcheck donation lint) and the steady-state loop performs zero
pool allocations — the residual host work is pointer bumps on
preallocated memory (the Gallium offload split, PAPERS.md), the device
work is one program per admission (the hXDP move, applied to the
serving loop).

``ResidentPool`` owns the per-table-generation program context (the
classify operands the fused step closes over) and the allocation
counters the bench gate asserts:

- ``allocs``: fresh persistent device buffers the resident path created
  (per-generation table snapshots, per-rung zero columns, epoch
  re-seeds).  Flat across a warmed steady-state run — the
  "zero device allocations" gate of bench_resident.
- ``dispatches`` / ``reuses``: fused launches and context cache hits.
- ``fallbacks``: admissions that declined the resident path (wide
  ruleIds, unsupported width) and fell back to the multi-dispatch plan.

The table-generation check is THE staleness guard: every
``load_tables`` bumps the classifier's generation token, and the pool
rebuilds its captured classify operands when the token moves.
``_INJECT_RESIDENT_STALE_BUG`` (tools/infw_lint.py state
--inject-defect residentstale) drops exactly that check — the donated
serving loop keeps classifying against the pre-patch tables — and the
statecheck ``resident`` config must catch it by oracle divergence with
a shrunk reproducer.
"""
from __future__ import annotations

import os
import threading
from typing import NamedTuple, Optional

import numpy as np

#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_RESIDENT_STALE_BUG env var), the pool's table-generation
#: staleness check is dropped — after a rule patch the resident fused
#: program keeps serving from the stale captured table operands.  Never
#: set in production.
_INJECT_RESIDENT_STALE_BUG = False


def _inject_resident_stale_bug() -> bool:
    if _INJECT_RESIDENT_STALE_BUG:
        return True
    env = os.environ.get("INFW_INJECT_RESIDENT_STALE_BUG", "")
    return env not in ("", "0", "false", "no")


class ResidentContext(NamedTuple):
    """The fused step's per-table-generation classify operands."""

    gen: int
    path: str           # "dense" | "trie" | "ctrie"
    tdev: object        # DeviceTables | CTrieTables
    ov_dev: object      # DeviceTables | None
    d_max: int          # ctrie static unroll bound (0 otherwise)


class ResidentPool:
    """Donated-buffer pool + program-context cache for one classifier.

    Thread-safety: context() may race load_tables — the generation token
    is read under the CLASSIFIER's lock together with the active tables,
    so a context can never pair a token with another generation's
    operands; the pool's own lock guards only its cache and counters.
    """

    #: two in-flight admissions (the ISSUE-16 pipeline): slot N+1's
    #: pack/encode/H2D and slot N-1's fused readback overlap slot N's
    #: compute — the bound the daemon's ring ingest and the scheduler's
    #: stage_depth both honor on the resident path
    PIPELINE_SLOTS = 2

    def __init__(self, device=None) -> None:
        self._lock = threading.Lock()
        self._ctx: Optional[ResidentContext] = None
        self._device = device
        self.counters = {
            "allocs": 0, "reuses": 0, "dispatches": 0, "fallbacks": 0,
            # superbatch (device-side epoch loop, ISSUE-16): one
            # dispatch chews k stacked admissions entirely on-device
            "superbatch_dispatches": 0, "superbatch_admissions": 0,
            # per-pipeline-slot dispatch parity (observability: a stuck
            # slot shows as one counter flatlining)
            "slot0_dispatches": 0, "slot1_dispatches": 0,
            # which classify the context serves, per dispatch
            "path_dense_dispatches": 0, "path_trie_dispatches": 0,
            "path_ctrie_dispatches": 0,
            # the level walk (note_walk): lanes x levels the step walks,
            # against lanes x levels the lanes' families need
            "trie_lane_levels_walked": 0, "trie_lane_levels_needed": 0,
        }
        #: allocation count at warm-completion (mark_warm): the serving-
        #: path gate is allocs - warm_allocs == 0
        self.warm_allocs: Optional[int] = None

    # -- counters ------------------------------------------------------------

    def note(self, key: str, n: int = 1) -> None:
        with self._lock:
            self.counters[key] = self.counters.get(key, 0) + n

    def note_alloc(self, _what: str = "") -> None:
        self.note("allocs")

    def mark_warm(self) -> None:
        """Freeze the prewarm allocation baseline: every pool
        allocation after this point happened on the serving path (the
        bench_resident zero-alloc gate reads steady_allocs())."""
        with self._lock:
            self.warm_allocs = self.counters["allocs"]

    def steady_allocs(self) -> int:
        with self._lock:
            if self.warm_allocs is None:
                return self.counters["allocs"]
            return self.counters["allocs"] - self.warm_allocs

    def note_walk(self, ctx: "ResidentContext", kind: np.ndarray,
                  v4_only: bool, depth: Optional[int]) -> None:
        """Count one dispatch of ``kind`` lanes (k admissions stacked
        count as one): its classify path and, on the level walk, the
        lanes x levels the step walks (all lanes walk the same static
        depth, jaxpath.walk_levels) against the lanes x levels their
        family needs — an IPv4 lane the levels down to the /32 boundary,
        an IPv6 lane the table's full depth, any other lane none."""
        from .kernels import jaxpath

        counts = {f"path_{ctx.path}_dispatches": 1}
        if ctx.path == "trie":
            full = len(ctx.tdev.trie_levels)
            v4 = jaxpath.v4_trie_depth(full)
            kind = np.asarray(kind)
            counts["trie_lane_levels_walked"] = (
                kind.size * jaxpath.walk_levels(full, v4_only, depth))
            counts["trie_lane_levels_needed"] = (
                v4 * int(np.count_nonzero(kind == jaxpath.KIND_IPV4))
                + full * int(np.count_nonzero(kind == jaxpath.KIND_IPV6)))
        with self._lock:
            for key, n in counts.items():
                self.counters[key] = self.counters.get(key, 0) + n

    def counter_values(self) -> dict:
        """resident_* gauges for /metrics, with the shape of the table
        the context serves: its device bytes and, on the level walk, the
        trie's level count and rows per level (level 0 is the DIR-16
        root's slot rows, the others node rows)."""
        with self._lock:
            out = {f"resident_{k}_total": v for k, v in self.counters.items()}
            out["resident_pool_warm"] = int(self.warm_allocs is not None)
            out["resident_steady_allocs"] = (
                self.counters["allocs"] - self.warm_allocs
                if self.warm_allocs is not None else 0
            )
            ctx = self._ctx
        if ctx is not None:
            import jax

            out["resident_table_device_bytes"] = sum(
                int(a.nbytes) for a in jax.tree.leaves(ctx.tdev))
            if ctx.path == "trie":
                levels = ctx.tdev.trie_levels
                out["resident_trie_levels"] = len(levels)
                for i, level in enumerate(levels):
                    out[f"resident_trie_level{i}_rows"] = int(level.shape[0])
        return out

    # -- program context -----------------------------------------------------

    def context(self, clf) -> Optional[ResidentContext]:
        """The classify operands of the CURRENT table generation, or
        None when the resident path cannot serve this generation (no
        tables, wide ruleIds) — the caller falls back to the
        multi-dispatch plan.

        Cache discipline: one context per generation token; a stale hit
        is impossible because the token is assigned inside the
        classifier's install lock (load_tables) and read here together
        with the active tuple.  The injected residentstale defect
        returns the cached context WITHOUT the token check — the stale
        donated serving loop the statecheck acceptance must catch."""
        from .kernels import jaxpath

        with self._lock:
            ctx = self._ctx
        if ctx is not None and _inject_resident_stale_bug():
            self.note("reuses")
            return ctx
        with clf._lock:
            active = clf._active
            tables = clf._tables
            gen = clf._depth_gen
        if active is None:
            return None
        path, dev, _block_b, wide_rids, ov_dev, _walk = active
        if wide_rids:
            return None
        if ctx is not None and ctx.gen == gen:
            self.note("reuses")
            return ctx
        if path == "ctrie":
            if not (isinstance(dev, tuple) and len(dev) == 2):
                return None
            tdev, d_max = dev[0], dev[1]
        elif path == "trie":
            if not isinstance(dev, jaxpath.DeviceTables):
                # mesh rules-sharded partitions re-place per load and
                # are not the resident program's operand shape — the
                # multi-dispatch plan keeps serving them
                return None
            tdev, d_max = dev, 0
        else:
            # dense path: the resident program is pure XLA (the Pallas
            # dense kernel cannot compose into the fused step), so keep
            # a DeviceTables twin of the small dense table — built once
            # per generation, bit-identical verdicts either way
            try:
                jaxpath.check_wire_ruleids(tables)
            except ValueError:
                return None
            tdev = jaxpath.device_tables(tables, clf._device, pad=True)
            d_max = 0
            self.note_alloc("dense-twin")
        ctx = ResidentContext(
            gen=gen, path=path, tdev=tdev, ov_dev=ov_dev, d_max=d_max,
        )
        with self._lock:
            self._ctx = ctx
        self.note_alloc("context")
        return ctx

    def stage_wire(self, clf, wire_np: np.ndarray):
        """Start the async H2D of one wire chunk (the per-admission
        staging copy: on the CPU backend this aliases aligned host
        memory — e.g. a pinned ring slot — and on device backends it
        rides XLA's stream arena, not the pool)."""
        import jax

        return jax.device_put(
            np.ascontiguousarray(wire_np, np.uint32), clf._device
        )
