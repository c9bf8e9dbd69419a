"""Stateful flow tier: device-resident connection tracking with an
exact-match fast path (ISSUE-11).

The dataplane's verdict cache: a W-way set-associative hash table in
fixed-shape device tensors (kernels.jaxpath FlowTable) probed BEFORE the
LPM + ordered rule scan.  A hit serves the cached res16 verdict (with
per-flow packet/byte counters and TCP-state transitions updated
in-kernel); only the misses fall through to the stateless classify,
compacted to a pow2 bucket so a 90%-established batch pays ~1/8 of the
LPM+scan cost, and their fresh verdicts batch-insert back into the
table in one scatter dispatch.

Correctness invariant (oracle-gated everywhere — tests, bench_flow, the
statecheck flow configs): a flow hit returns EXACTLY what the stateless
path would.  Three mechanisms make that hold:

- the flow key covers every verdict-relevant packet field (tenant,
  ifindex, all 4 source-IP words, proto, dst_port, icmp type/code,
  kind, l4_ok) — pkt_len only feeds statistics;
- entries are GENERATION-stamped: a hit requires the entry's recorded
  per-tenant ruleset generation to equal the current one, and every
  table mutation (incremental patch, folded txn flush, full reload,
  tenant swap/destroy) bumps the generation — so a patch can never
  serve a stale verdict, with no O(table) flush on the mutation path;
- verdicts inserted by an in-flight dispatch carry the generation
  captured at PROBE time, so a verdict computed against superseded
  tables is stale on arrival.

TCP-state model (SYN/EST/FIN/RST gating what counts as "established"):
non-TCP flows establish on first insert; a TCP flow whose first packet
is a pure SYN is tracked as NEW but NOT serve-eligible (SYN floods never
graduate into the fast path) and promotes to EST on its next packet;
FIN marks half-close (still served — verdicts stay bit-identical either
way); RST tears the entry down.  Sources without TCP flags (flags
column absent -> 0) degrade to established-on-first-packet.

The numpy HostFlowModel mirrors every device mutation bit-exactly
(deterministic scatter forms only) — it is the host-model oracle the
statecheck flow configs compare device columns against after every
settled op.
"""
from __future__ import annotations

import os
import threading
from typing import Callable, Dict, NamedTuple, Optional

import numpy as np

from .constants import (
    IPPROTO_ICMP,
    IPPROTO_ICMPV6,
    IPPROTO_TCP,
    KIND_IPV4,
    KIND_IPV6,
)
from .kernels.jaxpath import (
    FLOW_EMPTY,
    FLOW_EST,
    FLOW_FIN,
    FLOW_KEY_WORDS,
    FLOW_NEW,
    TCP_ACK,
    TCP_FIN,
    TCP_RST,
    TCP_SYN,
)

#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_FLOW_STALE_BUG env var), FlowTier.bump_generation is a
#: full no-op — the invalidation a rule patch / tenant swap must apply
#: is DROPPED, so resident flow entries keep serving the pre-edit
#: verdict.  The statecheck acceptance gate (tools/infw_lint.py state
#: --inject-defect flowstale) proves the model checker catches this via
#: oracle divergence with a shrunk reproducer.  Never set in production.
_INJECT_FLOW_STALE_BUG = False


def _inject_flow_stale_bug() -> bool:
    if _INJECT_FLOW_STALE_BUG:
        return True
    env = os.environ.get("INFW_INJECT_FLOW_STALE_BUG", "")
    return env not in ("", "0", "false", "no")


#: TEST-ONLY defect injection (ISSUE-16): when truthy (module flag or
#: the INFW_INJECT_SLOT_EPOCH_BUG env var), the SECOND pipeline slot's
#: resident dispatch skips the donated epoch chain — instead of riding
#: the slot-0 dispatch's incremented device scalar it re-seeds from the
#: host counter TWO behind, so the device stamps slot-1 inserts with a
#: stale epoch while the host model stamps the true one.  The statecheck
#: acceptance (tools/infw_lint.py state --inject-defect slotepoch) must
#: catch this by flow-column divergence with a shrunk reproducer.
#: Never set in production.
_INJECT_SLOT_EPOCH_BUG = False


def _inject_slot_epoch_bug() -> bool:
    if _INJECT_SLOT_EPOCH_BUG:
        return True
    env = os.environ.get("INFW_INJECT_SLOT_EPOCH_BUG", "")
    return env not in ("", "0", "false", "no")


def _pow2(n: int) -> int:
    return max(8, 1 << (max(int(n), 1) - 1).bit_length())


class FlowConfig(NamedTuple):
    """Geometry of one flow tier.  ``entries`` is PER SLAB (bucketed to
    a power of two for the mask-based double hashing); the device table
    holds ``pages * entries`` rows.  Single-tenant classifiers use one
    page; the arena tier allocates one slab per arena page, steered by
    the same tenant page table that steers classification."""

    entries: int = 1 << 14
    pages: int = 1
    ways: int = 4
    max_tenants: int = 1
    #: hit freshness horizon in probe epochs (one epoch per probe
    #: dispatch): entries last seen more than this many dispatches ago
    #: never serve and are preferred eviction victims
    max_age: int = 1 << 20

    @staticmethod
    def make(entries: int = 1 << 14, pages: int = 1, ways: int = 4,
             max_tenants: int = 1, max_age: int = 1 << 20) -> "FlowConfig":
        if entries < 1 or pages < 1 or max_tenants < 1:
            raise ValueError(
                "flow table entries, pages and max_tenants must be >= 1"
            )
        if not 1 <= ways <= 8:
            raise ValueError(f"flow ways must be in [1, 8], got {ways}")
        if max_age < 1:
            raise ValueError(f"flow max_age must be >= 1, got {max_age}")
        return FlowConfig(
            entries=_pow2(entries), pages=int(pages), ways=int(ways),
            max_tenants=int(max_tenants), max_age=int(max_age),
        )

    @property
    def capacity(self) -> int:
        return self.entries * self.pages


class FlowStats:
    """Monotonic flow-tier counters (FlowStats on /metrics)."""

    FIELDS = ("hits", "misses", "inserts", "evictions", "promotes",
              "stale_rejects", "invalidations", "aged", "age_sweeps")

    def __init__(self) -> None:
        self._lock = threading.Lock()
        for f in self.FIELDS:
            setattr(self, f, 0)

    def add(self, **kw) -> None:
        with self._lock:
            for k, v in kw.items():
                setattr(self, k, getattr(self, k) + int(v))

    def values(self) -> Dict[str, int]:
        with self._lock:
            return {f: int(getattr(self, f)) for f in self.FIELDS}


# --- host mirrors of the device wire/key/hash forms --------------------------


def host_unpack_wire(wire: np.ndarray) -> Dict[str, np.ndarray]:
    """Numpy mirror of kernels.jaxpath.unpack_wire (widths 3/4/6/7) —
    the HostFlowModel consumes the EXACT fields the device kernels see,
    so host and device keys can never drift."""
    wire = np.asarray(wire, np.uint32)
    w0 = wire[:, 0]
    w1 = wire[:, 1]
    narrow = wire.shape[1] in (3, 6)
    ip_off = 2 if narrow else 3
    b = wire.shape[0]
    if wire.shape[1] in (3, 4):
        ip_words = np.zeros((b, 4), np.uint32)
        ip_words[:, 0] = wire[:, ip_off]
    else:
        ip_words = wire[:, ip_off : ip_off + 4].astype(np.uint32)
    proto = ((w0 >> 3) & 0xFF).astype(np.int32)
    if narrow:
        is_icmp = (proto == IPPROTO_ICMP) | (proto == IPPROTO_ICMPV6)
        l4w = (w1 & 0xFFFF).astype(np.int32)
        ifindex = ((w0 >> 11) & 0xFFFF).astype(np.int32)
        dst_port = np.where(is_icmp, 0, l4w)
        icmp_type = np.where(is_icmp, l4w >> 8, 0)
        icmp_code = np.where(is_icmp, l4w & 0xFF, 0)
        pkt_len = ((w1 >> 16) & 0xFFFF).astype(np.int32)
    else:
        ifindex = wire[:, 2].astype(np.int32)
        dst_port = (w1 & 0xFFFF).astype(np.int32)
        icmp_type = ((w0 >> 11) & 0xFF).astype(np.int32)
        icmp_code = ((w0 >> 19) & 0xFF).astype(np.int32)
        pkt_len = (((w1 >> 16) & 0xFFFF) | ((w0 >> 27) << 16)).astype(
            np.int32
        )
    return {
        "kind": (w0 & 3).astype(np.int32),
        "l4_ok": ((w0 >> 2) & 1).astype(np.int32),
        "ifindex": ifindex,
        "ip_words": ip_words,
        "proto": proto,
        "dst_port": dst_port,
        "icmp_type": icmp_type,
        "icmp_code": icmp_code,
        "pkt_len": pkt_len,
    }


def host_flow_key_words(f: Dict[str, np.ndarray],
                        tenant: np.ndarray) -> np.ndarray:
    m0 = (
        (f["proto"].astype(np.uint32) & 0xFF)
        | ((f["dst_port"].astype(np.uint32) & 0xFFFF) << 8)
        | ((f["kind"].astype(np.uint32) & 3) << 24)
        | ((f["l4_ok"].astype(np.uint32) & 1) << 26)
    )
    m1 = (f["icmp_type"].astype(np.uint32) & 0xFF) | (
        (f["icmp_code"].astype(np.uint32) & 0xFF) << 8
    )
    return np.stack(
        [
            tenant.astype(np.uint32),
            f["ifindex"].astype(np.uint32),
            f["ip_words"][:, 0],
            f["ip_words"][:, 1],
            f["ip_words"][:, 2],
            f["ip_words"][:, 3],
            m0,
            m1,
        ],
        axis=1,
    )


def host_flow_hash(keys: np.ndarray):
    h = np.full(keys.shape[0], 0x811C9DC5, np.uint32)
    for w in range(FLOW_KEY_WORDS):
        h = (h ^ keys[:, w].astype(np.uint32)) * np.uint32(0x01000193)
    return h, (h >> np.uint32(16)) | np.uint32(1)


def host_flow_slots(keys: np.ndarray, page: np.ndarray, *,
                    slab_entries: int, ways: int) -> np.ndarray:
    h1, h2 = host_flow_hash(keys)
    w = np.arange(ways, dtype=np.uint32)[None, :]
    local = (h1[:, None] + w * h2[:, None]) & np.uint32(slab_entries - 1)
    return (
        np.clip(page, 0, None)[:, None] * slab_entries
        + local.astype(np.int32)
    )


class HostFlowModel:
    """Bit-exact numpy mirror of the device flow table: same key/hash
    forms, same way-choice and winner-dedup rules, same deterministic
    scatter semantics (add/max/min plus per-slot-unique set) — the
    statecheck flow configs compare every device column against this
    after each settled op."""

    def __init__(self, config: FlowConfig) -> None:
        self.config = config
        C = config.capacity
        self.keys = np.zeros((C, FLOW_KEY_WORDS), np.uint32)
        self.vg = np.zeros((C, 2), np.int32)   # [verdict, gen]
        self.se = np.zeros((C, 2), np.int32)   # [state, epoch]
        self.cnt = np.zeros((C, 3), np.int32)  # [pkts, bhi, blo]
        self.gens = np.zeros(config.max_tenants, np.int32)
        self.page_table = np.full(config.max_tenants, -1, np.int32)
        if config.pages == 1 and config.max_tenants == 1:
            self.page_table[0] = 0

    def columns(self) -> Dict[str, np.ndarray]:
        return {
            "keys": self.keys, "vg": self.vg, "se": self.se,
            "cnt": self.cnt,
        }

    def _lanes(self, wire, tenant, tflags):
        f = host_unpack_wire(wire)
        b = wire.shape[0]
        tenant = (
            np.zeros(b, np.int32) if tenant is None
            else np.asarray(tenant, np.int32)
        )
        tflags = (
            np.zeros(b, np.int32) if tflags is None
            else np.asarray(tflags, np.int32)
        )
        mt = self.config.max_tenants
        t_ok = (tenant >= 0) & (tenant < mt)
        page = np.where(
            t_ok, self.page_table[np.clip(tenant, 0, mt - 1)], -1
        )
        keyw = host_flow_key_words(f, tenant)
        is_ip = (f["kind"] == KIND_IPV4) | (f["kind"] == KIND_IPV6)
        cand = host_flow_slots(
            keyw, page, slab_entries=self.config.entries,
            ways=self.config.ways,
        )
        return f, tenant, tflags, page, keyw, is_ip, cand

    def probe(self, wire, tenant, tflags, epoch_now: int):
        """Mirror of jaxpath._flow_probe_core -> (res16, hit mask,
        hits, stale); mutates counters/epoch/state like the device."""
        cfg = self.config
        f, tenant, tflags, page, keyw, is_ip, cand = self._lanes(
            wire, tenant, tflags
        )
        elig = is_ip & (f["l4_ok"] != 0) & (page >= 0)
        ek = self.keys[cand]
        ese = self.se[cand]
        evg = self.vg[cand]
        match = np.all(ek == keyw[:, None, :], axis=2) & elig[:, None]
        live = ese[:, :, 0] >= FLOW_EST
        mygen = self.gens[np.clip(tenant, 0, cfg.max_tenants - 1)]
        gen_ok = evg[:, :, 1] == mygen[:, None]
        fresh = (epoch_now - ese[:, :, 1]) <= cfg.max_age
        hit_w = match & live & gen_ok & fresh
        stale_w = match & live & fresh & ~gen_ok
        W = cfg.ways
        widx = np.arange(W, dtype=np.int32)[None, :]
        first = np.min(np.where(hit_w, widx, W), axis=1)
        hit = first < W
        sel = np.sum(np.where(widx == first[:, None], cand, 0), axis=1)
        stale = np.any(stale_w, axis=1) & ~hit
        res16 = np.where(
            hit,
            np.sum(np.where(widx == first[:, None], evg[:, :, 0], 0),
                   axis=1),
            0,
        ).astype(np.uint16)
        hs = sel[hit]
        ln = f["pkt_len"]
        upd = np.stack(
            [np.ones_like(ln), (ln >> 8) & 0xFFFFFF, ln & 0xFF], axis=1
        )
        np.add.at(self.cnt, hs, upd[hit])
        is_tcp = f["proto"] == IPPROTO_TCP
        fin = is_tcp & ((tflags & TCP_FIN) != 0)
        rst = is_tcp & ((tflags & TCP_RST) != 0)
        big = np.int32(np.iinfo(np.int32).max)
        mx = np.stack(
            [np.where(hit & fin, FLOW_FIN, -1).astype(np.int32),
             np.full(len(hit), epoch_now, np.int32)],
            axis=1,
        )
        np.maximum.at(self.se, hs, mx[hit])
        mn = np.stack(
            [np.full(len(hit), FLOW_EMPTY, np.int32),
             np.full(len(hit), big, np.int32)],
            axis=1,
        )
        np.minimum.at(self.se, sel[hit & rst], mn[hit & rst])
        return res16, hit, int(hit.sum()), int(stale.sum())

    def insert(self, wire, tenant, tflags, verdict16, epoch_now: int,
               gens: Optional[np.ndarray] = None,
               lane_ok: Optional[np.ndarray] = None):
        """Mirror of jaxpath._flow_insert_core -> (inserts, evictions,
        promotes).  ``gens`` overrides the generation stamp source (the
        tier passes its probe-time snapshot); ``lane_ok`` mirrors the
        resident fused step's in-program miss mask (the host-compaction
        equivalent — same eligible lanes, same order)."""
        cfg = self.config
        f, tenant, tflags, page, keyw, is_ip, cand = self._lanes(
            wire, tenant, tflags
        )
        if gens is None:
            gens = self.gens
        is_tcp = f["proto"] == IPPROTO_TCP
        syn = is_tcp & ((tflags & TCP_SYN) != 0)
        ack = is_tcp & ((tflags & TCP_ACK) != 0)
        fin = is_tcp & ((tflags & TCP_FIN) != 0)
        rst = is_tcp & ((tflags & TCP_RST) != 0)
        elig = is_ip & (f["l4_ok"] != 0) & (page >= 0) & ~rst
        if lane_ok is not None:
            elig = elig & np.asarray(lane_ok, bool)
        ek = self.keys[cand]
        ese = self.se[cand]
        est = ese[:, :, 0]
        eep = ese[:, :, 1]
        match_w = np.all(ek == keyw[:, None, :], axis=2) & (est > 0)
        empty_w = est == 0
        W = cfg.ways
        widx = np.arange(W, dtype=np.int32)[None, :]
        m_first = np.min(np.where(match_w, widx, W), axis=1)
        e_first = np.min(np.where(empty_w, widx, W), axis=1)
        oldest = np.argmin(eep, axis=1).astype(np.int32)
        way = np.where(
            m_first < W, m_first, np.where(e_first < W, e_first, oldest)
        )
        slot = np.sum(np.where(widx == way[:, None], cand, 0), axis=1)
        matched = m_first < W
        old_state = np.sum(np.where(widx == way[:, None], est, 0), axis=1)
        C = cfg.capacity
        lane = np.arange(slot.shape[0], dtype=np.int32)
        winner = np.full(C + 1, -1, np.int32)
        np.maximum.at(winner, np.where(elig, slot, C), lane)
        win = elig & (winner[np.clip(slot, 0, C)] == lane)
        ln = f["pkt_len"]
        seeds = np.zeros((C, 3), np.int32)
        np.add.at(
            seeds, slot[elig],
            np.stack(
                [np.ones_like(ln), (ln >> 8) & 0xFFFFFF, ln & 0xFF],
                axis=1,
            )[elig],
        )
        state_val = np.where(
            fin, FLOW_FIN, np.where(is_tcp & syn & ~ack, FLOW_NEW, FLOW_EST)
        ).astype(np.int32)
        mygen = gens[np.clip(tenant, 0, cfg.max_tenants - 1)]
        ws = slot[win]
        self.keys[ws] = keyw[win]
        self.vg[ws, 0] = (
            np.asarray(verdict16, np.int64)[win] & 0xFFFF
        ).astype(np.int32)
        self.vg[ws, 1] = mygen[win]
        self.se[ws, 0] = state_val[win]
        self.se[ws, 1] = np.int32(epoch_now)
        self.cnt[ws] = seeds[ws]
        evict = win & ~matched & (old_state > 0)
        promote = win & matched & (old_state == FLOW_NEW) & (
            state_val == FLOW_EST
        )
        return int(win.sum()), int(evict.sum()), int(promote.sum())

    def age(self, cutoff: int) -> int:
        expire = (self.se[:, 0] > 0) & (self.se[:, 1] < cutoff)
        self.se[expire, 0] = FLOW_EMPTY
        return int(expire.sum())

    def occupancy(self) -> int:
        return int((self.se[:, 0] > 0).sum())


# --- the device tier ---------------------------------------------------------


class FlowTier:
    """Host-side owner of the device flow table: dispatch plumbing for
    the probe/insert kernels, the per-tenant generation + flow-page
    state, counters, and (opt-in) the shadow HostFlowModel the model
    checker compares against.

    Thread-safety: the device column tuple is double-buffered like every
    other table family — dispatches snapshot it under the lock and
    in-flight work finishes on the snapshot it captured; mutations
    install a new tuple under the lock.
    """

    def __init__(self, config: FlowConfig, device=None, shardings=None,
                 track_model: bool = False) -> None:
        import jax
        import jax.numpy as jnp

        from .kernels import jaxpath

        self.config = config
        self._device = device
        self._shardings = shardings or {}
        self._lock = threading.Lock()
        self.stats = FlowStats()
        #: optional sink for eviction events: called as
        #: on_evict(evictions, inserts, epoch) after an insert dispatch
        #: that displaced live flows (the daemon pushes a
        #: FlowEvictRecord on the obs ring)
        self.on_evict: Optional[Callable] = None
        C = config.capacity
        put = lambda name, a: jax.device_put(
            jnp.asarray(a), self._shardings.get(name, device)
        )
        # one packed slot-minor matrix on the device (jaxpath.FlowTable)
        self._flow = jaxpath.FlowTable(
            state=put("state", np.zeros((jaxpath.FLOW_ROWS, C), np.int32))
        )
        self._gens_host = np.zeros(config.max_tenants, np.int32)
        self._pages_host = np.full(config.max_tenants, -1, np.int32)
        if config.pages == 1 and config.max_tenants == 1:
            # the single-tenant tier: tenant 0 owns the one slab
            self._pages_host[0] = 0
        self._gens_dev = put("gens", self._gens_host)
        self._pages_dev = put("page_table", self._pages_host)
        self._epoch = 0
        self._max_age_dev = put("max_age", np.int32(config.max_age))
        # per-(B,) cached inert tenant/flags device columns so the
        # common no-tenant/no-flags dispatch re-uploads nothing
        self._zeros_cache: Dict[int, tuple] = {}
        # Resident-serving epoch chain (ISSUE-12): the fused resident
        # step increments the epoch ON DEVICE and returns the aliased
        # buffer, so steady-state dispatches upload nothing for it;
        # _epoch_dev_val mirrors the device value so an interleaved
        # classic probe (which bumps only the host counter) forces one
        # re-seed instead of serving a torn epoch.
        self._epoch_dev = None
        self._epoch_dev_val = -1
        #: ordered pending host-model mirrors of resident dispatches
        #: (track_model only): the fused step's probe+insert must replay
        #: into the model in DEVICE order, and the insert half needs the
        #: merged verdicts — only host-resident at materialize time
        self._mirror_q: list = []
        #: pipeline slot parity (ISSUE-16): resident dispatches
        #: alternate between the two in-flight admission slots; the
        #: counter only matters for observability and the slotepoch
        #: injected-defect surface — the donated chain itself is
        #: slot-agnostic (one device-ordered epoch sequence)
        self._resident_slot = 0
        self.model = HostFlowModel(config) if track_model else None

    # -- generation / paging -------------------------------------------------

    def bump_generation(self, tenant: int = 0) -> None:
        """Invalidate every resident flow verdict of ``tenant`` (O(1):
        entries go stale by generation compare, no table sweep).  Called
        at every table-mutation chokepoint — load_tables (patch, folded
        txn flush, full rebuild, overlay change) and the arena tenant
        lifecycle."""
        if _inject_flow_stale_bug():
            return  # TEST-ONLY: the dropped-invalidation defect
        import jax
        import jax.numpy as jnp

        with self._lock:
            if not 0 <= tenant < self.config.max_tenants:
                return
            self._gens_host[tenant] += 1
            self._gens_dev = jax.device_put(
                jnp.asarray(self._gens_host),
                self._shardings.get("gens", self._device),
            )
            if self.model is not None:
                self.model.gens[tenant] += 1
        self.stats.add(invalidations=1)

    def bump_all_generations(self) -> None:
        if _inject_flow_stale_bug():
            return
        import jax
        import jax.numpy as jnp

        with self._lock:
            self._gens_host += 1
            self._gens_dev = jax.device_put(
                jnp.asarray(self._gens_host),
                self._shardings.get("gens", self._device),
            )
            if self.model is not None:
                self.model.gens += 1
        self.stats.add(invalidations=1)

    def set_page(self, tenant: int, page: int) -> None:
        """Steer ``tenant``'s flow slab (the arena tier mirrors its page
        table here; -1 unmaps).  Always paired with a generation bump by
        the callers, so slab reuse can never serve a previous tenant's
        entries — and the key's tenant word makes cross-tenant serving
        impossible even without the bump."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            if not 0 <= tenant < self.config.max_tenants:
                return
            self._pages_host[tenant] = (
                int(page) % self.config.pages if page >= 0 else -1
            )
            self._pages_dev = jax.device_put(
                jnp.asarray(self._pages_host),
                self._shardings.get("page_table", self._device),
            )
            if self.model is not None:
                self.model.page_table[:] = self._pages_host

    # -- dispatch ------------------------------------------------------------

    def _put(self, a):
        import jax

        return jax.device_put(a, self._device)

    def _zeros(self, b: int):
        z = self._zeros_cache.get(b)
        if z is None:
            z = (
                self._put(np.zeros(b, np.int32)),
                self._put(np.zeros(b, np.int32)),
            )
            self._zeros_cache[b] = z
        return z

    def probe(self, wire_np: np.ndarray,
              tenant_np: Optional[np.ndarray] = None,
              tflags_np: Optional[np.ndarray] = None):
        """Dispatch the fused probe for one wire batch and install the
        updated per-flow columns.  Returns (fused device array, ctx):
        the fused buffer decodes with jaxpath.split_flow_probe_outputs;
        ``ctx`` carries the probe-time epoch and generation snapshot the
        matching insert must stamp entries with (a verdict computed
        against superseded tables is then stale on arrival)."""
        from .kernels import jaxpath

        b = wire_np.shape[0]
        zt, zf = self._zeros(b)
        wire = self._put(np.ascontiguousarray(wire_np, np.uint32))
        tenant = (
            zt if tenant_np is None
            else self._put(np.ascontiguousarray(tenant_np, np.int32))
        )
        tflags = (
            zf if tflags_np is None
            else self._put(np.ascontiguousarray(tflags_np, np.int32))
        )
        fn = jaxpath.jitted_flow_probe(self.config.entries,
                                       self.config.ways)
        with self._lock:
            self._epoch += 1
            epoch = self._epoch
            epoch_dev = self._put(np.int32(epoch))
            gens_dev = self._gens_dev
            pages_dev = self._pages_dev
            fused, updated = fn(
                self._flow, gens_dev, pages_dev, wire, tenant, tflags,
                epoch_dev, self._max_age_dev,
            )
            self._flow = updated
            if self.model is not None:
                self.model.probe(
                    wire_np, tenant_np, tflags_np, epoch
                )
            gens_host = self._gens_host.copy()
        return fused, {
            "epoch": epoch, "epoch_dev": epoch_dev, "gens_dev": gens_dev,
            "pages_dev": pages_dev, "gens_host": gens_host,
            "wire": wire, "tenant": tenant, "tflags": tflags,
        }

    def insert(self, ctx, miss_wire_np: np.ndarray, verdict16: np.ndarray,
               tenant_np: Optional[np.ndarray] = None,
               tflags_np: Optional[np.ndarray] = None) -> tuple:
        """Batch-insert miss verdicts (one scatter dispatch), stamped
        with the probe-time generation snapshot from ``ctx``.  Returns
        (inserts, evictions, promotes)."""
        from .kernels import jaxpath

        b = miss_wire_np.shape[0]
        zt, zf = self._zeros(b)
        wire = self._put(np.ascontiguousarray(miss_wire_np, np.uint32))
        tenant = (
            zt if tenant_np is None
            else self._put(np.ascontiguousarray(tenant_np, np.int32))
        )
        tflags = (
            zf if tflags_np is None
            else self._put(np.ascontiguousarray(tflags_np, np.int32))
        )
        vdev = self._put(np.ascontiguousarray(verdict16, np.uint32))
        fn = jaxpath.jitted_flow_insert(self.config.entries,
                                        self.config.ways)
        with self._lock:
            updated, counts = fn(
                self._flow, ctx["gens_dev"], ctx["pages_dev"], wire,
                tenant, tflags, vdev, ctx["epoch_dev"],
            )
            self._flow = updated
            if self.model is not None:
                self.model.insert(
                    miss_wire_np, tenant_np, tflags_np, verdict16,
                    ctx["epoch"], gens=ctx["gens_host"],
                )
        c = np.asarray(counts)
        inserts, evictions, promotes = int(c[0]), int(c[1]), int(c[2])
        self.stats.add(inserts=inserts, evictions=evictions,
                       promotes=promotes)
        if evictions and self.on_evict is not None:
            try:
                self.on_evict(evictions, inserts, ctx["epoch"])
            except Exception:
                pass
        return inserts, evictions, promotes

    # -- resident serving (donated-buffer fused step, ISSUE-12) --------------

    def resident_gens_snapshot(self):
        """(gens_dev, gens_host copy) captured under the lock — the
        resident plan takes this BEFORE reading the table snapshot, so a
        concurrent load_tables between the two capture points can only
        make the stamped generation OLDER than the tables that compute
        the verdicts (inserts then stale on arrival — safe; the reverse
        order would stamp old-table verdicts as live)."""
        with self._lock:
            return self._gens_dev, self._gens_host.copy()

    def resident_dispatch(self, fn, tables_args, wire_dev, b: int,
                          wire_np: Optional[np.ndarray] = None,
                          tenant_np: Optional[np.ndarray] = None,
                          tflags_np: Optional[np.ndarray] = None,
                          gens_snap=None, alloc_note=None,
                          telemetry: Optional["TelemetryTier"] = None,
                          mlscore: Optional["AnomalyTier"] = None,
                          payload_ops=None, payload_dev=None):
        """Run one fused resident step and chain the donated buffers:
        ``fn(flow, gens, pages, epoch, *tables_args, wire, tenant,
        tflags, max_age) -> (new flow, new epoch, fused)``.  The updated
        columns and epoch REPLACE the resident state under the lock (the
        inputs are consumed by donation), so consecutive dispatches form
        one device-ordered chain.  Returns (fused device buffer, epoch).

        ``alloc_note`` (the ResidentPool counter hook) is called once
        per fresh device allocation this dispatch performs beyond the
        wire staging — zero on the warmed steady state, which the bench
        gate asserts."""
        zt, zf = None, None
        if tenant_np is None or tflags_np is None:
            if b not in self._zeros_cache and alloc_note is not None:
                alloc_note("zeros")
            zt, zf = self._zeros(b)
        tenant = (
            zt if tenant_np is None
            else self._put(np.ascontiguousarray(tenant_np, np.int32))
        )
        tflags = (
            zf if tflags_np is None
            else self._put(np.ascontiguousarray(tflags_np, np.int32))
        )
        with self._lock:
            self._epoch += 1
            epoch = self._epoch
            slot = self._resident_slot
            self._resident_slot ^= 1
            if slot == 1 and _inject_slot_epoch_bug():
                # TEST-ONLY (slotepoch defect): the second pipeline
                # slot skips the donated epoch chain — it re-seeds TWO
                # behind the host counter, so the device stamps slot-1
                # inserts with a stale epoch while the host model
                # stamps the true one (flow-column divergence at the
                # next settled check)
                epoch_dev = self._put(np.int32(epoch - 2))
            elif (
                self._epoch_dev is not None
                and self._epoch_dev_val == epoch - 1
            ):
                epoch_dev = self._epoch_dev  # donated chain: no upload
            else:
                # first dispatch, or a classic probe bumped the host
                # counter since: re-seed the device scalar once
                epoch_dev = self._put(np.int32(epoch - 1))
                if alloc_note is not None:
                    alloc_note("epoch")
            gens_dev = self._gens_dev if gens_snap is None else gens_snap[0]
            pages_dev = self._pages_dev
            # telemetry / mlscore fused variants (ISSUE-13/14): the
            # donated sketch and score tensors chain through the SAME
            # dispatch — exchanged under each tier's lock in the ONE
            # nesting order (flow lock -> telemetry lock -> mlscore
            # lock) so their updates land in device-dispatch order.
            # Operand order matches jitted_resident_step: flow, gens,
            # pages, epoch, [sk], [sc, model, tparams], [payload model
            # ops], tables..., wire[, pay, plen].  The payload-tier
            # operands (ISSUE-19) are persistent values, not state — no
            # exchange closure; they ride every dispatch as-is.
            def run(sk_state=None, sc_ops=None):
                ops = [self._flow, gens_dev, pages_dev, epoch_dev]
                if sk_state is not None:
                    ops.append(sk_state)
                if sc_ops is not None:
                    ops.extend(sc_ops)
                if payload_ops is not None:
                    ops.extend(payload_ops)
                tail = [wire_dev]
                if payload_dev is not None:
                    tail.extend(payload_dev)
                return fn(*ops, *tables_args, *tail, tenant, tflags,
                          self._max_age_dev)

            if telemetry is not None and mlscore is not None:
                def launch_sk(sk):
                    held = {}

                    def launch_sc(sc, model, tparams):
                        nf, ne, sk2, sc2, fz = run(sk, (sc, model,
                                                        tparams))
                        held["sk2"] = sk2
                        held["rest"] = (nf, ne, fz)
                        return sc2, held["rest"]

                    mlscore.resident_exchange(
                        launch_sc, epoch, wire_np, tenant_np, tflags_np,
                    )
                    return held["sk2"], held["rest"]

                new_flow, new_epoch, fused = telemetry.resident_exchange(
                    launch_sk, epoch, wire_np, tenant_np, tflags_np,
                )
            elif telemetry is not None:
                def launch(sk):
                    nf, ne, sk2, fz = run(sk)
                    return sk2, (nf, ne, fz)
                new_flow, new_epoch, fused = telemetry.resident_exchange(
                    launch, epoch, wire_np, tenant_np, tflags_np,
                )
            elif mlscore is not None:
                def launch(sc, model, tparams):
                    nf, ne, sc2, fz = run(None, (sc, model, tparams))
                    return sc2, (nf, ne, fz)
                new_flow, new_epoch, fused = mlscore.resident_exchange(
                    launch, epoch, wire_np, tenant_np, tflags_np,
                )
            else:
                new_flow, new_epoch, fused = run()
            self._flow = new_flow
            self._epoch_dev = new_epoch
            self._epoch_dev_val = epoch
            if self.model is not None:
                gens_host = (
                    self._gens_host.copy() if gens_snap is None
                    else gens_snap[1]
                )
                self._mirror_q.append((
                    epoch, np.asarray(wire_np, np.uint32).copy(),
                    None if tenant_np is None else np.asarray(
                        tenant_np, np.int32).copy(),
                    None if tflags_np is None else np.asarray(
                        tflags_np, np.int32).copy(),
                    fused, gens_host,
                ))
        return fused, epoch

    def resident_dispatch_super(self, fn, tables_args, wire_dev, k: int,
                                b: int,
                                wire_np: Optional[np.ndarray] = None,
                                tenant_np: Optional[np.ndarray] = None,
                                tflags_np: Optional[np.ndarray] = None,
                                gens_snap=None, alloc_note=None,
                                telemetry: Optional["TelemetryTier"] = None,
                                mlscore: Optional["AnomalyTier"] = None,
                                payload_ops=None, payload_dev=None):
        """Run ONE superbatch device program over ``k`` stacked
        admissions (jaxpath.jitted_resident_superbatch) and chain the
        donated buffers exactly like ``resident_dispatch`` — the device
        epoch advances ``k`` times INSIDE the program (the scan carry),
        the host counter advances ``k`` here, and the model mirror
        queues one entry PER ADMISSION, each referencing its row of the
        stacked (k, L) fused readback — so out-of-order materialize
        still drains in device-epoch order.  ``wire_np`` / ``tenant_np``
        / ``tflags_np`` are (k, b[, w]) host stacks.  Returns
        (fused stack, last epoch)."""
        zt, zf = None, None
        if tenant_np is None or tflags_np is None:
            if (k, b) not in self._zeros_cache and alloc_note is not None:
                alloc_note("zeros")
            zt, zf = self._zeros((k, b))
        tenant = (
            zt if tenant_np is None
            else self._put(np.ascontiguousarray(tenant_np, np.int32))
        )
        tflags = (
            zf if tflags_np is None
            else self._put(np.ascontiguousarray(tflags_np, np.int32))
        )
        with self._lock:
            epoch0 = self._epoch
            self._epoch += k
            epoch = self._epoch
            # both pipeline slots advance through one superbatch: keep
            # the parity counter honest for the interleaved single path
            self._resident_slot = (self._resident_slot + k) & 1
            if (
                self._epoch_dev is not None
                and self._epoch_dev_val == epoch0
            ):
                epoch_dev = self._epoch_dev  # donated chain: no upload
            else:
                epoch_dev = self._put(np.int32(epoch0))
                if alloc_note is not None:
                    alloc_note("epoch")
            gens_dev = self._gens_dev if gens_snap is None else gens_snap[0]
            pages_dev = self._pages_dev

            def run(sk_state=None, sc_ops=None):
                ops = [self._flow, gens_dev, pages_dev, epoch_dev]
                if sk_state is not None:
                    ops.append(sk_state)
                if sc_ops is not None:
                    ops.extend(sc_ops)
                if payload_ops is not None:
                    ops.extend(payload_ops)
                tail = [wire_dev]
                if payload_dev is not None:
                    tail.extend(payload_dev)
                return fn(*ops, *tables_args, *tail, tenant, tflags,
                          self._max_age_dev)

            if telemetry is not None and mlscore is not None:
                def launch_sk(sk):
                    held = {}

                    def launch_sc(sc, model, tparams):
                        nf, ne, sk2, sc2, fz = run(sk, (sc, model,
                                                        tparams))
                        held["sk2"] = sk2
                        held["rest"] = (nf, ne, fz)
                        return sc2, held["rest"]

                    mlscore.resident_exchange_super(
                        launch_sc, epoch0, k, wire_np, tenant_np,
                        tflags_np,
                    )
                    return held["sk2"], held["rest"]

                new_flow, new_epoch, fused = telemetry.resident_exchange_super(
                    launch_sk, epoch0, k, wire_np, tenant_np, tflags_np,
                )
            elif telemetry is not None:
                def launch(sk):
                    nf, ne, sk2, fz = run(sk)
                    return sk2, (nf, ne, fz)
                new_flow, new_epoch, fused = telemetry.resident_exchange_super(
                    launch, epoch0, k, wire_np, tenant_np, tflags_np,
                )
            elif mlscore is not None:
                def launch(sc, model, tparams):
                    nf, ne, sc2, fz = run(None, (sc, model, tparams))
                    return sc2, (nf, ne, fz)
                new_flow, new_epoch, fused = mlscore.resident_exchange_super(
                    launch, epoch0, k, wire_np, tenant_np, tflags_np,
                )
            else:
                new_flow, new_epoch, fused = run()
            self._flow = new_flow
            self._epoch_dev = new_epoch
            self._epoch_dev_val = epoch
            if self.model is not None:
                gens_host = (
                    self._gens_host.copy() if gens_snap is None
                    else gens_snap[1]
                )
                wire_stack = np.asarray(wire_np, np.uint32)
                for j in range(k):
                    self._mirror_q.append((
                        epoch0 + 1 + j, wire_stack[j].copy(),
                        None if tenant_np is None else np.asarray(
                            tenant_np[j], np.int32).copy(),
                        None if tflags_np is None else np.asarray(
                            tflags_np[j], np.int32).copy(),
                        (fused, j), gens_host,
                    ))
        return fused, epoch

    def resident_seed_epoch(self) -> None:
        """Re-sync the device epoch chain to the host counter (one tiny
        upload).  Called at warm-mark time: the classic probe/insert
        warm bumps only the host epoch, so without this the FIRST
        serving dispatch would pay the re-seed — a pool allocation the
        zero-alloc steady-state gate would (rightly) flag."""
        with self._lock:
            if self._epoch_dev_val != self._epoch:
                self._epoch_dev = self._put(np.int32(self._epoch))
                self._epoch_dev_val = self._epoch

    def resident_note_materialized(self, epoch: int) -> None:
        """Replay pending host-model mirrors up to ``epoch`` in device
        order (track_model only).  The fused step's insert half needs
        the merged verdicts, which are host-resident only once the
        dispatch materializes — draining in epoch order keeps the model
        correct even when results are read out of dispatch order."""
        if self.model is None:
            return
        from .kernels import jaxpath

        with self._lock:
            while self._mirror_q and self._mirror_q[0][0] <= epoch:
                ep, wire_np, tenant_np, tflags_np, fused, gens_host = (
                    self._mirror_q.pop(0)
                )
                # a superbatch entry references one row of the stacked
                # (k, L) readback; resident_fused_host blocks until the
                # dispatch lands either way
                res16, hit, _h, _s, _c = jaxpath.split_resident_outputs(
                    jaxpath.resident_fused_host(fused), wire_np.shape[0]
                )
                self.model.probe(wire_np, tenant_np, tflags_np, ep)
                self.model.insert(
                    wire_np, tenant_np, tflags_np, res16, ep,
                    gens=gens_host, lane_ok=~hit,
                )

    def age(self, horizon: Optional[int] = None) -> int:
        """Free every entry last seen more than ``horizon`` epochs ago
        (default: the configured max_age) — the explicit reclamation
        sweep (stale entries never serve regardless; this returns their
        slots to the free pool ahead of LRU pressure)."""
        from .kernels import jaxpath

        h = int(horizon if horizon is not None else self.config.max_age)
        with self._lock:
            cutoff = self._epoch - h
            cdev = self._put(np.int32(cutoff))
            self._flow, aged = jaxpath.jitted_flow_age()(self._flow, cdev)
            if self.model is not None:
                self.model.age(cutoff)
        aged = int(np.asarray(aged))
        self.stats.add(aged=aged, age_sweeps=1)
        return aged

    def reset(self) -> None:
        """Drop every resident flow (a fresh zero state) — the bench's
        per-measured-pass cold start; generations and pages persist."""
        import jax
        import jax.numpy as jnp

        with self._lock:
            self._flow = self._flow._replace(state=jax.device_put(
                jnp.zeros_like(self._flow.state),
                self._shardings.get("state", self._device),
            ))
            if self.model is not None:
                m = HostFlowModel(self.config)
                m.gens = self.model.gens
                m.page_table = self.model.page_table
                self.model = m

    def occupancy(self) -> int:
        from .kernels import jaxpath

        # dispatch INSIDE the lock (like age): under the resident loop
        # the columns are DONATED per admission — a snapshot taken off
        # the lock could be deleted by a concurrent dispatch before the
        # occupancy program reads it ("Array has been deleted")
        with self._lock:
            return int(np.asarray(
                jaxpath.jitted_flow_occupancy()(self._flow)
            ))

    @property
    def epoch(self) -> int:
        with self._lock:
            return self._epoch

    def flow_columns(self) -> Dict[str, np.ndarray]:
        """Host copies of the device state's fields in the (C, k)
        slot-major form HostFlowModel.columns() keeps (the model-checker
        compare side).  Materialized INSIDE the lock: the resident loop
        donates the state per admission, so an off-lock snapshot could
        be deleted by a concurrent dispatch mid-read."""
        from .kernels import jaxpath

        with self._lock:
            state = np.asarray(self._flow.state)
        cols = {
            k: np.ascontiguousarray(state[lo:hi].T)
            for k, (lo, hi) in jaxpath.FLOW_FIELDS.items()
        }
        cols["keys"] = cols["keys"].view(np.uint32)
        return cols

    def counter_values(self) -> Dict[str, int]:
        """flow_* counters + occupancy gauge for /metrics."""
        out = {f"flow_{k}_total": v for k, v in self.stats.values().items()}
        out["flow_occupancy"] = self.occupancy()
        out["flow_capacity"] = self.config.capacity
        return out

    def warm(self, ladder) -> int:
        """Pre-compile the probe/insert executables for every wire
        shape in ``ladder`` (4- and 7-word widths) so the warm flow
        lifecycle performs zero jit compiles on the serving path.
        Inert KIND_OTHER rows: never eligible, so the resident table is
        untouched.  The ladder is completed downward with every pow2
        below its maximum: the MISS fall-through compacts to pow2
        buckets (flow_miss_bucket), so a high-hit-rate chunk emits
        insert dispatches far smaller than any admission size."""
        ladder = sorted(set(int(b) for b in ladder))
        if ladder:
            b = 8
            extra = []
            while b < ladder[-1]:
                extra.append(b)
                b <<= 1
            ladder = sorted(set(ladder) | set(extra))
        n = 0
        for b in ladder:
            for width in (4, 7):
                wire = np.zeros((int(b), width), np.uint32)
                wire[:, 0] = 3  # KIND_OTHER: ineligible everywhere
                fused, ctx = self.probe(wire)
                np.asarray(fused)
                self.insert(ctx, wire, np.zeros(int(b), np.uint16))
                n += 2
        return n


def flow_miss_bucket(m: int) -> int:
    """Pow2 padding bucket for the compacted miss batch, so the
    fall-through stateless dispatch re-specializes only per bucket."""
    return _pow2(m)
