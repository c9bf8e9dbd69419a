"""MXU anomaly-scoring kernels: quantized per-flow ML inference (ISSUE-14).

The first genuinely MXU-shaped workload: per-flow feature vectors scored
by a small oblivious decision forest lowered to tensor form — every tree
level is ONE shared (feature, threshold) comparison, the D comparison
bits index a leaf, the (B, T*L) leaf one-hots hit the leaf-value vector
as ONE int8 x int8 -> int32 matmul (the MXU's native quantized form) —
plus an optional int8 MLP head with fixed-point requantization.  The
whole decision, not just the lookup, rides the accelerator (the hXDP
move, applied to anomaly detection): scoring composes into the resident
fused step (jaxpath.jitted_resident_step(score=spec)) or runs as one
follow-on launch per admission on the multi-dispatch wire path, exactly
like the telemetry sketches (ISSUE-13).

State (ScoreState, one donated pytree like SketchState):

- ``skeys`` (S, 6) uint32 / ``scols`` (S, 8) int32 — the per-source
  feature table: a ways-way set-associative exact store (the flow-insert
  shape) keyed on (tenant, src ip, kind), columns [pkts, syns, denies,
  newports, lastport, lastepoch, anomhits, rsvd].  Rates, flag mixes and
  the port-churn portscan signal accumulate here; LRU by lastepoch.
- ``cms``  (D, W) int32 — count-min rows over the same source key: the
  eviction-robust heavy-hitter count feature (overcount-only, saturated
  at ``sat`` like the telemetry sketch).
- ``tstat`` (T, 4) int32 — per-tenant window counters [scored lanes,
  anomalous lanes, enforced denies, max score (floored at 0)].
- ``epoch`` (1,) int32 — the admission counter, incremented ON DEVICE
  and chained through donation (the flow-epoch discipline): the
  inter-arrival proxy is epoch_now - row lastepoch.

Quantization scheme (integer/fixed-point END TO END, so a bit-exact
numpy oracle exists):

- features are int32, saturated at ``sat``; fraction features are Q8
  fixed point ((x * 256) // max(pkts, 1));
- the forest compares int32 features against int32 thresholds; leaf
  values are int8 and accumulate in int32 through the one-hot matmul;
- the MLP head right-shifts features by ``qshift[0]`` and clamps to
  [0, 127] (int8 activations), accumulates int32, then requantizes the
  hidden layer by ``qshift[1]`` with a [0, 127] clamp — the clamp the
  ``mlquant`` injected defect drops (device-side only: activations wrap
  through int8 while the host model keeps clamping).

``HostScoreModel`` mirrors every scatter and every matmul bit-for-bit in
numpy — the statecheck ``mlscore`` configs compare device tensors (and
scores) against it at every settled check.

Enforcement (the AnomalyTier policy layer, infw.mlscore): per-tenant
``tparams`` rows [threshold, enforce flag] decide; in enforce mode a
lane over threshold is rewritten to Deny (ruleId 0) UNLESS its
(proto, dst_port) is a failsafe cell (infw.failsaferules — the same
port list the analysis/rules.py coverage proof checks), and already-deny
lanes keep their rule's verdict.  Shadow mode never touches verdicts.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple, Optional, Tuple

import numpy as np

from .. import failsaferules
from ..constants import DENY, IPPROTO_TCP, IPPROTO_UDP, KIND_IPV4, KIND_IPV6

#: TEST-ONLY defect injection: when truthy (module flag or the
#: INFW_INJECT_MLQUANT_BUG env var), the DEVICE kernels drop the MLP
#: head's requantization clamp — hidden activations wrap through int8
#: instead of saturating at 127 — while the host model keeps clamping.
#: The statecheck acceptance (tools/infw_lint.py state --inject-defect
#: mlquant) must catch the divergence and ddmin-shrink it.  Never set
#: in production.
_INJECT_MLQUANT_BUG = False


def _inject_mlquant_bug() -> bool:
    if _INJECT_MLQUANT_BUG:
        return True
    env = os.environ.get("INFW_INJECT_MLQUANT_BUG", "")
    return env not in ("", "0", "false", "no")


#: source key words: [tenant, ip0, ip1, ip2, ip3, kind] — per-SOURCE
#: aggregation (no verdict in the key: one row accumulates a source's
#: whole mix, which is what the rate/fraction features need)
SCORE_KEY_WORDS = 6

#: the fixed feature schema (index -> meaning); every feature is int32
#: and NONE reads attack ground-truth labels (they exist for the
#: measuring side only) — verdicts here are RULE verdicts, computed
#: before any enforcement:
#:   0 src_pkts       source-row packet count (post-update, sat-clamped)
#:   1 src_syns       source-row pure-SYN count
#:   2 src_denies     source-row rule-deny count
#:   3 src_newports   source-row port-change count (portscan churn)
#:   4 cms_est        count-min estimate of the source's packets
#:   5 epoch_delta    admissions since the source was last seen
#:                    (65535 = first sight)
#:   6 lane_syn       this lane is a pure SYN (0/1)
#:   7 lane_flags     this lane's TCP flags byte
#:   8 pkt_len        this lane's packet length
#:   9 kind           address family (1 v4 / 2 v6)
#:  10 dst_port       this lane's destination port
#:  11 proto          this lane's L4 protocol
#:  12 syn_frac_q8    (src_syns * 256) // max(src_pkts, 1)
#:  13 newport_frac_q8 (src_newports * 256) // max(src_pkts, 1)
#:  14 deny_frac_q8   (src_denies * 256) // max(src_pkts, 1)
#:  15 lane_deny      this lane's rule verdict is Deny (0/1)
SCORE_FEATURES = 16

#: epoch-delta sentinel for a source with no resident row (first sight)
FIRST_SIGHT_DELTA = 65535

#: res16 written by an enforced rewrite: action Deny, ruleId 0 — rule
#: verdicts always carry a nonzero order, so enforced denies are
#: distinguishable in stats/event streams
ANOMALY_DENY_RESULT = DENY

#: default per-tenant anomaly threshold (one >=100 leaf fires alone)
DEFAULT_THRESHOLD = 100


def _pow2(n: int, floor: int = 8) -> int:
    return max(floor, 1 << (max(int(n), 1) - 1).bit_length())


class ScoreSpec(NamedTuple):
    """Geometry of one scoring tier (hashable — the jit cache key).
    Model VALUES (thresholds, leaves, weights) are runtime operands, so
    a hot swap never recompiles; only geometry lives here."""

    trees: int = 4            # oblivious trees
    depth: int = 3            # levels per tree (leaves = 2**depth)
    slots: int = 512          # per-source feature rows (power of two)
    ways: int = 4             # set-associative probes per key
    cms_depth: int = 2        # count-min rows
    cms_width: int = 1024     # buckets per row (power of two)
    sat: int = 65535          # feature/counter saturation clamp
    hidden: int = 0           # int8 MLP head width (0 = forest only)
    max_tenants: int = 1

    @property
    def leaves(self) -> int:
        return 1 << self.depth

    @staticmethod
    def make(trees: int = 4, depth: int = 3, slots: int = 512,
             ways: int = 4, cms_depth: int = 2, cms_width: int = 1024,
             sat: int = 65535, hidden: int = 0,
             max_tenants: int = 1) -> "ScoreSpec":
        if not 1 <= trees <= 16:
            raise ValueError(f"score trees must be in [1, 16], got {trees}")
        if not 1 <= depth <= 6:
            raise ValueError(f"score depth must be in [1, 6], got {depth}")
        if not 1 <= ways <= 8:
            raise ValueError(f"score ways must be in [1, 8], got {ways}")
        if not 1 <= cms_depth <= 8:
            raise ValueError(
                f"score cms_depth must be in [1, 8], got {cms_depth}"
            )
        if sat < 1:
            raise ValueError(f"score sat must be >= 1, got {sat}")
        if not 0 <= hidden <= 64:
            raise ValueError(f"score hidden must be in [0, 64], got {hidden}")
        if max_tenants < 1:
            raise ValueError("score max_tenants must be >= 1")
        return ScoreSpec(
            trees=int(trees), depth=int(depth), slots=_pow2(slots),
            ways=int(ways), cms_depth=int(cms_depth),
            cms_width=_pow2(cms_width), sat=int(sat), hidden=int(hidden),
            max_tenants=int(max_tenants),
        )


class ScoreState(NamedTuple):
    """Device scoring tensors (host numpy in the model's mirror)."""

    skeys: object  # (S, 6) uint32
    scols: object  # (S, 8) int32
    cms: object    # (D, W) int32
    tstat: object  # (T, 4) int32 [scored, anom, enforced, maxscore]
    epoch: object  # (1,) int32 admission counter


class ScoreModelDev(NamedTuple):
    """Model VALUE operands (device arrays; shapes fixed by ScoreSpec,
    so swapping values never recompiles — the hot-swap contract)."""

    fidx: object    # (T, D) int32 feature index per tree level
    fthr: object    # (T, D) int32 threshold per tree level
    leaf: object    # (T * L,) int8 leaf values
    w1: object      # (F, H) int8
    b1: object      # (H,) int32
    w2: object      # (H,) int8
    b2: object      # (1,) int32
    qshift: object  # (2,) int32 [feature shift, hidden requant shift]


class ScoreModel(NamedTuple):
    """Host-side model artifact: a ScoreSpec plus the numpy value
    arrays (the npz + manifest payload, infw.mlscore.save_model)."""

    spec: ScoreSpec
    fidx: np.ndarray
    fthr: np.ndarray
    leaf: np.ndarray
    w1: np.ndarray
    b1: np.ndarray
    w2: np.ndarray
    b2: np.ndarray
    qshift: np.ndarray
    version: str = "default"

    def arrays(self) -> dict:
        return {
            "fidx": self.fidx, "fthr": self.fthr, "leaf": self.leaf,
            "w1": self.w1, "b1": self.b1, "w2": self.w2, "b2": self.b2,
            "qshift": self.qshift,
        }


def validate_model(model: ScoreModel) -> None:
    """Shape/dtype/range contract of a model artifact against its spec
    (load_model and set_score_model both run this — a malformed swap
    must fail at the control plane, never inside a serving dispatch)."""
    s = model.spec
    want = {
        "fidx": ((s.trees, s.depth), np.int32),
        "fthr": ((s.trees, s.depth), np.int32),
        "leaf": ((s.trees * s.leaves,), np.int8),
        "w1": ((SCORE_FEATURES, s.hidden), np.int8),
        "b1": ((s.hidden,), np.int32),
        "w2": ((s.hidden,), np.int8),
        "b2": ((1,), np.int32),
        "qshift": ((2,), np.int32),
    }
    for name, (shape, dtype) in want.items():
        a = np.asarray(getattr(model, name))
        if a.shape != shape or a.dtype != dtype:
            raise ValueError(
                f"score model {name!r}: want shape {shape} dtype "
                f"{np.dtype(dtype).name}, got {a.shape} {a.dtype.name}"
            )
    if (model.fidx < 0).any() or (model.fidx >= SCORE_FEATURES).any():
        raise ValueError(
            f"score model fidx out of range [0, {SCORE_FEATURES})"
        )
    if (model.qshift < 0).any() or (model.qshift > 31).any():
        raise ValueError("score model qshift out of range [0, 31]")


def zero_state_host(spec: ScoreSpec) -> ScoreState:
    return ScoreState(
        skeys=np.zeros((spec.slots, SCORE_KEY_WORDS), np.uint32),
        scols=np.zeros((spec.slots, 8), np.int32),
        cms=np.zeros((spec.cms_depth, spec.cms_width), np.int32),
        tstat=np.zeros((spec.max_tenants, 4), np.int32),
        epoch=np.zeros(1, np.int32),
    )


def zero_tparams(spec: ScoreSpec,
                 threshold: int = DEFAULT_THRESHOLD,
                 enforce: bool = False) -> np.ndarray:
    """(T, 2) int32 per-tenant policy rows [threshold, enforce flag]."""
    t = np.zeros((spec.max_tenants, 2), np.int32)
    t[:, 0] = int(threshold)
    t[:, 1] = 1 if enforce else 0
    return t


# --- failsafe precedence -----------------------------------------------------
#
# The port list is the SAME one the analysis/rules.py coverage proof
# checks (failsaferules) — one source of truth, so "enforce never
# overrides a failsafe Allow" and "no reachable rule Deny covers a
# failsafe port" protect identical cells.

_FS_TCP = np.asarray(
    sorted({fs.port for fs in failsaferules.get_tcp()}), np.int32
)
_FS_UDP = np.asarray(
    sorted({fs.port for fs in failsaferules.get_udp()}), np.int32
)


def failsafe_lane_mask_np(proto: np.ndarray,
                          dst_port: np.ndarray) -> np.ndarray:
    """(B,) bool: lanes whose (proto, dst_port) is a failsafe cell —
    enforce mode may NEVER rewrite these to Deny."""
    proto = np.asarray(proto, np.int32)
    dst_port = np.asarray(dst_port, np.int32)
    tcp = (proto == IPPROTO_TCP) & np.isin(dst_port, _FS_TCP)
    udp = (proto == IPPROTO_UDP) & np.isin(dst_port, _FS_UDP)
    return tcp | udp


def _failsafe_lane_mask_jax(proto, dst_port):
    import jax.numpy as jnp

    tcp = (proto == IPPROTO_TCP) & jnp.any(
        dst_port[:, None] == jnp.asarray(_FS_TCP)[None, :], axis=1
    )
    udp = (proto == IPPROTO_UDP) & jnp.any(
        dst_port[:, None] == jnp.asarray(_FS_UDP)[None, :], axis=1
    )
    return tcp | udp


# --- model builders ----------------------------------------------------------


def default_model(spec: Optional[ScoreSpec] = None) -> ScoreModel:
    """The shipped detection forest (forest-only, no MLP head): one
    tree per attack family over the fixed feature schema, leaf values
    sized so any single firing tree crosses DEFAULT_THRESHOLD.

    - tree 0 (SYN flood): syn_frac_q8 >= 192 AND src_pkts >= 24 AND the
      lane itself is a pure SYN -> 120;
    - tree 1 (port scan): newport_frac_q8 >= 128 AND src_pkts >= 24 ->
      120 (bit 2, cms_est >= 16, rides along informationally);
    - tree 2 (rate/deny storm): cms_est >= 4096 alone scores 30
      (sub-threshold), with deny_frac_q8 >= 192 -> 120;
    - remaining trees are inert (unsatisfiable thresholds, zero leaves).

    Extra trees beyond 4 / extra depth beyond 3 pad inert, so the
    default detector is available at any geometry."""
    spec = spec or ScoreSpec.make()
    T, D, L = spec.trees, spec.depth, spec.leaves
    NEVER = np.int32(2**31 - 1)
    fidx = np.zeros((T, D), np.int32)
    fthr = np.full((T, D), NEVER, np.int32)
    leaf = np.zeros((T, L), np.int8)

    def tree(t, levels, hits):
        # levels: [(feature, threshold)] for the first len(levels)
        # comparison bits; hits: {leaf bitmask (over those bits): value}
        for d, (f, th) in enumerate(levels):
            fidx[t, d] = f
            fthr[t, d] = th
        nbits = len(levels)
        for bits, val in hits.items():
            # unspecified (inert) levels compare against NEVER -> bit 0,
            # so only the low nbits vary; set every padded leaf whose
            # low bits match
            for hi in range(1 << (D - nbits)):
                leaf[t, (hi << nbits) | bits] = val

    if T >= 1 and D >= 3:
        tree(0, [(12, 192), (0, 24), (6, 1)], {0b111: 120})
        if T >= 2:
            tree(1, [(13, 128), (0, 24), (4, 16)], {0b011: 120, 0b111: 120})
        if T >= 3:
            tree(2, [(4, 4096), (14, 192)], {0b01: 30, 0b11: 120})
    H = spec.hidden
    return ScoreModel(
        spec=spec, fidx=fidx, fthr=fthr, leaf=leaf.reshape(-1),
        w1=np.zeros((SCORE_FEATURES, H), np.int8),
        b1=np.zeros(H, np.int32), w2=np.zeros(H, np.int8),
        b2=np.zeros(1, np.int32), qshift=np.zeros(2, np.int32),
        version="default",
    )


def clamp_stress_model(spec: ScoreSpec) -> ScoreModel:
    """A head-ful model whose hidden activations exceed the int8 clamp
    on ordinary traffic — the statecheck ``mlscore`` configs run THIS
    model so the mlquant injected defect (dropped requantization clamp)
    diverges within the first settled check.  Input quantization clips
    features to [0, 127] BEFORE the weights, so the stress comes from
    the weight: 3 * min(pkt_len, 127) reaches 381 for any packet over
    127 bytes — clamp present: 127; clamp dropped: int8 wraparound.
    With the clamp PRESENT the head is saturation-stable, so the model
    stays bit-identical to the device."""
    if spec.hidden < 1:
        raise ValueError("clamp_stress_model needs spec.hidden >= 1")
    m = default_model(spec)
    w1 = np.zeros((SCORE_FEATURES, spec.hidden), np.int8)
    w1[8, 0] = 3   # pkt_len drives hidden unit 0 far past the clamp
    w2 = np.zeros(spec.hidden, np.int8)
    w2[0] = 1
    return m._replace(w1=w1, w2=w2, version="clamp-stress")


def model_device(model: ScoreModel, device=None) -> ScoreModelDev:
    """Upload the value arrays (one small H2D per swap; shapes are
    spec-fixed so the serving executables never recompile)."""
    import jax

    validate_model(model)
    put = lambda a: jax.device_put(np.ascontiguousarray(a), device)
    return ScoreModelDev(
        fidx=put(model.fidx), fthr=put(model.fthr), leaf=put(model.leaf),
        w1=put(model.w1), b1=put(model.b1), w2=put(model.w2),
        b2=put(model.b2), qshift=put(model.qshift),
    )


# --- shared key/hash forms (numpy and jax compute IDENTICAL values) ----------


def _key_words_np(f, tenant: np.ndarray) -> np.ndarray:
    return np.stack([
        tenant.astype(np.uint32),
        f["ip_words"][:, 0].astype(np.uint32),
        f["ip_words"][:, 1].astype(np.uint32),
        f["ip_words"][:, 2].astype(np.uint32),
        f["ip_words"][:, 3].astype(np.uint32),
        f["kind"].astype(np.uint32) & np.uint32(3),
    ], axis=1)


def _hash_np(keys: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    h = np.full(keys.shape[0], 0x811C9DC5, np.uint32)
    for w in range(SCORE_KEY_WORDS):
        h = (h ^ keys[:, w].astype(np.uint32)) * np.uint32(0x01000193)
    return h, (h >> np.uint32(16)) | np.uint32(1)


def _key_words_jax(batch, tenant):
    import jax.numpy as jnp

    return jnp.stack([
        tenant.astype(jnp.uint32),
        batch.ip_words[:, 0].astype(jnp.uint32),
        batch.ip_words[:, 1].astype(jnp.uint32),
        batch.ip_words[:, 2].astype(jnp.uint32),
        batch.ip_words[:, 3].astype(jnp.uint32),
        batch.kind.astype(jnp.uint32) & 3,
    ], axis=1)


def _hash_jax(keys):
    import jax.numpy as jnp

    h = jnp.full(keys.shape[:1], 0x811C9DC5, jnp.uint32)
    for w in range(SCORE_KEY_WORDS):
        h = (h ^ keys[:, w].astype(jnp.uint32)) * jnp.uint32(0x01000193)
    return h, (h >> 16) | jnp.uint32(1)


# --- the host oracle ---------------------------------------------------------


class HostScoreModel:
    """Bit-exact numpy mirror of the device scoring kernel: same
    key/hash forms, same scatter order (cms add+clamp -> source-table
    probe/update -> feature gather -> forest matmul -> MLP head ->
    policy), same deterministic dedup rules.  The statecheck ``mlscore``
    configs compare every device tensor against this after each settled
    op; tests and bench_mlscore compare per-lane scores too."""

    def __init__(self, spec: ScoreSpec, model: Optional[ScoreModel] = None,
                 tparams: Optional[np.ndarray] = None) -> None:
        self.spec = spec
        self.model = model or default_model(spec)
        validate_model(self.model)
        if self.model.spec != spec:
            raise ValueError("score model geometry != tier spec")
        self.tparams = (
            zero_tparams(spec) if tparams is None
            else np.asarray(tparams, np.int32).copy()
        )
        s = zero_state_host(spec)
        self.skeys, self.scols, self.cms, self.tstat, self.epoch = (
            s.skeys, s.scols, s.cms, s.tstat, s.epoch
        )

    def columns(self) -> dict:
        return {"skeys": self.skeys, "scols": self.scols, "cms": self.cms,
                "tstat": self.tstat, "epoch": self.epoch}

    def tick(self) -> None:
        """Advance the admission counter without traffic — the mirror of
        one inert warm dispatch (AnomalyTier.warm)."""
        self.epoch = self.epoch + np.int32(1)

    def drain(self) -> None:
        """Window reset: tstat and the per-row anomaly-hit column clear;
        rates (pkts/cms) persist — they are continuous features."""
        self.tstat = np.zeros_like(self.tstat)
        self.scols[:, 6] = 0

    def swap(self, model: ScoreModel) -> None:
        validate_model(model)
        if model.spec != self.spec:
            raise ValueError("score model geometry != tier spec")
        self.model = model

    def reset_state(self) -> None:
        """Zero every state tensor (model/policy untouched) — the
        mirror of AnomalyTier.reset_state."""
        s = zero_state_host(self.spec)
        self.skeys, self.scols, self.cms, self.tstat, self.epoch = (
            s.skeys, s.scols, s.cms, s.tstat, s.epoch
        )

    def _features(self, f, tenant, tflags, res, elig):
        """The update+feature half, shared by update(): returns
        (features (B, F) int32, slot, elig) with the state mutated."""
        from .jaxpath import TCP_ACK, TCP_SYN

        spec = self.spec
        b = tenant.shape[0]
        S, Wy = spec.slots, spec.ways
        D, W = spec.cms_depth, spec.cms_width
        sat = np.int32(spec.sat)
        e1 = np.int32(self.epoch[0] + 1)
        keyw = _key_words_np(f, tenant)
        h1, h2 = _hash_np(keyw)
        # 1. count-min add + clamp, then the post-update estimate
        rows = np.arange(D, dtype=np.uint32)[None, :]
        col = ((h1[:, None] + rows * h2[:, None])
               & np.uint32(W - 1)).astype(np.int64)
        flat = rows.astype(np.int64) * W + col
        cms = self.cms.reshape(-1)
        np.add.at(cms, flat[elig].reshape(-1), 1)
        np.minimum(cms, sat, out=cms)
        self.cms = cms.reshape(D, W)
        est = np.minimum(
            np.min(self.cms.reshape(-1)[flat], axis=1).astype(np.int32), sat
        )
        # 2. source-table probe: match else first-empty else LRU victim
        wid = np.arange(Wy, dtype=np.uint32)[None, :]
        cand = ((h1[:, None] + wid * h2[:, None])
                & np.uint32(S - 1)).astype(np.int64)
        ek = self.skeys[cand]
        ecols = self.scols[cand]
        occupied = ecols[:, :, 0] > 0
        match_w = np.all(ek == keyw[:, None, :], axis=2) & occupied
        widx = np.arange(Wy, dtype=np.int32)[None, :]
        m_first = np.min(np.where(match_w, widx, Wy), axis=1)
        matched = m_first < Wy
        mslot = np.sum(np.where(widx == m_first[:, None], cand, 0), axis=1)
        e_first = np.min(np.where(~occupied, widx, Wy), axis=1)
        lru = np.argmin(ecols[:, :, 5], axis=1).astype(np.int32)
        vway = np.where(e_first < Wy, e_first, lru)
        vslot = np.sum(np.where(widx == vway[:, None], cand, 0), axis=1)
        slot = np.where(matched, mslot, vslot)
        # pre-update row views for the lane-local features
        pre_lastport = self.scols[np.clip(slot, 0, S - 1), 4]
        pre_lastepoch = self.scols[np.clip(slot, 0, S - 1), 5]
        # last eligible lane per slot wins the set-writes (flow insert)
        lane = np.arange(b, dtype=np.int64)
        idx_e = np.where(elig, slot, S)
        winner = np.full(S + 1, -1, np.int64)
        np.maximum.at(winner, idx_e, lane)
        win = elig & (winner[np.clip(slot, 0, S)] == lane)
        repl = win & ~matched
        # per-slot contributions over ALL eligible lanes assigned there
        # (collision pollution is deterministic and mirrored, the flow
        # insert discipline)
        is_tcp = f["proto"] == IPPROTO_TCP
        syn_lane = (
            is_tcp & ((tflags & TCP_SYN) != 0) & ((tflags & TCP_ACK) == 0)
        )
        deny_lane = (res & np.uint32(0xFF)).astype(np.int32) == DENY
        newport_lane = matched & (f["dst_port"] != pre_lastport)
        contrib = np.stack([
            np.ones(b, np.int32), syn_lane.astype(np.int32),
            deny_lane.astype(np.int32), newport_lane.astype(np.int32),
        ], axis=1)
        seeds = np.zeros((S + 1, 4), np.int32)
        np.add.at(seeds, idx_e, contrib)
        seeds = seeds[:S]
        # replaced rows restart from zero (keys swap, counters reset)
        repl_mask = np.zeros(S + 1, np.int32)
        np.maximum.at(repl_mask, np.where(repl, slot, S), 1)
        repl_mask = repl_mask[:S].astype(bool)
        base = np.where(repl_mask[:, None], 0, self.scols[:, 0:4])
        self.scols[:, 0:4] = np.minimum(base + seeds, sat)
        self.scols[repl_mask, 6] = 0
        self.scols[repl_mask, 7] = 0
        ws = slot[win]
        self.skeys[slot[repl]] = keyw[repl]
        self.scols[ws, 4] = f["dst_port"][win]
        touched = np.unique(idx_e[elig])
        self.scols[touched[touched < S], 5] = e1
        # 3. feature gather from the POST-update rows
        g = np.clip(slot, 0, S - 1)
        pkts = self.scols[g, 0]
        syns = self.scols[g, 1]
        denies = self.scols[g, 2]
        newports = self.scols[g, 3]
        delta = np.where(
            matched,
            np.clip(e1 - pre_lastepoch, 0, FIRST_SIGHT_DELTA),
            FIRST_SIGHT_DELTA,
        ).astype(np.int32)
        pk = np.maximum(pkts, 1)
        feats = np.stack([
            pkts, syns, denies, newports, est, delta,
            syn_lane.astype(np.int32),
            (tflags & 0xFF).astype(np.int32),
            f["pkt_len"].astype(np.int32),
            f["kind"].astype(np.int32),
            f["dst_port"].astype(np.int32),
            f["proto"].astype(np.int32),
            (syns * 256) // pk,
            (newports * 256) // pk,
            (denies * 256) // pk,
            deny_lane.astype(np.int32),
        ], axis=1).astype(np.int32)
        self.epoch = self.epoch + np.int32(1)
        return feats, slot

    def infer(self, feats: np.ndarray) -> np.ndarray:
        """Forest + MLP head over assembled features — the pure
        arithmetic half (no state), reused by tests that pin the
        quantized semantics on hand-built feature rows."""
        m = self.model
        spec = self.spec
        T, D, L = spec.trees, spec.depth, spec.leaves
        b = feats.shape[0]
        fsel = feats[:, np.clip(m.fidx, 0, SCORE_FEATURES - 1).reshape(-1)]
        bits = (
            fsel.reshape(b, T, D) >= m.fthr[None, :, :]
        ).astype(np.int32)
        leaf_idx = np.sum(bits << np.arange(D, dtype=np.int32)[None, None, :],
                          axis=2)
        oh = (
            leaf_idx[:, :, None] == np.arange(L, dtype=np.int32)[None, None, :]
        ).astype(np.int8).reshape(b, T * L)
        score = oh.astype(np.int32) @ m.leaf.astype(np.int32)
        if spec.hidden:
            in_shift = int(m.qshift[0])
            h_shift = int(m.qshift[1])
            xq = np.clip(feats >> in_shift, 0, 127).astype(np.int8)
            h = xq.astype(np.int32) @ m.w1.astype(np.int32) + m.b1
            # the requantization clamp — the host model ALWAYS clamps
            # (the device drops it under the mlquant injected defect)
            hq = np.clip(h >> h_shift, 0, 127).astype(np.int8)
            score = score + (
                hq.astype(np.int32) @ m.w2.astype(np.int32) + m.b2[0]
            )
        return score.astype(np.int32)

    def update(self, wire: np.ndarray, res: np.ndarray,
               tenant: Optional[np.ndarray] = None,
               tflags: Optional[np.ndarray] = None):
        """One admission: update the feature state, score every lane and
        apply the per-tenant policy.  Returns (scores int32, anom bool,
        res' uint32) — res' == res in shadow mode."""
        from ..flow import host_unpack_wire

        spec = self.spec
        wire = np.asarray(wire, np.uint32)
        b = wire.shape[0]
        f = host_unpack_wire(wire)
        tenant = (np.zeros(b, np.int32) if tenant is None
                  else np.asarray(tenant, np.int32))
        tflags = (np.zeros(b, np.int32) if tflags is None
                  else np.asarray(tflags, np.int32))
        res = np.asarray(res).astype(np.uint32)
        is_ip = (f["kind"] == KIND_IPV4) | (f["kind"] == KIND_IPV6)
        t_ok = (tenant >= 0) & (tenant < spec.max_tenants)
        elig = is_ip & t_ok
        feats, slot = self._features(f, tenant, tflags, res, elig)
        score = self.infer(feats)
        tclip = np.clip(tenant, 0, spec.max_tenants - 1)
        thr = self.tparams[tclip, 0]
        enf = self.tparams[tclip, 1] != 0
        anom = elig & (score >= thr)
        fs = failsafe_lane_mask_np(f["proto"], f["dst_port"])
        act = (res & np.uint32(0xFF)).astype(np.int32)
        rewrite = anom & enf & ~fs & (act != DENY)
        res_out = np.where(rewrite, np.uint32(ANOMALY_DENY_RESULT), res)
        # per-slot anomaly hits (window column, cleared at drain)
        np.add.at(
            self.scols[:, 6],
            np.clip(slot, 0, spec.slots - 1)[anom], 1,
        )
        np.minimum(self.scols[:, 6], np.int32(spec.sat),
                   out=self.scols[:, 6])
        # per-tenant window counters + max score (floored at 0)
        upd = np.stack([
            elig.astype(np.int32), anom.astype(np.int32),
            rewrite.astype(np.int32),
        ], axis=1)
        np.add.at(self.tstat[:, 0:3], tclip[elig], upd[elig])
        np.maximum.at(self.tstat[:, 3], tclip[elig], score[elig])
        return score, anom, res_out


# --- device kernels ----------------------------------------------------------


def _score_infer(feats, model: ScoreModelDev, *, spec: ScoreSpec):
    """Forest + MLP head on device — statement-for-statement the twin
    of HostScoreModel.infer.  The leaf one-hot matmul and the MLP layers
    run int8 x int8 with int32 accumulation (preferred_element_type) —
    the MXU's native quantized form."""
    import jax.numpy as jnp

    T, D, L = spec.trees, spec.depth, spec.leaves
    b = feats.shape[0]
    fsel = jnp.take(
        feats, jnp.clip(model.fidx, 0, SCORE_FEATURES - 1).reshape(-1),
        axis=1, mode="clip",
    ).reshape(b, T, D)
    bits = (fsel >= model.fthr[None, :, :]).astype(jnp.int32)
    leaf_idx = jnp.sum(
        bits << jnp.arange(D, dtype=jnp.int32)[None, None, :], axis=2
    )
    oh = (
        leaf_idx[:, :, None] == jnp.arange(L, dtype=jnp.int32)[None, None, :]
    ).astype(jnp.int8).reshape(b, T * L)
    score = jnp.matmul(
        oh, model.leaf[:, None], preferred_element_type=jnp.int32
    )[:, 0]
    if spec.hidden:
        in_shift = model.qshift[0]
        h_shift = model.qshift[1]
        xq = jnp.clip(feats >> in_shift, 0, 127).astype(jnp.int8)
        h = jnp.matmul(
            xq, model.w1, preferred_element_type=jnp.int32
        ) + model.b1
        h = h >> h_shift
        if not _inject_mlquant_bug():
            # fixed-point requantization: relu + saturate to the int8
            # activation range (dropped by the injected mlquant defect
            # — DEVICE side only, so the host model diverges)
            h = jnp.clip(h, 0, 127)
        hq = h.astype(jnp.int8)
        score = score + (
            jnp.matmul(
                hq, model.w2[:, None], preferred_element_type=jnp.int32
            )[:, 0]
            + model.b2[0]
        )
    return score.astype(jnp.int32)


def _score_update_core(sc: ScoreState, batch, tenant, tflags, res,
                       model: ScoreModelDev, tparams,
                       *, spec: ScoreSpec):
    """One admission of scoring — the in-program form the resident fused
    step composes (jaxpath._resident_step_core) and the standalone
    launch (jitted_score_update) wraps.  Every state write is a
    deterministic scatter; HostScoreModel mirrors this function
    statement for statement.  Returns (sc', score (B,) int32, anom (B,)
    bool, res' (B,) uint32) — res' is the policy-rewritten verdict
    vector (== res when every tenant is in shadow mode)."""
    import jax.numpy as jnp

    from .jaxpath import TCP_ACK, TCP_SYN

    S, Wy = spec.slots, spec.ways
    D, W = spec.cms_depth, spec.cms_width
    sat = jnp.int32(spec.sat)
    b = batch.kind.shape[0]
    e1 = (sc.epoch[0] + jnp.int32(1)).astype(jnp.int32)
    keyw = _key_words_jax(batch, tenant)
    is_ip = (batch.kind == KIND_IPV4) | (batch.kind == KIND_IPV6)
    t_ok = (tenant >= 0) & (tenant < spec.max_tenants)
    elig = is_ip & t_ok
    h1, h2 = _hash_jax(keyw)
    # 1. count-min add + clamp, then the post-update estimate
    rows = jnp.arange(D, dtype=jnp.uint32)[None, :]
    col = ((h1[:, None] + rows * h2[:, None])
           & jnp.uint32(W - 1)).astype(jnp.int32)
    flat = rows.astype(jnp.int32) * W + col
    idx = jnp.where(elig[:, None], flat, D * W)
    cms = sc.cms.reshape(-1).at[idx.reshape(-1)].add(1, mode="drop")
    cms = jnp.minimum(cms, sat)
    est = jnp.minimum(
        jnp.min(
            jnp.take(cms, flat.reshape(-1), mode="clip").reshape(b, D),
            axis=1,
        ).astype(jnp.int32),
        sat,
    )
    # 2. source-table probe: match else first-empty else LRU victim
    wid = jnp.arange(Wy, dtype=jnp.uint32)[None, :]
    cand = ((h1[:, None] + wid * h2[:, None])
            & jnp.uint32(S - 1)).astype(jnp.int32)
    ek = jnp.take(sc.skeys, cand, axis=0, mode="clip")
    ecols = jnp.take(sc.scols, cand, axis=0, mode="clip")
    occupied = ecols[:, :, 0] > 0
    match_w = jnp.all(ek == keyw[:, None, :], axis=2) & occupied
    widx = jnp.arange(Wy, dtype=jnp.int32)[None, :]
    m_first = jnp.min(jnp.where(match_w, widx, Wy), axis=1)
    matched = m_first < Wy
    mslot = jnp.sum(jnp.where(widx == m_first[:, None], cand, 0), axis=1)
    e_first = jnp.min(jnp.where(~occupied, widx, Wy), axis=1)
    lru = jnp.argmin(ecols[:, :, 5], axis=1).astype(jnp.int32)
    vway = jnp.where(e_first < Wy, e_first, lru)
    vslot = jnp.sum(jnp.where(widx == vway[:, None], cand, 0), axis=1)
    slot = jnp.where(matched, mslot, vslot)
    pre_lastport = jnp.take(sc.scols[:, 4], jnp.clip(slot, 0, S - 1),
                            mode="clip")
    pre_lastepoch = jnp.take(sc.scols[:, 5], jnp.clip(slot, 0, S - 1),
                             mode="clip")
    lane = jnp.arange(b, dtype=jnp.int32)
    idx_e = jnp.where(elig, slot, S)
    winner = jnp.full(S + 1, -1, jnp.int32).at[idx_e].max(lane, mode="drop")
    win = elig & (
        jnp.take(winner, jnp.clip(slot, 0, S), mode="clip") == lane
    )
    repl = win & ~matched
    is_tcp = batch.proto == IPPROTO_TCP
    syn_lane = (
        is_tcp & ((tflags & TCP_SYN) != 0) & ((tflags & TCP_ACK) == 0)
    )
    deny_lane = (res.astype(jnp.uint32) & 0xFF).astype(jnp.int32) == DENY
    newport_lane = matched & (batch.dst_port != pre_lastport)
    contrib = jnp.stack([
        jnp.ones(b, jnp.int32), syn_lane.astype(jnp.int32),
        deny_lane.astype(jnp.int32), newport_lane.astype(jnp.int32),
    ], axis=1)
    seeds = jnp.zeros((S + 1, 4), jnp.int32).at[idx_e].add(
        contrib, mode="drop"
    )[:S]
    repl_mask = (
        jnp.zeros(S + 1, jnp.int32)
        .at[jnp.where(repl, slot, S)].max(1, mode="drop")[:S]
    ).astype(bool)
    base = jnp.where(repl_mask[:, None], 0, sc.scols[:, 0:4])
    cols03 = jnp.minimum(base + seeds, sat)
    col6 = jnp.where(repl_mask, 0, sc.scols[:, 6])
    col7 = jnp.where(repl_mask, 0, sc.scols[:, 7])
    skeys = sc.skeys.at[jnp.where(repl, slot, S)].set(keyw, mode="drop")
    idx_w = jnp.where(win, slot, S)
    col4 = sc.scols[:, 4].at[idx_w].set(batch.dst_port.astype(jnp.int32),
                                        mode="drop")
    col5 = sc.scols[:, 5].at[idx_e].set(e1, mode="drop")
    # 3. feature gather from the POST-update rows
    g = jnp.clip(slot, 0, S - 1)
    pkts = jnp.take(cols03[:, 0], g, mode="clip")
    syns = jnp.take(cols03[:, 1], g, mode="clip")
    denies = jnp.take(cols03[:, 2], g, mode="clip")
    newports = jnp.take(cols03[:, 3], g, mode="clip")
    delta = jnp.where(
        matched,
        jnp.clip(e1 - pre_lastepoch, 0, FIRST_SIGHT_DELTA),
        FIRST_SIGHT_DELTA,
    ).astype(jnp.int32)
    pk = jnp.maximum(pkts, 1)
    feats = jnp.stack([
        pkts, syns, denies, newports, est, delta,
        syn_lane.astype(jnp.int32),
        (tflags & 0xFF).astype(jnp.int32),
        batch.pkt_len.astype(jnp.int32),
        batch.kind.astype(jnp.int32),
        batch.dst_port.astype(jnp.int32),
        batch.proto.astype(jnp.int32),
        (syns * 256) // pk,
        (newports * 256) // pk,
        (denies * 256) // pk,
        deny_lane.astype(jnp.int32),
    ], axis=1).astype(jnp.int32)
    score = _score_infer(feats, model, spec=spec)
    # 4. policy: per-tenant threshold + mode; enforce NEVER rewrites a
    # failsafe cell and never touches an existing rule Deny
    tclip = jnp.clip(tenant, 0, spec.max_tenants - 1)
    thr = jnp.take(tparams[:, 0], tclip, mode="clip")
    enf = jnp.take(tparams[:, 1], tclip, mode="clip") != 0
    anom = elig & (score >= thr)
    fs = _failsafe_lane_mask_jax(batch.proto, batch.dst_port)
    act = (res.astype(jnp.uint32) & 0xFF).astype(jnp.int32)
    rewrite = anom & enf & ~fs & (act != DENY)
    res_out = jnp.where(
        rewrite, jnp.uint32(ANOMALY_DENY_RESULT), res.astype(jnp.uint32)
    )
    col6 = jnp.minimum(
        col6.at[jnp.where(anom, slot, S)].add(1, mode="drop"), sat
    )
    scols = jnp.stack(
        [cols03[:, 0], cols03[:, 1], cols03[:, 2], cols03[:, 3],
         col4, col5, col6, col7], axis=1
    )
    # 5. per-tenant window counters + max score (floored at 0)
    upd = jnp.stack([
        elig.astype(jnp.int32), anom.astype(jnp.int32),
        rewrite.astype(jnp.int32),
    ], axis=1)
    trow = jnp.where(elig, tclip, spec.max_tenants)
    tstat03 = sc.tstat[:, 0:3].at[trow].add(upd, mode="drop")
    tstat3 = sc.tstat[:, 3].at[trow].max(score, mode="drop")
    tstat = jnp.concatenate([tstat03, tstat3[:, None]], axis=1)
    sc2 = ScoreState(
        skeys=skeys, scols=scols, cms=cms.reshape(D, W), tstat=tstat,
        epoch=(sc.epoch + jnp.int32(1)).astype(jnp.int32),
    )
    return sc2, score, anom, res_out


#: donated operand position of the standalone score update — the
#: persistent scoring tensors are rewritten in place every admission
#: (input-output aliasing, verified by the jaxcheck donation lint);
#: model values and tparams are NOT donated (they persist across swaps)
SCORE_DONATE_ARGNUMS = (0,)


@functools.lru_cache(maxsize=None)
def jitted_score_update(spec: ScoreSpec):
    """The multi-dispatch scoring launch: one device program updating
    the feature state and scoring every lane from (wire, verdicts).
    Cache keyed on the score geometry only; batch shape specializes
    through jit's shape keying (warmed by the scheduler ladder).  The
    state operand is DONATED; the model/tparams operands are persistent
    device arrays swapped whole on a model hot-swap (no recompile)."""
    import jax

    from . import jaxpath

    def f(sc, model, tparams, wire, tenant, tflags, res):
        return _score_update_core(
            sc, jaxpath.unpack_wire(wire), tenant, tflags, res, model,
            tparams, spec=spec,
        )

    return jax.jit(f, donate_argnums=SCORE_DONATE_ARGNUMS)


@functools.lru_cache(maxsize=None)
def jitted_score_drain():
    """Donated window reset: tstat and the per-row anomaly-hit column
    zero in place; the rate state (source rows, count-min) persists —
    rates are continuous features, not window counters."""
    import jax
    import jax.numpy as jnp

    def f(sc):
        scols = jnp.concatenate(
            [sc.scols[:, 0:6], jnp.zeros_like(sc.scols[:, 6:8])], axis=1
        )
        return ScoreState(
            skeys=sc.skeys, scols=scols, cms=sc.cms,
            tstat=jnp.zeros_like(sc.tstat), epoch=sc.epoch,
        )

    return jax.jit(f, donate_argnums=(0,))
