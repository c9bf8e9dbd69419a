"""Fused Pallas TPU classification kernel (dense path).

TPU-first re-expression of the XDP hot path
(/root/reference/bpf/ingress_node_firewall_kernel.c:189-457) for tables up
to a few thousand targets (the reference caps at MAX_TARGETS=1024,
bpf/ingress_node_firewall.h:13).  Instead of a pointer-chasing LPM trie +
unrolled scan per packet, the whole classification becomes three MXU
matmuls per packet block:

1. **LPM as bit-matmul**: the 160-bit LPM key (ifindex:32 || srcIP:128) is
   unpacked to a {0,1} int8 matrix; for each table entry two int8 matrices
   M0 = mask & ~prefix and M1 = mask & prefix are prebuilt.  The number of
   in-mask mismatching bits is  bits @ M0 + (1-bits) @ M1  (int8 x int8 ->
   int32 on the MXU); an entry matches iff that count is 0.  Longest
   prefix selection is a max over (mask_len+1) scores with first-index
   tie-break; the packet-side prefix caps (v4 <= /32, kernel.c:207) become
   a score mask.
2. **Rule-row gather as one-hot matmul**: the matched target's packed rule
   bytes are fetched by onehot(tidx) @ rules_bytes — the MXU plays the
   role of the map lookup, keeping the whole rule table in VMEM.
3. **Ordered first-match scan**: vectorized over the 128-padded rule axis
   with min-index selection; identical semantics to kernel.c:222-258.

The kernel emits per-packet (result, tidx); XDP verdict + statistics are
fused around it by XLA (jaxpath.finalize).  tidx doubles as the
debug-lookup record (the reference's dbg hash map, kernel.c:59-64).
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

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
from .jaxpath import DeviceBatch, finalize

BLOCK_B = 256     # default packets per grid step (see classify_pallas block_b)
RULE_PAD = 128    # padded rule axis (MAX_RULES_PER_TARGET=100 <= 128)
# Field-major rule-byte layout: rid_act packs (ruleId<<1)|(action-1) in one
# byte (ruleId <= 100 -> 7 bits; action in {1,2} -> 1 bit), giving exactly
# 8*128 = 1024 gather columns — MXU-tile aligned, and 11% less work than a
# separate action column.
NUM_FIELDS = 8    # rid_act, proto, ps_hi, ps_lo, pe_hi, pe_lo, itype, icode
KEY_BITS = 160
MAX_DENSE_TARGETS = 4096
# Measured on v5e (100K rule entries = 1000 CIDRs x 100 rules): int8 MXU
# path beats bf16 (17.1 vs 22.6 ms/2^20 at block 256); block sweep gives
# 256: 67.0, 512: 74.7, 1024: 78.6 M pkts/s; 2048 exceeds the 16MB
# scoped-VMEM limit (the (Bb, Tp) i32 mismatch + rule-row blocks double).
DEFAULT_DTYPE = "int8"


def choose_block_b(num_targets_padded: int) -> int:
    """Largest packet block that keeps the kernel inside scoped VMEM for
    the given (padded) target count."""
    return 1024 if num_targets_padded <= 1024 else BLOCK_B


class PallasTables(NamedTuple):
    """Dense-kernel table operands (device arrays).

    Two exact-integer MXU paths, selected by the operand dtype:
    - int8 (default): s8 x s8 -> s32, double-rate on v5e; rule bytes are
      stored biased by -128 so [0,255] fits s8 (bias re-added in-kernel).
    - bf16: bf16 x bf16 -> f32; every value is a small integer in [-1,255]
      that bf16 represents exactly, and f32 accumulation of <=160 products
      is exact.

    The LPM mismatch count folds into ONE matmul:
        mism = bits @ (M0 - M1) + rowsum(M1)
    where M0 = mask & ~prefix, M1 = mask & prefix: bits@M0 counts
    should-be-zero key bits that are one, (1-bits)@M1 counts should-be-one
    bits that are zero, and expanding (1-bits)@M1 gives the folded form."""

    mdt: jax.Array       # (KEY_BITS, Tp) int8|bf16 — M0 - M1, in {-1,0,1}
    m1sum: jax.Array     # (1, Tp) int32|f32 — per-entry rowsum(M1)
    mask_len: jax.Array  # (1, Tp) int32, -1 for padding columns
    rules_bytes: jax.Array  # (Tp, NUM_FIELDS*RULE_PAD) int8 (biased -128) | bf16


def _round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def build_pallas_tables(tables: CompiledTables, dtype: str = DEFAULT_DTYPE) -> PallasTables:
    """Host-side packing of CompiledTables into the bit-matrix layout.

    dtype "bf16": operands bf16, accumulate f32 (values <= 255, exact).
    dtype "int8": operands int8, accumulate int32 on the MXU's double-rate
    s8 path; rule bytes are stored biased by -128 so [0,255] fits s8, and
    the kernel adds the bias back after the one-hot gather (exact)."""
    T = tables.num_entries
    if T > MAX_DENSE_TARGETS:
        raise ValueError(
            f"dense kernel supports up to {MAX_DENSE_TARGETS} targets, got {T}"
        )
    Tp = _round_up(max(T, 1), 128)

    key_words = tables.key_words.astype(np.uint32)[:T]
    mask_words = tables.mask_words.astype(np.uint32)[:T]

    # (T, 160) bit expansion, big-endian within each word.
    def unpack_bits(words: np.ndarray) -> np.ndarray:
        out = np.zeros((words.shape[0], KEY_BITS), np.int8)
        for w in range(5):
            for b in range(32):
                out[:, w * 32 + b] = (words[:, w] >> np.uint32(31 - b)) & 1
        return out

    prefix_bits = unpack_bits(key_words) if T else np.zeros((0, KEY_BITS), np.int8)
    mask_bits = unpack_bits(mask_words) if T else np.zeros((0, KEY_BITS), np.int8)
    m0 = mask_bits & (1 - prefix_bits)
    m1 = mask_bits & prefix_bits

    mdt = np.zeros((KEY_BITS, Tp), np.float32)
    mdt[:, :T] = (m0.astype(np.int32) - m1.astype(np.int32)).T
    m1sum = np.zeros((1, Tp), np.float32)
    m1sum[0, :T] = m1.sum(axis=1)

    mask_len = np.full((1, Tp), -1, np.int32)
    mask_len[0, :T] = tables.mask_len[:T]

    R = tables.rule_width
    # ruleId and action share one byte as (ruleId<<1)|action, so ruleIds
    # must fit in 7 bits; encode_rules guarantees order < 100, but a caller
    # passing a wider custom table must fail loudly, not misclassify.
    if tables.rule_width > 128:
        raise ValueError(
            f"rule_width {tables.rule_width} > 128: ruleId would not fit "
            "in the packed (ruleId<<1)|action byte"
        )
    rb = np.zeros((Tp, NUM_FIELDS * RULE_PAD), np.float32)
    rules = tables.rules[:T].astype(np.int64)
    max_rid = int(rules[..., 0].max()) if T else 0
    if max_rid > 0x7F:
        raise ValueError(
            f"max ruleId {max_rid} > 127 does not fit the packed "
            "(ruleId<<1)|action byte; use the jax u32 classify path"
        )
    rid = rules[..., 0] & 0x7F
    act = np.clip(rules[..., 6], 1, 2) - 1  # {DENY=1,ALLOW=2} -> {0,1}
    fields = [
        np.where(rules[..., 0] != 0, (rid << 1) | act, 0),  # rid_act
        rules[..., 1] & 0xFF,          # protocol
        (rules[..., 2] >> 8) & 0xFF,   # dstPortStart hi
        rules[..., 2] & 0xFF,          # dstPortStart lo
        (rules[..., 3] >> 8) & 0xFF,   # dstPortEnd hi
        rules[..., 3] & 0xFF,          # dstPortEnd lo
        rules[..., 4] & 0xFF,          # icmpType
        rules[..., 5] & 0xFF,          # icmpCode
    ]
    for f, vals in enumerate(fields):
        rb[:T, f * RULE_PAD : f * RULE_PAD + R] = vals

    if dtype == "int8":
        return PallasTables(
            mdt=jnp.asarray(mdt, jnp.int8),
            m1sum=jnp.asarray(m1sum, jnp.int32),
            mask_len=jnp.asarray(mask_len),
            rules_bytes=jnp.asarray(rb - 128.0, jnp.int8),
        )
    return PallasTables(
        mdt=jnp.asarray(mdt, jnp.bfloat16),
        m1sum=jnp.asarray(m1sum, jnp.float32),
        mask_len=jnp.asarray(mask_len),
        rules_bytes=jnp.asarray(rb, jnp.bfloat16),
    )


def _classify_kernel(fields_ref, words_ref, md_ref, m1s_ref, mlen_ref, rules_ref, out_ref):
    Bb = fields_ref.shape[0]
    Tp = md_ref.shape[1]

    kind = fields_ref[:, 0:1]
    proto = fields_ref[:, 2:3]
    dport = fields_ref[:, 3:4]
    itype = fields_ref[:, 4:5]
    icode = fields_ref[:, 5:6]

    mm_dtype = md_ref.dtype  # bf16 or int8 — selects the MXU path
    acc_dtype = jnp.int32 if mm_dtype == jnp.int8 else jnp.float32

    # --- 1. unpack the 160-bit LPM key ------------------------------------
    iota32 = jax.lax.broadcasted_iota(jnp.int32, (Bb, 32), 1)
    pieces = []
    for w in range(5):
        word = fields_ref[:, 1:2] if w == 0 else words_ref[:, w - 1 : w]
        pieces.append(
            (jax.lax.shift_right_logical(word, 31 - iota32) & 1).astype(mm_dtype)
        )
    bits = jnp.concatenate(pieces, axis=1)  # (Bb, 160) in {0,1}

    # --- 2. LPM: in-mask mismatch counts via ONE MXU matmul ---------------
    # bits@M0 + (1-bits)@M1 == bits@(M0-M1) + rowsum(M1); all terms are
    # small integers, exact on both the bf16->f32 and s8->s32 paths.
    dn = (((1,), (0,)), ((), ()))
    mism = jax.lax.dot_general(
        bits, md_ref[:, :], dn, preferred_element_type=acc_dtype
    ) + m1s_ref[:, :]  # (Bb, Tp) exact small-integer counts

    mlen = mlen_ref[:, :]  # (1, Tp); -1 marks padding
    cap = jnp.where(kind == KIND_IPV4, 32, 128)  # (Bb, 1)
    ok = (mism == jnp.zeros((), acc_dtype)) & (mlen >= 0) & (mlen <= cap)
    score = jnp.where(ok, mlen + 1, 0)  # (Bb, Tp)
    best = jnp.max(score, axis=1, keepdims=True)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (Bb, Tp), 1)
    # First index achieving the (positive) max; tidx == Tp means no match.
    # (score == best) & (score > 0) keeps all operands full-width — Mosaic
    # rejects (B,1)-bool broadcasts through logical ops.
    tidx = jnp.min(
        jnp.where((score == best) & (score > 0), iota_t, Tp), axis=1, keepdims=True
    )
    matched = best > 0

    # --- 3. rule-row fetch: one-hot @ rule bytes on the MXU ---------------
    # tidx == Tp (no match) produces an all-zero row -> ruleId 0 -> UNDEF.
    onehot = (iota_t == tidx).astype(mm_dtype)  # (Bb, Tp)
    rowb = jax.lax.dot_general(
        onehot, rules_ref[:, :], dn, preferred_element_type=acc_dtype
    ).astype(jnp.int32)  # (Bb, 8*RULE_PAD) — one-hot sums are exact bytes
    if mm_dtype == jnp.int8:
        # int8 rule bytes are stored biased by -128; add the bias back for
        # matched packets (no-match rows must stay all-zero -> UNDEF).
        rowb = rowb + jnp.where(matched, 128, 0)

    R = RULE_PAD
    rid_act = rowb[:, 0 * R : 1 * R]
    rid = jax.lax.shift_right_logical(rid_act, 1)
    act = (rid_act & 1) + 1  # {0,1} -> {DENY=1, ALLOW=2}; unused when rid==0
    rproto = rowb[:, 1 * R : 2 * R]
    ps = rowb[:, 2 * R : 3 * R] * 256 + rowb[:, 3 * R : 4 * R]
    pe = rowb[:, 4 * R : 5 * R] * 256 + rowb[:, 5 * R : 6 * R]
    it = rowb[:, 6 * R : 7 * R]
    ic = rowb[:, 7 * R : 8 * R]

    # --- 4. ordered first-match scan (kernel.c:222-258) -------------------
    valid = rid != 0
    proto_eq = (rproto != 0) & (rproto == proto)
    is_transport = (
        (rproto == IPPROTO_TCP) | (rproto == IPPROTO_UDP) | (rproto == IPPROTO_SCTP)
    )
    # boolean algebra instead of a bool-valued select (Mosaic restriction)
    pe_zero = pe == 0
    port_hit = (pe_zero & (dport == ps)) | (
        jnp.logical_not(pe_zero) & (dport >= ps) & (dport < pe)
    )
    fam = jnp.where(kind == KIND_IPV4, IPPROTO_ICMP, IPPROTO_ICMPV6)
    icmp_hit = (rproto == fam) & (it == itype) & (ic == icode)
    hit = valid & ((proto_eq & ((is_transport & port_hit) | icmp_hit)) | (rproto == 0))

    iota_r = jax.lax.broadcasted_iota(jnp.int32, (Bb, R), 1)
    first = jnp.min(jnp.where(hit, iota_r, R), axis=1, keepdims=True)
    any_hit = first < R
    oh2 = (iota_r == first).astype(jnp.int32)
    rid_f = jnp.sum(rid * oh2, axis=1, keepdims=True)
    act_f = jnp.sum(act * oh2, axis=1, keepdims=True)
    result = jnp.where(any_hit, (rid_f << 8) | act_f, 0)

    out_ref[:, 0:1] = result
    out_ref[:, 1:2] = jnp.where(matched, tidx, -1)


def _pallas_scan(
    fields: jax.Array, words: jax.Array, pt: PallasTables, interpret: bool,
    block_b: int,
) -> jax.Array:
    B = fields.shape[0]
    Tp = pt.mdt.shape[1]
    grid = (B // block_b,)
    return pl.pallas_call(
        _classify_kernel,
        out_shape=jax.ShapeDtypeStruct((B, 2), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, 8), lambda i: (i, 0)),
            pl.BlockSpec((block_b, 4), lambda i: (i, 0)),
            pl.BlockSpec((KEY_BITS, Tp), lambda i: (0, 0)),
            pl.BlockSpec((1, Tp), lambda i: (0, 0)),
            pl.BlockSpec((1, Tp), lambda i: (0, 0)),
            pl.BlockSpec((Tp, NUM_FIELDS * RULE_PAD), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_b, 2), lambda i: (i, 0)),
        interpret=interpret,
    )(fields, words, pt.mdt, pt.m1sum, pt.mask_len, pt.rules_bytes)


def classify_pallas(
    pt: PallasTables, batch: DeviceBatch, interpret: bool = False,
    block_b: int = BLOCK_B,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full forward pass via the Pallas kernel; returns (results, xdp,
    stats) identical to jaxpath.classify."""
    B = batch.kind.shape[0]
    Bp = _round_up(max(B, 1), block_b)
    pad = Bp - B

    fields = jnp.stack(
        [
            batch.kind,
            batch.ifindex,
            batch.proto,
            batch.dst_port,
            batch.icmp_type,
            batch.icmp_code,
            batch.l4_ok,
            batch.pkt_len,
        ],
        axis=1,
    ).astype(jnp.int32)
    words = batch.ip_words.astype(jnp.int32)  # bit patterns; shifts are logical
    if pad:
        # Padding packets are KIND_OTHER: always PASS, never recorded.
        pad_fields = jnp.zeros((pad, 8), jnp.int32).at[:, 0].set(KIND_OTHER)
        fields = jnp.concatenate([fields, pad_fields], axis=0)
        words = jnp.concatenate([words, jnp.zeros((pad, 4), jnp.int32)], axis=0)

    out = _pallas_scan(fields, words, pt, interpret, block_b)[:B]
    raw_result = out[:, 0].astype(jnp.uint32)
    return finalize(raw_result, batch)


@functools.lru_cache(maxsize=None)
def _jitted_classify_pallas(interpret: bool, block_b: int):
    return jax.jit(
        functools.partial(classify_pallas, interpret=interpret, block_b=block_b)
    )


def classify_pallas_wire(
    pt: PallasTables, wire: jax.Array, interpret: bool = False,
    block_b: int = BLOCK_B,
) -> Tuple[jax.Array, jax.Array]:
    """Wire-format Pallas pass (see jaxpath.classify_wire): packed (B, 7)
    uint32 descriptors in, (results_u16, stats) out; the unpack fuses into
    the field-stacking that feeds the kernel."""
    from . import jaxpath

    res, _xdp, stats = classify_pallas(
        pt, jaxpath.unpack_wire(wire), interpret=interpret, block_b=block_b
    )
    return res.astype(jnp.uint16), stats


@functools.lru_cache(maxsize=None)
def jitted_classify_pallas_wire(interpret: bool, block_b: int = BLOCK_B):
    return jax.jit(
        functools.partial(classify_pallas_wire, interpret=interpret, block_b=block_b)
    )


@functools.lru_cache(maxsize=None)
def jitted_classify_pallas_wire_fused(interpret: bool, block_b: int = BLOCK_B):
    """Single-buffer output (see jaxpath.fuse_wire_outputs): one D2H RPC
    per chunk instead of two: each readback pays the host-device sync
    floor once."""
    from . import jaxpath

    def f(pt: PallasTables, wire: jax.Array) -> jax.Array:
        return jaxpath.fuse_wire_outputs(
            *classify_pallas_wire(pt, wire, interpret=interpret, block_b=block_b)
        )

    return jax.jit(f)


def jitted_classify_pallas(interpret: bool, block_b: int = BLOCK_B):
    """Cached jit wrapper; the cache key is normalized so callers that omit
    block_b share the entry with callers passing BLOCK_B explicitly."""
    return _jitted_classify_pallas(interpret, block_b)


def default_interpret() -> bool:
    """Pallas kernels compile for the TPU and run in interpret mode only on
    the CPU backend (the tests' platform).  Any other platform raises: a
    kernel silently interpreted there would hide that the device is not a
    TPU."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"no Pallas kernels for the {backend!r} backend (tpu, or cpu in "
        "interpret mode)"
    )
