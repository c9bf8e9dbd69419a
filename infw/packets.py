"""Batched packet representation.

The reference's per-packet inputs are the XDP context fields consumed by
ingress_node_firewall_main and ip_extract_l4info
(/root/reference/bpf/ingress_node_firewall_kernel.c:95-174,412-439):
ethertype, source IP, L4 protocol, destination port or ICMP type/code,
ingress ifindex and packet length.  The TPU dataplane consumes those same
fields as a struct-of-arrays batch; header parsing from raw bytes happens
host-side (infw.obs.pcap) or packets are generated synthetically.

Field conventions:
- ``kind``: KIND_* code for the ethertype switch outcome (constants.py);
- ``l4_ok``: 0 if ip_extract_l4info would have failed (unsupported L4
  protocol or truncated header) -> lookup returns SET_ACTION(UNDEF);
- ``ip_words``: (B, 4) uint32 big-endian words of the 16-byte source-IP key
  data (IPv4 packets occupy word 0, rest zero — kernel.c:206-212);
- ``dst_port`` is host byte order (the kernel compares bpf_ntohs(dstPort));
- ``pkt_len`` is the full frame length (bpf_xdp_get_buff_len).
"""
from __future__ import annotations

import os as _os
import subprocess as _subprocess
import zlib as _zlib
from dataclasses import dataclass
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .constants import IPPROTO_ICMP, IPPROTO_ICMPV6, KIND_IPV4, KIND_IPV6
from .netutil import ip_str_to_words

_native_pack_unavailable = False


@dataclass
class PacketBatch:
    kind: np.ndarray       # (B,) int32
    l4_ok: np.ndarray      # (B,) int32 (0/1)
    ifindex: np.ndarray    # (B,) int32
    ip_words: np.ndarray   # (B, 4) uint32
    proto: np.ndarray      # (B,) int32
    dst_port: np.ndarray   # (B,) int32
    icmp_type: np.ndarray  # (B,) int32
    icmp_code: np.ndarray  # (B,) int32
    pkt_len: np.ndarray    # (B,) int32
    #: optional (B,) int32 TCP flag bits (jaxpath.TCP_*) consumed by the
    #: stateful flow tier's SYN/EST/FIN/RST state machine; None (sources
    #: that carry no flags) degrades the TCP model to established-on-
    #: first-packet.  Never crosses the classify wire formats — the
    #: verdict does not depend on it.
    tcp_flags: Optional[np.ndarray] = None
    #: optional (B, L) uint8 payload-prefix column (first 64/128 bytes,
    #: ISSUE-19) consumed by the payload-matching tier, plus its (B,)
    #: int32 valid-byte counts (bytes past ``payload_len[i]`` are
    #: padding the matcher masks off).  Rides BESIDE the packed wire —
    #: header classification never reads it, so header-only sources
    #: (None) skip the tier without a shape change.
    payload: Optional[np.ndarray] = None
    payload_len: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return int(self.kind.shape[0])

    @property
    def size(self) -> int:
        return len(self)

    def slice(self, start: int, stop: int) -> "PacketBatch":
        return PacketBatch(
            **{
                f: getattr(self, f)[start:stop]
                for f in (
                    "kind l4_ok ifindex ip_words proto dst_port "
                    "icmp_type icmp_code pkt_len".split()
                )
            },
            tcp_flags=(
                None if self.tcp_flags is None
                else self.tcp_flags[start:stop]
            ),
            payload=(
                None if self.payload is None else self.payload[start:stop]
            ),
            payload_len=(
                None if self.payload_len is None
                else self.payload_len[start:stop]
            ),
        )

    def take(self, idx: np.ndarray) -> "PacketBatch":
        """Arbitrary-index subset (used to regroup packets by family so
        each device chunk is depth-homogeneous)."""
        return PacketBatch(
            **{
                f: getattr(self, f)[idx]
                for f in (
                    "kind l4_ok ifindex ip_words proto dst_port "
                    "icmp_type icmp_code pkt_len".split()
                )
            },
            tcp_flags=(
                None if self.tcp_flags is None else self.tcp_flags[idx]
            ),
            payload=(
                None if self.payload is None else self.payload[idx]
            ),
            payload_len=(
                None if self.payload_len is None else self.payload_len[idx]
            ),
        )

    def pack_wire(self) -> np.ndarray:
        """Pack into the (B, 7) uint32 device wire format — 28B/packet
        instead of 9 separate int32 arrays (48B/packet).  The host→device
        link (PCIe) is the streaming bottleneck, so the descriptor is
        packed like a NIC ring entry:

          w0: kind(2) | l4_ok(1)<<2 | proto(8)<<3 | icmpType(8)<<11
              | icmpCode(8)<<19 | pktLenHi(5)<<27
          w1: dstPort(16) | pktLenLo(16)<<16
          w2: ifindex (full u32)
          w3..w6: ip_words

        pktLen carries 21 bits (clamp at 2 MiB - 1): jumbo frames are
        < 10K and even BIG-TCP GRO/TSO aggregates cap at 512 KiB, so no
        real capture frame clips and byte statistics stay exact.

        Device-side inverse: kernels.jaxpath.unpack_wire (fused into the
        classify jit, so unpacking costs no extra HBM round trip)."""
        out = np.empty((len(self), 7), np.uint32)
        self._pack_wire_header(out)
        out[:, 3:7] = self.ip_words.astype(np.uint32)
        return out

    def _pack_wire_header(self, out: np.ndarray) -> None:
        """w0..w2 of the wire layout (shared by the 7- and 4-word formats)."""
        plen = np.clip(self.pkt_len, 0, 0x1FFFFF).astype(np.uint32)
        out[:, 0] = (
            (self.kind.astype(np.uint32) & 3)
            | ((self.l4_ok.astype(np.uint32) & 1) << 2)
            | ((self.proto.astype(np.uint32) & 0xFF) << 3)
            | ((self.icmp_type.astype(np.uint32) & 0xFF) << 11)
            | ((self.icmp_code.astype(np.uint32) & 0xFF) << 19)
            | ((plen >> 16) << 27)
        )
        out[:, 1] = (self.dst_port.astype(np.uint32) & 0xFFFF) | (
            (plen & 0xFFFF) << 16
        )
        out[:, 2] = self.ifindex.astype(np.uint32)

    def is_v4_compactable(self) -> bool:
        """True when the batch can take the 4-word wire format: no IPv6
        packets and no nonzero high IP words (the host parser guarantees
        zeros for v4/malformed/other frames; synthetic batches may not)."""
        return not bool(
            (np.asarray(self.kind) == KIND_IPV6).any()
        ) and not bool(np.asarray(self.ip_words)[:, 1:].any())

    def pack_wire_v4(self) -> np.ndarray:
        """The family-compact (B, 4) uint32 wire format — 16B/packet for
        v4-only chunks (the daemon's ingest regroups by family, so the
        majority family of real traffic takes this path): w0..w2 as
        pack_wire, w3 = IP word 0.  Caller contract: is_v4_compactable().
        Device-side inverse: unpack_wire (width-discriminated)."""
        out = np.empty((len(self), 4), np.uint32)
        self._pack_wire_header(out)
        out[:, 3] = self.ip_words[:, 0].astype(np.uint32)
        return out

    def pack_wire_subset(self, idx: np.ndarray) -> Tuple[np.ndarray, bool]:
        """take(idx) + pack_wire[_v4] fused into one pass -> (wire,
        v4_only).  Dispatches to the native C++ kernel when available
        (the daemon's per-chunk hot path: copying 9 SoA arrays per chunk
        just to re-pack them doubles the host cost); NumPy fallback is
        the composed slow path, differentially tested against it."""
        global _native_pack_unavailable
        idx = np.ascontiguousarray(idx, np.int64)
        if not _native_pack_unavailable:
            try:
                return self._pack_wire_subset_native(idx)
            except (OSError, ImportError, AttributeError, AssertionError,
                    _subprocess.SubprocessError):
                _native_pack_unavailable = True
        sub = self.take(idx)
        compact = sub.is_v4_compactable()
        wire = sub.pack_wire_v4() if compact else sub.pack_wire()
        v4_only = not bool((np.asarray(sub.kind) == KIND_IPV6).any())
        return wire, v4_only

    def _pack_wire_subset_native(self, idx: np.ndarray) -> Tuple[np.ndarray, bool]:
        import ctypes

        from .backend.cpu_ref import load_library

        lib = load_library()
        n = len(idx)
        flat = np.empty(n * 7, np.uint32)
        c = lambda a, dt: np.ascontiguousarray(a, dt)
        p = lambda a, t: a.ctypes.data_as(ctypes.POINTER(t))
        kind = c(self.kind, np.int32)
        l4_ok = c(self.l4_ok, np.int32)
        ifindex = c(self.ifindex, np.int32)
        words = c(self.ip_words, np.uint32)
        proto = c(self.proto, np.int32)
        dst_port = c(self.dst_port, np.int32)
        icmp_type = c(self.icmp_type, np.int32)
        icmp_code = c(self.icmp_code, np.int32)
        pkt_len = c(self.pkt_len, np.int32)
        flags = lib.infw_pack_wire_subset(
            n, p(idx, ctypes.c_int64),
            p(kind, ctypes.c_int32), p(l4_ok, ctypes.c_int32),
            p(ifindex, ctypes.c_int32), p(words, ctypes.c_uint32),
            p(proto, ctypes.c_int32), p(dst_port, ctypes.c_int32),
            p(icmp_type, ctypes.c_int32), p(icmp_code, ctypes.c_int32),
            p(pkt_len, ctypes.c_int32),
            p(flat, ctypes.c_uint32), min(8, _os.cpu_count() or 1),
        )
        compact = bool(flags & 1)
        w = 4 if compact else 7
        return flat[: n * w].reshape(n, w), bool(flags & 2)

    def pad_to(self, n: int) -> "PacketBatch":
        """Pad with KIND_OTHER packets (always XDP_PASS, no stats) so batch
        shapes stay static under jit."""
        b = len(self)
        if b >= n:
            return self
        pad = n - b

        def _pad(a):
            widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
            return np.pad(a, widths, constant_values=3)  # KIND_OTHER / junk

        return PacketBatch(
            kind=_pad(self.kind),
            l4_ok=np.pad(self.l4_ok, (0, pad)),
            ifindex=np.pad(self.ifindex, (0, pad)),
            ip_words=np.pad(self.ip_words, ((0, pad), (0, 0))),
            proto=np.pad(self.proto, (0, pad)),
            dst_port=np.pad(self.dst_port, (0, pad)),
            icmp_type=np.pad(self.icmp_type, (0, pad)),
            icmp_code=np.pad(self.icmp_code, (0, pad)),
            pkt_len=np.pad(self.pkt_len, (0, pad)),
            tcp_flags=(
                None if self.tcp_flags is None
                else np.pad(self.tcp_flags, (0, pad))
            ),
            payload=(
                None if self.payload is None
                else np.pad(self.payload, ((0, pad), (0, 0)))
            ),
            payload_len=(
                None if self.payload_len is None
                else np.pad(self.payload_len, (0, pad))
            ),
        )


def make_batch(
    *,
    src: Sequence[str],
    proto: Sequence[int],
    ifindex: Sequence[int],
    dst_port: Optional[Sequence[int]] = None,
    icmp_type: Optional[Sequence[int]] = None,
    icmp_code: Optional[Sequence[int]] = None,
    pkt_len: Optional[Sequence[int]] = None,
    l4_ok: Optional[Sequence[int]] = None,
    kind: Optional[Sequence[int]] = None,
) -> PacketBatch:
    """Convenience constructor from parallel per-packet field lists; ``src``
    is a list of IP address strings and determines v4/v6 kind."""
    b = len(src)
    words = np.zeros((b, 4), np.uint32)
    kinds = np.zeros(b, np.int32)
    for i, addr in enumerate(src):
        w, is_v4 = ip_str_to_words(addr)
        words[i] = w
        kinds[i] = KIND_IPV4 if is_v4 else KIND_IPV6
    if kind is not None:
        kinds = np.asarray(kind, np.int32)

    def arr(x, default=0):
        if x is None:
            return np.full(b, default, np.int32)
        return np.asarray(x, np.int32)

    return PacketBatch(
        kind=kinds,
        l4_ok=arr(l4_ok, 1),
        ifindex=arr(ifindex),
        ip_words=words,
        proto=arr(proto),
        dst_port=arr(dst_port),
        icmp_type=arr(icmp_type),
        icmp_code=arr(icmp_code),
        pkt_len=arr(pkt_len, 64),
    )


def concat(batches: List[PacketBatch]) -> PacketBatch:
    flags = None
    if any(b.tcp_flags is not None for b in batches):
        flags = np.concatenate([
            b.tcp_flags if b.tcp_flags is not None
            else np.zeros(len(b), np.int32)
            for b in batches
        ])
    return PacketBatch(
        **{
            f: np.concatenate([getattr(b, f) for b in batches])
            for f in (
                "kind l4_ok ifindex ip_words proto dst_port "
                "icmp_type icmp_code pkt_len".split()
            )
        },
        tcp_flags=flags,
    )


def expand_wire_v4(w: np.ndarray) -> np.ndarray:
    """(n, 4) compact wire rows -> (n, 7): zero high IP words (the compact
    format's eligibility guarantee).  Lives next to pack_wire/pack_wire_v4
    so the 4-word/7-word correspondence has one owner; used when a merged
    ingest job mixes compact and full segments and must ship one width."""
    out = np.zeros((w.shape[0], 7), np.uint32)
    out[:, :4] = w
    return out


def _l4_word(w0: np.ndarray, w1: np.ndarray) -> np.ndarray:
    """The 16-bit l4 overlay shared by narrow_wire and wire8: dst_port
    for transport rows, type<<8|code for the family ICMPs — lossless for
    classification because the ordered scan never reads both
    (kernel.c:222-258)."""
    proto = (w0 >> 3) & 0xFF
    is_icmp = (proto == IPPROTO_ICMP) | (proto == IPPROTO_ICMPV6)
    return np.where(
        is_icmp,
        ((w0 >> 11) & 0xFF) << 8 | ((w0 >> 19) & 0xFF),
        w1 & 0xFFFF,
    ).astype(np.uint32)


def _ifindex_dict(ifx: np.ndarray):
    """The ifindex dictionary shared by wire8 and the delta codec — ONE
    implementation of the device contract (<= 15 distinct interfaces per
    chunk, 16-slot ifmap padded with -1, 4-bit indexes) so the formats'
    eligibility can never desynchronize.  Returns (ifmap, ifdict) or
    None when the chunk exceeds the cap."""
    uniq = np.unique(ifx)
    if len(uniq) > 15:
        return None
    ifmap = np.full(16, -1, np.int32)
    ifmap[: len(uniq)] = uniq.astype(np.int64)
    return ifmap, np.searchsorted(uniq, ifx).astype(np.uint32)


def narrow_wire(w: np.ndarray):
    """(n, 4|7) wire -> the NARROW (n, 3|6) format, or None when the rows
    don't qualify.  Saves one word per packet (v4 16B -> 12B, v6 28B ->
    24B) on the H2D link — the replay bottleneck — by (a) folding the
    ifindex into w0 when every ifindex fits 16 bits, and (b) overlaying
    dst_port with the ICMP type/code in one 16-bit "l4 word", which is
    LOSSLESS for classification: the ordered scan reads dst_port only for
    transport protocols and the ICMP fields only for the family's ICMP
    protocol (kernel.c:222-258), never both, and the kernels' parse sets
    l4_ok=0 for any other protocol.  pkt_len must fit 16 bits (w0's
    high-bit stash must be clear) so byte statistics stay exact.

    Narrow layout:
      w0: kind(2) | l4_ok(1)<<2 | proto(8)<<3 | ifindex(16)<<11
      w1: l4word(16) | pktLen(16)<<16
      w2..: ip word 0 (v4) / words 0..3 (v6)

    Device-side inverse: kernels.jaxpath.unpack_wire (width 3/6)."""
    w0 = w[:, 0]
    ifx = w[:, 2]
    if int(w0.size) == 0:
        return np.zeros((0, w.shape[1] - 1), np.uint32)
    if (w0 >> 27).any() or (ifx >> 16).any():
        return None  # pkt_len >= 64KiB or wide ifindex: keep the full form
    l4w = _l4_word(w0, w[:, 1])
    out = np.empty((w.shape[0], w.shape[1] - 1), np.uint32)
    out[:, 0] = (w0 & 0x7FF) | (ifx << 11)
    out[:, 1] = l4w | (w[:, 1] & 0xFFFF0000)  # pktLen low 16 stays in place
    out[:, 2:] = w[:, 3:]
    return out


def wire8(w: np.ndarray):
    """(n, 4) v4-compact wire -> the 8-BYTE format, or None when the rows
    don't qualify: (n, 2) uint32 rows plus the (16,) int32 ifindex
    dictionary the device decodes through.

    The byte diet beyond the 12B narrow form comes from two observations:
    (a) classification itself never reads pkt_len — it exists only for
    byte statistics, which the host can compute EXACTLY from the returned
    verdicts and its own pkt_len column (stats_from_results), so the
    length never needs to cross the link; (b) a chunk rarely spans more
    than a handful of interfaces, so a 4-bit dictionary index replaces
    the 16-bit ifindex (the bond-expansion world of interfaces.go:85-116
    still fits: 15 member links per chunk).

    Layout:  w0: kind(2) | l4_ok(1)<<2 | proto(8)<<3 | ifdict(4)<<11 |
                 l4word(16)<<15          (l4word as in narrow_wire)
             w1: ip word 0
    Device-side inverse: kernels.jaxpath.unpack_wire8 (needs the dict).
    Qualifies only v4-compact chunks (ip words 1..3 zero — the caller's
    pack_wire_v4 contract)."""
    if w.shape[1] != 4:
        return None
    if w.shape[0] == 0:
        return np.zeros((0, 2), np.uint32), np.full(16, -1, np.int32)
    w0 = w[:, 0]
    d = _ifindex_dict(w[:, 2])
    if d is None:
        return None
    ifmap, ifdict = d
    l4w = _l4_word(w0, w[:, 1])
    out = np.empty((w.shape[0], 2), np.uint32)
    out[:, 0] = (w0 & 0x7FF) | (ifdict << 11) | (l4w << 15)
    out[:, 1] = w[:, 3]
    return out, ifmap


# --- delta+varint compressed wire (the sub-8B format) -----------------------
#
# The replay tier is host->device LINK bound (VERDICT round-5 weak #1: the
# end-to-end record sits at ~0.5 M pkts/s against a ~49 M/s
# device-attributable rate), so bytes-per-packet is the lever.  wire8
# reached 8 B by shedding pkt_len and dictionary-coding the ifindex; the
# delta format goes below it by exploiting the same locality that
# cache-aware forwarding tables exploit (PAPERS: cache-aware FIB
# structures): a chunk's IP words cluster under the table's prefixes, so
# SORTING the chunk by IP and shipping varint-coded deltas averages 2-3
# bytes where the raw word costs 4.  The sort permutation never crosses
# the link — the device classifies in sorted order and the HOST applies
# the inverse permutation to the returned verdicts (order is host-side
# bookkeeping, exactly like pkt_len).
#
# Layout (three sections, offsets fully determined by (n, dict_mode,
# fixed_w) — the "fixed-stride plan" the device decoder specializes on):
#   A: meta15 dictionary indexes — meta15 = kind(2) | l4_ok(1)<<2 |
#      proto(8)<<3 | ifdict(4)<<11, the sub-l4 bits of wire8's w0.  A
#      chunk rarely holds more than a handful of distinct (kind, proto,
#      iface) combinations, so: dict_mode 0 = single value, no section;
#      1 = <=16 values, two 4-bit indexes per byte; 2 = <=256 values,
#      one byte each.
#   B: l4 word (narrow_wire's port/ICMP overlay), 2 bytes LE per packet
#      (ports are uniform in practice — varint would usually cost 3).
#   C: sorted-IP deltas — LEB128 varints (7 bits per byte, bit 7 =
#      continuation), or a fixed 1/2/4-byte little-endian stride when
#      that costs no more (fixed_w > 0; enables the Pallas decode plan).
#      The first "delta" is the absolute first sorted IP word.
#
# Device-side inverse: kernels.wire_decode (XLA parallel varint decode /
# fixed-stride expand + cumsum).  Host-side inverse + fail-closed
# validation: decode_delta_host below (crc over the shipped bytes, strict
# varint structure checks) — the codec never guesses on corrupt input.

#: varint width thresholds: value v needs 1 + sum(v >= 2^(7k)) bytes
_VARINT_STEPS = tuple(np.uint64(1) << np.uint64(7 * k) for k in range(1, 5))


@dataclass
class DeltaWire:
    """One encoded chunk.  ``payload`` is what crosses the link (plus the
    tiny ``dict_vals``/``ifmap`` headers); ``perm`` stays host-side."""

    payload: np.ndarray    # (P,) uint8 — sections A | B | C
    dict_vals: np.ndarray  # (D,) uint32 meta15 dictionary, D >= 1
    ifmap: np.ndarray      # (16,) int32 wire8-style ifindex dictionary
    perm: np.ndarray       # (n,) int64 sort permutation (host-only)
    n: int
    dict_mode: int         # 0 = constant, 1 = 4-bit packed, 2 = u8
    fixed_w: int           # 0 = varint section C, else 1/2/4-byte stride
    crc: int               # crc32 over payload+dict_vals+ifmap

    @property
    def wire_bytes(self) -> int:
        return int(self.payload.nbytes)


def delta_section_offsets(n: int, dict_mode: int) -> Tuple[int, int]:
    """(offset of section B, offset of section C's start) — the static
    layout contract shared with the device decoder."""
    n_a = 0 if dict_mode == 0 else ((n + 1) // 2 if dict_mode == 1 else n)
    return n_a, n_a + 2 * n


def varint_encode(vals: np.ndarray) -> np.ndarray:
    """Vectorized LEB128 encode of uint64 values (< 2^35 — deltas are
    32-bit so at most 5 bytes each)."""
    v = np.ascontiguousarray(vals, np.uint64)
    nb = np.ones(len(v), np.int64)
    for step in _VARINT_STEPS:
        nb += v >= step
    ends = np.cumsum(nb)
    starts = ends - nb
    out = np.zeros(int(ends[-1]) if len(v) else 0, np.uint8)
    for k in range(5):
        m = nb > k
        if not m.any():
            break
        chunk = (v[m] >> np.uint64(7 * k)) & np.uint64(0x7F)
        cont = (nb[m] - 1 > k).astype(np.uint8) << 7
        out[starts[m] + k] = chunk.astype(np.uint8) | cont
    return out


def _delta_crc(payload: np.ndarray, dict_vals: np.ndarray,
               ifmap: np.ndarray) -> int:
    crc = _zlib.crc32(np.ascontiguousarray(payload, np.uint8).tobytes())
    crc = _zlib.crc32(np.ascontiguousarray(dict_vals, "<u4").tobytes(), crc)
    return _zlib.crc32(np.ascontiguousarray(ifmap, "<i4").tobytes(), crc)


#: sticky gate: once the native library fails to load/bind, stop
#: retrying per chunk (the pack-subset pattern)
_native_delta_unavailable = False


def _encode_delta_native(
    w: np.ndarray, max_bytes_per_pkt: Optional[float]
) -> Optional[DeltaWire]:
    """Native (C++) single-pass delta encode — byte-identical to the
    NumPy reference below (differentially tested); raises on library
    unavailability so the caller can fall back, returns None on the
    same non-qualification conditions."""
    import ctypes

    from .backend.cpu_ref import load_library

    lib = load_library()
    n = w.shape[0]
    wc = np.ascontiguousarray(w, np.uint32)
    payload = np.empty(8 * n, np.uint8)
    dict_vals = np.empty(256, np.uint32)
    ifmap = np.empty(16, np.int32)
    perm = np.empty(n, np.int64)
    meta = np.zeros(3, np.int32)
    p = lambda a, t: a.ctypes.data_as(ctypes.POINTER(t))
    total = lib.infw_encode_delta(
        n, p(wc, ctypes.c_uint32), p(payload, ctypes.c_uint8),
        p(dict_vals, ctypes.c_uint32), p(ifmap, ctypes.c_int32),
        p(perm, ctypes.c_int64), p(meta, ctypes.c_int32),
    )
    if total < 0:
        return None
    payload = payload[:total].copy()
    if max_bytes_per_pkt is not None and len(payload) >= max_bytes_per_pkt * n:
        return None
    dict_vals = dict_vals[: int(meta[0])].copy()
    return DeltaWire(
        payload=payload, dict_vals=dict_vals, ifmap=ifmap, perm=perm,
        n=n, dict_mode=int(meta[1]), fixed_w=int(meta[2]),
        crc=_delta_crc(payload, dict_vals, ifmap),
    )


def encode_delta_wire(
    w: np.ndarray, max_bytes_per_pkt: Optional[float] = None
) -> Optional[DeltaWire]:
    """(n, 4) v4-compact wire -> DeltaWire, or None when the chunk does
    not qualify (not v4-compact, >15 interfaces, >256 distinct meta15
    values, n == 0) or — with ``max_bytes_per_pkt`` set (the auto-codec
    gate) — when the compressed payload would not beat that budget.
    Qualification mirrors wire8: pkt_len never ships (host statistics),
    ifindex travels as a 4-bit dictionary.

    Dispatches to the native C++ single-pass encoder when available
    (ISSUE-12 part 4: host packing is the residual cost of the
    non-resident delta path once dispatch is one fused program — the
    sort + five vectorized sweeps below collapse into one pass); the
    NumPy body is the differentially-tested reference fallback."""
    if w.shape[1] != 4 or w.shape[0] == 0:
        return None
    global _native_delta_unavailable
    if not _native_delta_unavailable:
        try:
            return _encode_delta_native(w, max_bytes_per_pkt)
        except (OSError, ImportError, AttributeError, AssertionError,
                _subprocess.SubprocessError):
            _native_delta_unavailable = True
    n = w.shape[0]
    w0 = w[:, 0]
    d = _ifindex_dict(w[:, 2])
    if d is None:
        return None
    ifmap, ifdict = d
    meta15 = (w0 & 0x7FF) | (ifdict << 11)
    dict_vals, dict_idx = np.unique(meta15, return_inverse=True)
    if len(dict_vals) > 256:
        return None
    dict_mode = 0 if len(dict_vals) == 1 else (1 if len(dict_vals) <= 16 else 2)

    perm = np.argsort(w[:, 3], kind="stable").astype(np.int64)
    ip_sorted = w[perm, 3].astype(np.uint64)
    deltas = np.empty(n, np.uint64)
    deltas[0] = ip_sorted[0]
    np.subtract(ip_sorted[1:], ip_sorted[:-1], out=deltas[1:])
    var_c = varint_encode(deltas)
    # fixed-stride plan: when every delta fits w bytes and the fixed
    # section costs no more than the varints, take the fixed layout (the
    # decode is a pure reshape — the Pallas-friendly plan)
    fixed_w = 0
    dmax = int(deltas.max())
    for cand in (1, 2, 4):
        if dmax < (1 << (8 * cand)) and n * cand <= len(var_c):
            fixed_w = cand
            break

    l4 = _l4_word(w0, w[:, 1])[perm]
    midx = dict_idx[perm].astype(np.uint8)
    off_b, off_c = delta_section_offsets(n, dict_mode)
    c_len = n * fixed_w if fixed_w else len(var_c)
    payload = np.zeros(off_c + c_len, np.uint8)
    if dict_mode == 1:
        half = np.zeros(2 * ((n + 1) // 2), np.uint8)
        half[:n] = midx
        payload[:off_b] = half[0::2] | (half[1::2] << 4)
    elif dict_mode == 2:
        payload[:off_b] = midx
    payload[off_b:off_c] = (
        l4.astype("<u2").view(np.uint8).reshape(n, 2).reshape(-1)
    )
    if fixed_w:
        payload[off_c:] = (
            deltas.astype("<u8").view(np.uint8).reshape(n, 8)[:, :fixed_w]
            .reshape(-1)
        )
    else:
        payload[off_c:] = var_c
    if max_bytes_per_pkt is not None and len(payload) >= max_bytes_per_pkt * n:
        return None
    return DeltaWire(
        payload=payload, dict_vals=dict_vals.astype(np.uint32), ifmap=ifmap,
        perm=perm, n=n, dict_mode=dict_mode, fixed_w=fixed_w,
        crc=_delta_crc(payload, dict_vals, ifmap),
    )


class DeltaDecodeError(ValueError):
    """Fail-closed decode failure: the stream is truncated, corrupt or
    structurally invalid.  Callers must drop/deny the whole chunk — the
    codec never yields a best-effort partial decode."""


def _varint_decode_host(buf: np.ndarray, n: int) -> np.ndarray:
    """Strict LEB128 decode of exactly ``n`` values consuming EXACTLY the
    whole buffer; raises DeltaDecodeError on any structural violation
    (dangling continuation, >5-byte runs, overlong count, trailing
    bytes)."""
    b = np.asarray(buf, np.uint8)
    if n == 0:
        if len(b):
            raise DeltaDecodeError("trailing bytes after 0-value stream")
        return np.zeros(0, np.uint64)
    if len(b) == 0:
        raise DeltaDecodeError("empty varint section")
    term = (b & 0x80) == 0
    n_vals = int(term.sum())
    if n_vals != n:
        raise DeltaDecodeError(f"varint stream holds {n_vals} values, "
                               f"expected {n}")
    if not term[-1]:
        raise DeltaDecodeError("dangling continuation byte at stream end")
    ends = np.nonzero(term)[0]
    starts = np.concatenate([[-1], ends[:-1]]) + 1
    lens = ends - starts + 1
    if int(lens.max()) > 5:
        raise DeltaDecodeError("varint run exceeds 5 bytes (32-bit domain)")
    vals = np.zeros(n, np.uint64)
    for k in range(5):
        m = lens > k
        if not m.any():
            break
        vals[m] |= (b[starts[m] + k].astype(np.uint64) & 0x7F) << np.uint64(
            7 * k
        )
    if int(vals.max()) > 0xFFFFFFFF:
        raise DeltaDecodeError("varint value exceeds 32 bits")
    return vals


def decode_delta_host(dw: DeltaWire) -> Tuple[np.ndarray, ...]:
    """CPU inverse + validation oracle of encode_delta_wire: returns the
    classification fields in SORTED (stream) order — (kind, l4_ok,
    ifindex, proto, dst_port, icmp_type, icmp_code, ip_word0), the
    unpack_wire8 field contract (pkt_len never ships).  Raises
    DeltaDecodeError on ANY integrity violation: crc mismatch, bad
    section lengths, malformed varints, out-of-range dictionary indexes,
    delta overflow past 2^32.  This is the fail-closed boundary — a
    corrupt stream denies the chunk, it never misclassifies."""
    n = int(dw.n)
    if n < 0:
        raise DeltaDecodeError("negative packet count")
    if dw.crc != _delta_crc(dw.payload, dw.dict_vals, dw.ifmap):
        raise DeltaDecodeError("payload crc mismatch")
    if dw.dict_mode not in (0, 1, 2) or dw.fixed_w not in (0, 1, 2, 4):
        raise DeltaDecodeError("invalid layout flags")
    if len(dw.dict_vals) < 1 or len(dw.dict_vals) > 256:
        raise DeltaDecodeError("invalid dictionary size")
    off_b, off_c = delta_section_offsets(n, dw.dict_mode)
    p = np.asarray(dw.payload, np.uint8)
    if len(p) < off_c:
        raise DeltaDecodeError("payload shorter than fixed sections")
    if dw.fixed_w and len(p) != off_c + n * dw.fixed_w:
        raise DeltaDecodeError("fixed-stride section length mismatch")
    if dw.dict_mode == 0:
        dict_idx = np.zeros(n, np.int64)
    elif dw.dict_mode == 1:
        half = p[:off_b]
        dict_idx = np.empty(2 * len(half), np.int64)
        dict_idx[0::2] = half & 0xF
        dict_idx[1::2] = half >> 4
        if n % 2 and dict_idx[n] != 0:
            raise DeltaDecodeError("nonzero padding nibble")
        dict_idx = dict_idx[:n]
    else:
        dict_idx = p[:n].astype(np.int64)
    if n and int(dict_idx.max()) >= len(dw.dict_vals):
        raise DeltaDecodeError("dictionary index out of range")
    l4 = p[off_b:off_c].view("<u2").astype(np.int64)
    if dw.fixed_w:
        raw = np.zeros((n, 8), np.uint8)
        raw[:, : dw.fixed_w] = p[off_c:].reshape(n, dw.fixed_w)
        deltas = raw.reshape(-1).view("<u8").astype(np.uint64)
    else:
        deltas = _varint_decode_host(p[off_c:], n)
    ip = np.cumsum(deltas, dtype=np.uint64)
    if n and int(ip[-1]) > 0xFFFFFFFF:
        raise DeltaDecodeError("delta sum overflows 32-bit IP word")
    meta = dw.dict_vals[dict_idx].astype(np.int64)
    kind = (meta & 3).astype(np.int32)
    l4_ok = ((meta >> 2) & 1).astype(np.int32)
    proto = ((meta >> 3) & 0xFF).astype(np.int32)
    ifd = ((meta >> 11) & 0xF).astype(np.int64)
    ifindex = np.asarray(dw.ifmap, np.int32)[ifd]
    is_icmp = (proto == IPPROTO_ICMP) | (proto == IPPROTO_ICMPV6)
    dst_port = np.where(is_icmp, 0, l4).astype(np.int32)
    icmp_type = np.where(is_icmp, l4 >> 8, 0).astype(np.int32)
    icmp_code = np.where(is_icmp, l4 & 0xFF, 0).astype(np.int32)
    return (kind, l4_ok, ifindex, proto, dst_port, icmp_type, icmp_code,
            ip.astype(np.uint32))
