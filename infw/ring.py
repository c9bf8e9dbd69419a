"""Persistent pinned host ingest ring (ISSUE-12).

A preallocated shared-memory SPSC ring through which producers
(``tools/loadgen.py --ring``) and the daemon's ingest loop exchange
PACKED WIRE chunks: the producer writes each record IN PLACE into a
mapped slot (no per-chunk file create/rename/unlink syscalls, no
per-chunk numpy reallocation), publishes it with one commit-word store,
and the consumer's view IS the H2D staging buffer — ``jax.device_put``
reads straight out of the mapping (zero-copy on the CPU backend for
aligned slots).  The scheduler admits by ring cursor: one record is one
admission-sized chunk, already in the 4/7-word wire layout the packed
dispatch consumes.

Layout (one file, mapped by both sides):

- 4096-byte header page: magic ``INFWRNG1``, version, slots,
  slot_bytes, then the producer ``head`` and consumer ``tail`` cursors
  (uint64, monotonically increasing sequence numbers, each written by
  exactly one side).
- ``slots`` fixed-size slots of ``slot_bytes`` each, 64-byte aligned.
  Slot layout: commit (u64, = sequence + 1 once the payload below it is
  fully written — the publish barrier), n (u32 packets), width (u32, 4
  or 7), flags (u32: bit0 v4_only, bit1 tcp_flags present, bit2 payload
  column present), payload prefix width L (u32, the formerly-reserved
  word; 0 unless bit2), then ``n*width`` uint32 wire words, then ``n``
  int32 TCP flags when present, then the OPTIONAL payload-prefix column
  (ISSUE-19): ``n*L`` uint8 payload bytes + ``n`` int32 payload lengths.
  L is one of kernels.wire_decode.PAYLOAD_PREFIX_WIDTHS (64/128) so the
  column lands in the fixed jit geometry buckets the Aho-Corasick match
  compiles against.

Single-producer / single-consumer by design (the deployment shape: one
loadgen or NIC-facing shim per daemon); the commit word gives the
consumer a torn-read-free publish point without locks.  Overrun policy
is PRODUCER BLOCKS (bounded by ``timeout``): an ingest ring must apply
backpressure, not drop — dropping belongs to the NIC edge, where the
reference XDP program already counts it.
"""
from __future__ import annotations

import mmap
import os
import time
from typing import Optional

import numpy as np

from ._threads import sched_point

_MAGIC = b"INFWRNG1"
_VERSION = 1
_HEADER_BYTES = 4096
_SLOT_HEADER_BYTES = 64

#: record flag bits
FLAG_V4_ONLY = 1
FLAG_TCP_FLAGS = 2
FLAG_PAYLOAD = 4

DEFAULT_SLOTS = 64
DEFAULT_SLOT_PACKETS = 4096


def slot_bytes_for(max_packets: int, width: int = 7,
                   with_flags: bool = True,
                   payload_width: int = 0) -> int:
    """Slot size fitting ``max_packets`` of the widest record shape.
    ``payload_width`` > 0 reserves the per-packet payload-prefix column
    (L uint8 bytes + one int32 length)."""
    n = _SLOT_HEADER_BYTES + max_packets * width * 4
    if with_flags:
        n += max_packets * 4
    if payload_width:
        n += max_packets * (int(payload_width) + 4)
    return (n + 63) & ~63


class RingChunk:
    """One popped record: zero-copy numpy views into the mapped slot.

    The views stay valid until ``release()`` advances the consumer
    cursor — hold the chunk until the dispatch that read it has
    materialized (the daemon keeps it in the in-flight job), or copy.
    """

    __slots__ = ("wire", "tcp_flags", "payload", "payload_len",
                 "v4_only", "seq", "_ring")

    def __init__(self, ring, seq, wire, tcp_flags, v4_only,
                 payload=None, payload_len=None):
        self._ring = ring
        self.seq = seq
        self.wire = wire
        self.tcp_flags = tcp_flags
        self.v4_only = v4_only
        #: optional ring-sliced payload-prefix column (ISSUE-19):
        #: (n, L) uint8 view + (n,) int32 lengths, or None
        self.payload = payload
        self.payload_len = payload_len

    def release(self) -> None:
        """Return the slot to the producer (advance tail past seq).
        Records release in order — releasing out of order is a
        programming error the ring refuses."""
        if self._ring is not None:
            ring, self._ring = self._ring, None
            ring._advance_tail(self.seq)


class IngestRing:
    """The mapped ring.  ``create`` truncates/initializes the file
    (consumer side — it owns sizing); ``attach`` maps an existing ring
    (producer side) and validates the header."""

    def __init__(self, path: str, mm: mmap.mmap, create: bool,
                 slots: int, slot_bytes: int) -> None:
        self.path = path
        self._mm = mm
        self.slots = int(slots)
        self.slot_bytes = int(slot_bytes)
        self._u64 = np.frombuffer(mm, np.uint64, 6, 0)
        #: per-process (producer and consumer each attach their own
        #: instance): depth_hwm is the occupancy high-watermark this
        #: side observed; blocked_us is cumulative wall time reserve()
        #: spent waiting on a full ring — the PRODUCER-side backpressure
        #: signal, distinct from falling behind an open-loop schedule
        #: (tools/loadgen.py --ring splits the two in its manifest)
        #: Counter write discipline (ISSUE-18): every key is written by
        #: exactly ONE side of the SPSC pair — pushed/blocked_waits/
        #: blocked_us/depth_hwm_prod by the producer, popped/
        #: depth_hwm_cons by the consumer — because a plain-dict
        #: read-modify-write shared across both threads loses updates
        #: (the depth_hwm check-then-store raced before the split; the
        #: schedcheck test pins the fix).  counter_values() merges the
        #: two watermarks.
        self._stats = {"pushed": 0, "popped": 0, "blocked_waits": 0,
                       "depth_hwm_prod": 0, "depth_hwm_cons": 0,
                       "blocked_us": 0}
        #: consumer-side read cursor: records between tail and here are
        #: popped but not yet released (their slot views may be in
        #: flight as H2D staging buffers) — the producer only reuses
        #: slots behind TAIL, so in-flight views are never overwritten
        self._read_seq = int(self._u64[4])
        #: corrupt records skipped by pop(): their slots free only when
        #: the release protocol reaches them IN ORDER (_advance_tail
        #: drains through this set), so a poison record can never bump
        #: the tail past earlier in-flight slot views
        self._skipped: set = set()

    # -- lifecycle -----------------------------------------------------------

    @classmethod
    def create(cls, path: str, slots: int = DEFAULT_SLOTS,
               slot_packets: int = DEFAULT_SLOT_PACKETS,
               payload_width: int = 0) -> "IngestRing":
        # build the ring under a temp name and rename into place: a
        # producer's attach() (which retries until the path exists) can
        # then never map a half-initialized file — the header, cursors
        # and zeroed commit words are all durable before visibility
        slot_b = slot_bytes_for(slot_packets, payload_width=payload_width)
        total = _HEADER_BYTES + slots * slot_b
        tmp = f"{path}.tmp.{os.getpid()}"
        fd = os.open(tmp, os.O_CREAT | os.O_RDWR | os.O_TRUNC, 0o600)
        try:
            os.ftruncate(fd, total)
            mm = mmap.mmap(fd, total)
        finally:
            os.close(fd)
        hdr = np.frombuffer(mm, np.uint64, 6, 0)
        hdr[1] = (_VERSION << 32) | slots
        hdr[2] = slot_b
        hdr[3] = 0  # head
        hdr[4] = 0  # tail
        # zero every commit word so attach never reads a stale publish
        for i in range(slots):
            np.frombuffer(mm, np.uint64, 1,
                          _HEADER_BYTES + i * slot_b)[0] = 0
        mm[0:8] = _MAGIC  # magic last: a torn tmp file never validates
        mm.flush()
        os.replace(tmp, path)
        return cls(path, mm, True, slots, slot_b)

    @classmethod
    def attach(cls, path: str, timeout: float = 5.0) -> "IngestRing":
        deadline = time.monotonic() + timeout
        while True:
            try:
                fd = os.open(path, os.O_RDWR)
            except FileNotFoundError:
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.01)
                continue
            try:
                size = os.fstat(fd).st_size
                if size < _HEADER_BYTES:
                    # defensive: create() publishes atomically via
                    # rename, but a foreign/partial file should retry
                    # within the deadline instead of crashing mmap
                    raise ValueError(f"{path}: ring file too small")
                mm = mmap.mmap(fd, size)
            except ValueError:
                os.close(fd)
                if time.monotonic() > deadline:
                    raise
                time.sleep(0.01)
                continue
            os.close(fd)
            break
        if mm[0:8] != _MAGIC:
            mm.close()
            raise ValueError(f"{path}: not an infw ingest ring")
        hdr = np.frombuffer(mm, np.uint64, 6, 0)
        version = int(hdr[1]) >> 32
        slots = int(hdr[1]) & 0xFFFFFFFF
        if version != _VERSION:
            raise ValueError(
                f"{path}: ring version {version} != {_VERSION}"
            )
        return cls(path, mm, False, slots, int(hdr[2]))

    def close(self) -> None:
        try:
            self._mm.close()
        except (BufferError, ValueError):
            pass  # live numpy views pin the mapping; the OS reclaims

    # -- cursors -------------------------------------------------------------

    @property
    def head(self) -> int:
        return int(self._u64[3])

    @property
    def tail(self) -> int:
        return int(self._u64[4])

    def __len__(self) -> int:
        """Committed, unconsumed records."""
        return max(0, self.head - self.tail)

    def _slot_off(self, seq: int) -> int:
        return _HEADER_BYTES + (seq % self.slots) * self.slot_bytes

    def _advance_tail(self, seq: int) -> None:
        if int(self._u64[4]) != seq:
            raise RuntimeError(
                f"out-of-order ring release: tail={int(self._u64[4])}, "
                f"released seq={seq}"
            )
        self._u64[4] = seq + 1
        self._drain_skipped()

    def _drain_skipped(self) -> None:
        """Free poison (corrupt, skipped-by-pop) slots once the release
        order reaches them — never before, so the producer cannot
        overwrite earlier popped-but-unreleased slot views."""
        while int(self._u64[4]) in self._skipped:
            t = int(self._u64[4])
            self._skipped.discard(t)
            self._u64[4] = t + 1

    # -- producer ------------------------------------------------------------

    def max_packets(self, width: int = 7, with_flags: bool = True,
                    payload_width: int = 0) -> int:
        avail = self.slot_bytes - _SLOT_HEADER_BYTES
        per = width * 4 + (4 if with_flags else 0)
        if payload_width:
            per += int(payload_width) + 4
        return avail // per

    def reserve(self, n: int, width: int,
                with_flags: bool = False,
                payload_width: int = 0,
                timeout: Optional[float] = None):
        """Producer half 1: claim the next slot and return in-place
        views -> (wire (n, width) uint32 view, flags (n,) int32 view or
        None, token) — or, with ``payload_width`` L > 0, (wire, flags,
        payload (n, L) uint8 view, payload_len (n,) int32 view, token).
        The producer packs straight into the views (no intermediate
        chunk array), then ``commit(token)`` publishes.  Blocks while
        the ring is full (backpressure); raises TimeoutError past
        ``timeout`` seconds."""
        if n < 1 or width not in (4, 7):
            raise ValueError(f"bad record shape n={n} width={width}")
        if payload_width:
            from .kernels.wire_decode import PAYLOAD_PREFIX_WIDTHS

            if payload_width not in PAYLOAD_PREFIX_WIDTHS:
                raise ValueError(
                    f"payload prefix width {payload_width} not in "
                    f"{PAYLOAD_PREFIX_WIDTHS}"
                )
        if n > self.max_packets(width, with_flags, payload_width):
            raise ValueError(
                f"record of {n} packets exceeds the slot capacity "
                f"{self.max_packets(width, with_flags, payload_width)}"
            )
        deadline = None if timeout is None else time.monotonic() + timeout
        seq = self.head
        t_block = None
        while seq - self.tail >= self.slots:
            if t_block is None:
                t_block = time.monotonic()
            self._stats["blocked_waits"] += 1
            if deadline is not None and time.monotonic() > deadline:
                self._stats["blocked_us"] += int(
                    (time.monotonic() - t_block) * 1e6
                )
                raise TimeoutError("ingest ring full (consumer stalled)")
            time.sleep(0.0005)
        if t_block is not None:
            self._stats["blocked_us"] += int(
                (time.monotonic() - t_block) * 1e6
            )
        off = self._slot_off(seq)
        hdr32 = np.frombuffer(self._mm, np.uint32, 4, off + 8)
        flags = (FLAG_TCP_FLAGS if with_flags else 0)
        if payload_width:
            flags |= FLAG_PAYLOAD
        hdr32[0] = n
        hdr32[1] = width
        hdr32[2] = flags
        hdr32[3] = int(payload_width)
        wire = np.frombuffer(
            self._mm, np.uint32, n * width, off + _SLOT_HEADER_BYTES
        ).reshape(n, width)
        cursor = off + _SLOT_HEADER_BYTES + n * width * 4
        fl = None
        if with_flags:
            fl = np.frombuffer(self._mm, np.int32, n, cursor)
            cursor += n * 4
        if not payload_width:
            return wire, fl, (seq, off)
        pay = np.frombuffer(
            self._mm, np.uint8, n * payload_width, cursor
        ).reshape(n, payload_width)
        plen = np.frombuffer(
            self._mm, np.int32, n, cursor + n * payload_width
        )
        return wire, fl, pay, plen, (seq, off)

    def commit(self, token, v4_only: bool = False) -> int:
        """Producer half 2: publish the reserved record (commit-word
        store, then the head bump)."""
        seq, off = token
        hdr32 = np.frombuffer(self._mm, np.uint32, 4, off + 8)
        if v4_only:
            hdr32[2] |= FLAG_V4_ONLY
        np.frombuffer(self._mm, np.uint64, 1, off)[0] = seq + 1
        self._u64[3] = seq + 1
        self._stats["pushed"] += 1
        depth = len(self)
        sched_point("ring-hwm-prod")
        if depth > self._stats["depth_hwm_prod"]:
            self._stats["depth_hwm_prod"] = depth
        return seq

    def push(self, wire: np.ndarray, v4_only: bool = False,
             tcp_flags: Optional[np.ndarray] = None,
             payload: Optional[np.ndarray] = None,
             payload_len: Optional[np.ndarray] = None,
             timeout: Optional[float] = None) -> int:
        """One-call producer convenience: reserve + in-place copy +
        commit.  ``payload`` must already be bucketed to a
        PAYLOAD_PREFIX_WIDTHS column (kernels.wire_decode.
        pad_payload_prefix)."""
        n, width = wire.shape
        if payload is None:
            wv, fv, token = self.reserve(
                n, width, with_flags=tcp_flags is not None,
                timeout=timeout,
            )
        else:
            wv, fv, pv, lv, token = self.reserve(
                n, width, with_flags=tcp_flags is not None,
                payload_width=payload.shape[1], timeout=timeout,
            )
            np.copyto(pv, np.asarray(payload, np.uint8))
            np.copyto(lv, (
                np.asarray(payload_len, np.int32)
                if payload_len is not None
                else np.full(n, payload.shape[1], np.int32)
            ))
        np.copyto(wv, wire)
        if tcp_flags is not None:
            np.copyto(fv, np.asarray(tcp_flags, np.int32))
        return self.commit(token, v4_only=v4_only)

    # -- consumer ------------------------------------------------------------

    def pop(self, timeout: float = 0.0) -> Optional[RingChunk]:
        """Next committed record as zero-copy views, or None when the
        ring is empty past ``timeout``.  The slot is NOT reclaimed until
        the chunk's ``release()`` — the views double as the H2D staging
        buffer, so the producer must not overwrite them mid-copy."""
        deadline = time.monotonic() + timeout
        seq = self._read_seq
        while True:
            if self.head > seq:
                off = self._slot_off(seq)
                commit = int(np.frombuffer(self._mm, np.uint64, 1, off)[0])
                if commit == seq + 1:
                    break
            if time.monotonic() >= deadline:
                return None
            time.sleep(0.0005)
        off = self._slot_off(seq)
        hdr32 = np.frombuffer(self._mm, np.uint32, 4, off + 8)
        n, width, flags = int(hdr32[0]), int(hdr32[1]), int(hdr32[2])
        pw = int(hdr32[3]) if flags & FLAG_PAYLOAD else 0
        # the sanity bound must use the RECORD's own layout: a
        # flag-less record legally holds more packets than a flagged
        # one of the same slot size
        cap = self.max_packets(width, bool(flags & FLAG_TCP_FLAGS), pw)
        bad_pw = bool(flags & FLAG_PAYLOAD) and pw not in (64, 128)
        if width not in (4, 7) or n < 1 or bad_pw or n > cap:
            # fail closed on a torn/corrupt record: skip the READ
            # cursor only — the slot frees when the release order
            # reaches it (_drain_skipped), never by bumping the tail
            # past earlier in-flight slot views
            self._read_seq = seq + 1
            self._skipped.add(seq)
            self._drain_skipped()
            raise ValueError(
                f"corrupt ring record at seq {seq}: n={n} width={width}"
                f" payload_width={pw}"
            )
        wire = np.frombuffer(
            self._mm, np.uint32, n * width, off + _SLOT_HEADER_BYTES
        ).reshape(n, width)
        cursor = off + _SLOT_HEADER_BYTES + n * width * 4
        fl = None
        if flags & FLAG_TCP_FLAGS:
            fl = np.frombuffer(self._mm, np.int32, n, cursor)
            cursor += n * 4
        pay = plen = None
        if pw:
            pay = np.frombuffer(
                self._mm, np.uint8, n * pw, cursor
            ).reshape(n, pw)
            plen = np.frombuffer(self._mm, np.int32, n, cursor + n * pw)
        self._stats["popped"] += 1
        depth = self.head - seq
        sched_point("ring-hwm-cons")
        if depth > self._stats["depth_hwm_cons"]:
            self._stats["depth_hwm_cons"] = depth
        self._read_seq = seq + 1
        return RingChunk(self, seq, wire, fl, bool(flags & FLAG_V4_ONLY),
                         payload=pay, payload_len=plen)

    # -- observability -------------------------------------------------------

    def counter_values(self) -> dict:
        """ring_* gauges for /metrics."""
        return {
            "ring_pushed_total": self._stats["pushed"],
            "ring_popped_total": self._stats["popped"],
            "ring_blocked_waits_total": self._stats["blocked_waits"],
            "ring_blocked_us_total": self._stats["blocked_us"],
            "ring_depth": len(self),
            "ring_depth_hwm": max(self._stats["depth_hwm_prod"],
                                  self._stats["depth_hwm_cons"]),
            "ring_slots": self.slots,
        }


def push_batch_slice(ring: IngestRing, batch, lo: int, hi: int,
                     payload: Optional[np.ndarray] = None,
                     payload_len: Optional[np.ndarray] = None,
                     timeout: Optional[float] = None) -> int:
    """Producer side of one ring record: pack packets ``[lo, hi)`` of a
    PacketBatch (``pack_wire_subset``, the native fused pack) and publish
    them, with the batch's TCP flags and an optional payload-prefix
    column sliced alongside.  Blocks while the ring is full, up to
    ``timeout`` seconds.  Returns the record's sequence number."""
    wire, v4_only = batch.pack_wire_subset(np.arange(lo, hi, dtype=np.int64))
    flags = getattr(batch, "tcp_flags", None)
    return ring.push(
        wire, v4_only=v4_only,
        tcp_flags=None if flags is None else flags[lo:hi],
        payload=None if payload is None else payload[lo:hi],
        payload_len=None if payload_len is None else payload_len[lo:hi],
        timeout=timeout,
    )


def ring_path(state_dir: str) -> str:
    """The daemon's default ring location under its state dir."""
    return os.path.join(state_dir, "ingest.ring")
