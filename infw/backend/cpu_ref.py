"""ctypes bindings + Classifier wrapper for the native C++ reference
classifier (native/classifier.cpp).

The shared library is built on demand with g++ (cached by source hash) —
the framework's analogue of the reference's bpf2go build step
(/root/reference/pkg/ebpf/ingress_node_firewall_loader.go:53).
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from ..compiler import CompiledTables
from ..constants import MAX_TARGETS
from ..packets import PacketBatch
from .base import ClassifyOutput, PendingClassify, StatsAccumulator

_NATIVE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "native")
_SRC = os.path.join(_NATIVE_DIR, "classifier.cpp")

_lib_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None


def _build_library() -> str:
    """Path of the shared library built from the committed source.  The
    file name carries a hash of ``classifier.cpp``, so a library built
    from other source (a stale copy carried along with a checkout) is
    never loaded.  The build lands under a temporary name and is renamed
    into place, so concurrent processes never load a half-written file."""
    with open(_SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    lib = os.path.join(_NATIVE_DIR, "_build", f"libinfwref-{digest}.so")
    if not os.path.exists(lib):
        os.makedirs(os.path.dirname(lib), exist_ok=True)
        tmp = f"{lib}.tmp.{os.getpid()}"
        subprocess.run(
            ["g++", "-O2", "-fPIC", "-std=c++17", "-Wall", "-pthread",
             "-shared", "-o", tmp, _SRC],
            check=True,
            capture_output=True,
        )
        os.replace(tmp, lib)
    return lib


_ABI_VERSION = 4


def load_library() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is None:
            lib = ctypes.CDLL(_build_library())
            lib.infw_abi_version.restype = ctypes.c_int32
            if lib.infw_abi_version() != _ABI_VERSION:
                raise RuntimeError(
                    f"native reference library ABI {lib.infw_abi_version()}"
                    f" != {_ABI_VERSION}: classifier.cpp and cpu_ref.py "
                    "disagree"
                )
            i32p = ctypes.POINTER(ctypes.c_int32)
            u32p = ctypes.POINTER(ctypes.c_uint32)
            u8p = ctypes.POINTER(ctypes.c_uint8)
            i64p = ctypes.POINTER(ctypes.c_int64)
            lib.infw_classify.restype = None
            lib.infw_classify.argtypes = [
                ctypes.c_int32, ctypes.c_int32, u32p, i32p, u8p, i32p,
                ctypes.c_int32, i32p, i32p, u32p, u8p, i32p, i32p, i32p,
                i32p, i32p, u32p, i32p, i64p,
            ]
            lib.infw_parse_frames.restype = None
            lib.infw_parse_frames.argtypes = [
                ctypes.c_int64, u8p, i64p, u32p,
                i32p, i32p, u32p, i32p, i32p, i32p, i32p, i32p,
                ctypes.c_int32,
            ]
            lib.infw_pack_wire_subset.restype = ctypes.c_int32
            lib.infw_pack_wire_subset.argtypes = [
                ctypes.c_int64, i64p,
                i32p, i32p, i32p, u32p, i32p, i32p, i32p, i32p, i32p,
                u32p, ctypes.c_int32,
            ]
            lib.infw_encode_delta.restype = ctypes.c_int64
            lib.infw_encode_delta.argtypes = [
                ctypes.c_int64, u32p, u8p, u32p, i32p, i64p, i32p,
            ]
            _lib = lib
        return _lib


def _words_to_bytes(words: np.ndarray) -> np.ndarray:
    """(N, 4) uint32 big-endian words -> (N, 16) uint8."""
    return words.astype(">u4").view(np.uint8).reshape(words.shape[0], 16)


class CpuRefClassifier:
    """Native CPU dataplane implementing the Classifier protocol."""

    def __init__(self) -> None:
        self._lib = load_library()
        self._lock = threading.Lock()
        self._stats = StatsAccumulator()
        self._tables: Optional[CompiledTables] = None
        self._packed = None
        self._closed = False

    def load_tables(self, tables: CompiledTables, dirty_hint=None) -> None:
        # dirty_hint is a device-patch acceleration; the CPU backend's
        # full repack is already cheap, so it is accepted and ignored.
        if self._closed:
            raise RuntimeError("classifier is closed")
        T = tables.num_entries
        ent_ifindex = np.ascontiguousarray(tables.key_words[:T, 0], np.uint32)
        ent_masklen = np.ascontiguousarray(tables.mask_len[:T], np.int32)
        ent_ip = np.ascontiguousarray(
            _words_to_bytes(tables.key_words[:T, 1:5].astype(np.uint32))
        )
        rules = np.ascontiguousarray(tables.rules[:T], np.int32)
        with self._lock:
            self._tables = tables
            self._packed = (T, tables.rule_width, ent_ifindex, ent_masklen, ent_ip, rules)

    def classify(self, batch: PacketBatch, apply_stats: bool = True) -> ClassifyOutput:
        with self._lock:
            if self._packed is None:
                raise RuntimeError("no rule tables loaded")
            T, width, ent_ifindex, ent_masklen, ent_ip, rules = self._packed

        B = len(batch)
        kind = np.ascontiguousarray(batch.kind, np.int32)
        l4_ok = np.ascontiguousarray(batch.l4_ok, np.int32)
        pkt_ifindex = np.ascontiguousarray(batch.ifindex, np.uint32)
        pkt_ip = np.ascontiguousarray(_words_to_bytes(batch.ip_words.astype(np.uint32)))
        proto = np.ascontiguousarray(batch.proto, np.int32)
        dport = np.ascontiguousarray(batch.dst_port, np.int32)
        itype = np.ascontiguousarray(batch.icmp_type, np.int32)
        icode = np.ascontiguousarray(batch.icmp_code, np.int32)
        pktlen = np.ascontiguousarray(batch.pkt_len, np.int32)

        results = np.zeros(B, np.uint32)
        xdp = np.zeros(B, np.int32)
        stats = np.zeros((MAX_TARGETS, 4), np.int64)

        c = ctypes
        p = lambda a, t: a.ctypes.data_as(c.POINTER(t))
        self._lib.infw_classify(
            c.c_int32(T), c.c_int32(width),
            p(ent_ifindex, c.c_uint32), p(ent_masklen, c.c_int32),
            p(ent_ip, c.c_uint8), p(rules, c.c_int32),
            c.c_int32(B), p(kind, c.c_int32), p(l4_ok, c.c_int32),
            p(pkt_ifindex, c.c_uint32), p(pkt_ip, c.c_uint8),
            p(proto, c.c_int32), p(dport, c.c_int32), p(itype, c.c_int32),
            p(icode, c.c_int32), p(pktlen, c.c_int32),
            p(results, c.c_uint32), p(xdp, c.c_int32), p(stats, c.c_int64),
        )
        if apply_stats:
            self._stats.add(stats)
        return ClassifyOutput(results=results, xdp=xdp, stats_delta=stats)

    def classify_async(
        self, batch: PacketBatch, apply_stats: bool = True
    ) -> PendingClassify:
        """Eager: the native call is synchronous, so the handle resolves
        immediately (protocol parity with TpuClassifier)."""
        out = self.classify(batch, apply_stats=apply_stats)
        return PendingClassify(lambda: out)

    @property
    def stats(self) -> StatsAccumulator:
        return self._stats

    @property
    def tables(self) -> Optional[CompiledTables]:
        return self._tables

    def close(self) -> None:
        with self._lock:
            self._packed = None
            self._tables = None
            self._closed = True


# -- payload-tier host oracle (ISSUE-19) -------------------------------------
#
# The Aho-Corasick reference the statecheck `payload` config compares
# against.  Deliberately CONSTRUCTION-INDEPENDENT: a naive
# find-every-occurrence substring scan over each truncated prefix, so
# an automaton-construction bug (the aclink injected defect drops one
# failure-link fold) cannot be shared by both sides of the compare.


def payload_match_ref(patterns, pay, plen, prefix_len, pwords):
    """Naive multi-pattern reference -> (B, pwords) uint32 bitmaps.

    ``patterns`` is a sequence of byte strings (pattern j -> bit j),
    ``pay`` (B, L) uint8 payload-prefix columns, ``plen`` (B,) valid
    byte counts, ``prefix_len`` the matched prefix length.  Truncation
    semantics: pattern j is claimed for packet i iff an occurrence ends
    wholly within the first ``min(plen[i], prefix_len)`` bytes —
    occurrences crossing the truncation boundary claim nothing.
    """
    pay = np.asarray(pay, np.uint8)
    plen = np.asarray(plen).astype(np.int64)
    b = pay.shape[0]
    out = np.zeros((b, int(pwords)), np.uint32)
    pats = [bytes(p) for p in patterns]
    for i in range(b):
        n = int(min(plen[i], prefix_len, pay.shape[1]))
        hay = pay[i, :n].tobytes()
        for j, p in enumerate(pats):
            if p in hay:
                out[i, j // 32] |= np.uint32(1 << (j % 32))
    return out


class HostAcAutomaton:
    """A tiny, independent host Aho-Corasick (goto + failure links
    walked AT MATCH TIME, no folding) — the second reference
    implementation tests use to pin the naive scan and the compiled
    DFA against each other from a third angle."""

    def __init__(self, patterns):
        self.patterns = [bytes(p) for p in patterns]
        self.goto = [{}]
        self.out = [set()]
        for j, p in enumerate(self.patterns):
            s = 0
            for ch in p:
                if ch not in self.goto[s]:
                    self.goto.append({})
                    self.out.append(set())
                    self.goto[s][ch] = len(self.goto) - 1
                s = self.goto[s][ch]
            self.out[s].add(j)
        from collections import deque

        self.fail = [0] * len(self.goto)
        q = deque(self.goto[0].values())
        while q:
            s = q.popleft()
            for ch, t in self.goto[s].items():
                f = self.fail[s]
                while f and ch not in self.goto[f]:
                    f = self.fail[f]
                cand = self.goto[f].get(ch, 0)
                self.fail[t] = cand if cand != t else 0
                q.append(t)

    def matches(self, data: bytes):
        """Set of pattern indices with an occurrence ending in data."""
        found = set()
        s = 0
        for ch in data:
            while s and ch not in self.goto[s]:
                s = self.fail[s]
            s = self.goto[s].get(ch, 0)
            f = s
            while f:
                found |= self.out[f]
                f = self.fail[f]
            found |= self.out[s]
        return found
