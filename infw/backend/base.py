"""Classifier backend interface.

The runtime contract every dataplane backend implements.  Mirrors the role
of the loaded XDP program + its maps
(/root/reference/pkg/ebpf/ingress_node_firewall_loader.go:43-50): rules are
loaded idempotently, packets are classified, statistics accumulate until
reset.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional, Protocol

import numpy as np

from ..compiler import CompiledTables
from ..constants import MAX_TARGETS
from ..packets import PacketBatch


@dataclass
class ClassifyOutput:
    """Per-batch outputs: packed u32 results, XDP verdicts, and the batch's
    statistics increment (MAX_TARGETS, 4) int64 [allow_pkts, allow_bytes,
    deny_pkts, deny_bytes]."""

    results: np.ndarray
    xdp: np.ndarray
    stats_delta: np.ndarray


class PendingClassify:
    """Handle to an in-flight classification: the device work was dispatched
    but the results are not yet materialized on the host.

    The TPU analogue of the XDP program running inline on the NIC queue: a
    caller streaming batches keeps several in flight so H2D transfer, kernel
    and D2H readback of consecutive batches overlap.  `result()` blocks
    until this batch's outputs are host-resident and applies the stats
    increment exactly once.

    ``readback``, where a backend gives one, blocks until the device
    outputs are host-resident and does nothing else; ``wait()`` calls it,
    so a tracer can time the wait on the device apart from the host work
    that ``result()`` does after it."""

    def __init__(self, materialize, readback=None) -> None:
        self._materialize = materialize
        self._readback = readback
        self._out: Optional[ClassifyOutput] = None

    def wait(self) -> None:
        if self._out is None and self._readback is not None:
            self._readback()

    def result(self) -> ClassifyOutput:
        if self._out is None:
            self._out = self._materialize()
            self._materialize = self._readback = None  # drop device refs
        return self._out


class StatsAccumulator:
    """Host-side equivalent of the per-CPU statistics map
    (bpf/ingress_node_firewall_kernel.c:36-41): accumulates per-ruleId
    counters until the dataplane is reset; read by the metrics poller."""

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._stats = np.zeros((MAX_TARGETS, 4), np.int64)

    def add(self, delta: np.ndarray) -> None:
        with self._lock:
            self._stats += delta

    def snapshot(self) -> np.ndarray:
        with self._lock:
            return self._stats.copy()

    def reset(self) -> None:
        with self._lock:
            self._stats[:] = 0


class Classifier(Protocol):
    """One-per-node dataplane program."""

    def load_tables(self, tables: CompiledTables, dirty_hint=None) -> None:
        """Swap in a newly compiled ruleset (idempotent, atomic).

        ``dirty_hint`` (IncrementalTables.peek_dirty() or None) is an
        optional superset of the rows changed since the previous load —
        device backends use it to patch in place instead of re-uploading;
        others ignore it."""
        ...

    def classify(self, batch: PacketBatch, apply_stats: bool = True) -> ClassifyOutput:
        ...

    def classify_async(
        self, batch: PacketBatch, apply_stats: bool = True
    ) -> PendingClassify:
        """Dispatch without blocking; materialize via .result().  Sync
        backends may run eagerly and return an already-resolved handle.
        With ``apply_stats=False`` the accumulator is left untouched and
        the caller applies ``stats_delta`` itself (exactly-once semantics
        across retries)."""
        ...

    @property
    def stats(self) -> StatsAccumulator:
        ...

    @property
    def tables(self) -> Optional[CompiledTables]:
        ...

    def close(self) -> None:
        ...
