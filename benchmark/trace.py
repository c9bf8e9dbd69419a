"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
per-layer metrics read: the device's busy time (the union of the
intervals in which an operation ran on it), the operations that took
most time, and the device's idle gaps, each named by the host span
that was open at its middle.

Device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per operation run.  Host spans are ``jax.profiler.TraceAnnotation``
events on the ``/host:CPU`` plane, on the same clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, Optional, Sequence, Tuple

import numpy as np

from benchmark import spans

_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    return found[-1] if found else None


def op_name(text: str) -> str:
    """An operation's name without its HLO text: ``%fusion.12 = ...``
    gives ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")


def read_events(path: str, host_names: Sequence[str]):
    """({device plane: [(op name, start_ns, dur_ns)]},
    {annotation name: [(start_ns, end_ns)]}) from one trace file."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, list] = {}
    host: Dict[str, list] = {n: [] for n in host_names}
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            chosen = [lines[OPS_LINE]] if OPS_LINE in lines else list(lines.values())
            device[plane.name] = [
                (op_name(ev.name), float(ev.start_ns), float(ev.duration_ns))
                for ln in chosen for ev in ln.events
            ]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name in host:
                        host[ev.name].append(
                            (float(ev.start_ns), float(ev.start_ns + ev.duration_ns)))
    return device, host


def reduce(device: Dict[str, list], host: Dict[str, list],
           labels: Sequence[Tuple[str, str]], idle_label: str,
           top: int = 10) -> Optional[dict]:
    """Busy seconds averaged over the device planes, the ``top`` device
    operations by total seconds, and the ``top`` longest idle gaps of the
    first device plane, each named by the first of ``labels`` (label,
    annotation name) whose span covers the gap's middle, else
    ``idle_label``.  None when no device operation ran."""
    planes = [p for p in sorted(device) if device[p]]
    if not planes:
        return None
    busy, per_op = [], {}
    for p in planes:
        ev = device[p]
        st = np.array([e[1] for e in ev])
        du = np.array([e[2] for e in ev])
        busy.append(spans.covered(spans.union(st, st + du), -np.inf, np.inf))
        for name, _s, d in ev:
            per_op[name] = per_op.get(name, 0.0) + d
    ev = device[planes[0]]
    st = np.array([e[1] for e in ev])
    g = spans.gaps(spans.union(st, st + np.array([e[2] for e in ev])))
    host_u = [(label, spans.union(*zip(*host[name])) if host.get(name) else np.zeros((0, 2)))
              for label, name in labels]
    named = []
    for lo, hi in g:
        mid = 0.5 * (lo + hi)
        label = idle_label
        for lab, u in host_u:
            if len(u) and np.any((u[:, 0] <= mid) & (mid < u[:, 1])):
                label = lab
                break
        named.append((label, (hi - lo) * 1e-9))
    named.sort(key=lambda x: -x[1])
    idle_by = {}
    for label, s in named:
        idle_by[label] = idle_by.get(label, 0.0) + s
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    return {
        "busy_s": float(np.mean(busy)) * 1e-9,
        "device_ops": [[n, d * 1e-9] for n, d in ops],
        "idle_gaps": [[n, s] for n, s in named[:top]],
        "idle_by_host_span": idle_by,
    }
