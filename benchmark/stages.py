"""The program's own spans and stages in a profiler trace: device seconds
by stage of the resident step, and the device's idle time split by the
program span open across it.

A daemon run with ``--trace`` names each phase of its loop and each step
of a ring record as an ``infw.*`` host span (``SpanTracer.span``), on the
clock of the device ops.  The ops of its resident step carry the stage
of their ``jax.named_scope`` in their HLO metadata: the profiler records
every module it ran with that module's optimized HLO (the ``Hlo Proto``
of the ``/host:metadata`` plane, keyed by the name the ``XLA Modules``
line shows), and the program's ``jaxpath.hlo_stage_map`` maps that HLO's
instructions, which are the trace's op names, to stages.  A program
without the spans or the stages gives no reading here: every metric that
reads this module then returns None.
"""
from __future__ import annotations

import json
import sys
from typing import Dict, Optional

import numpy as np

from benchmark import spans, trace

#: the resident executables, by the base name of their module
RESIDENT_MODULES = ("jit_resident_step", "jit_resident_superbatch")
MODULES_LINE = "XLA Modules"
METADATA_PLANE = "/host:metadata"
HLO_PROTO_STAT = "Hlo Proto"
#: the program's spans: the loop's phases and each ring record's steps
SPAN_PREFIX = "infw."
WAIT_SPAN = "infw.loop.wait"
READBACK_SPAN, FINALIZE_SPAN = "infw.ring.readback", "infw.ring.finalize"
UNMAPPED, UNSPANNED = "unmapped", "unspanned"


# -- the HLO the profiler recorded ----------------------------------------------


def _fields(buf: bytes):
    """(field number, value) of one protobuf message: varints as ints,
    length-delimited fields as bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind == 1:
            value, i = buf[i:i + 8], i + 8
        elif kind == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"protobuf wire type {kind}")
        yield key >> 3, value


def _varint(buf: bytes, i: int):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return value, i


def base_name(module: str) -> str:
    """``jit_resident_step(1234)`` gives ``jit_resident_step``."""
    return module.split("(", 1)[0]


def hlo_modules(path: str, wanted=RESIDENT_MODULES) -> Dict[str, str]:
    """{module name as the trace shows it: optimized HLO text with
    metadata} for the modules of the trace file whose base name is in
    ``wanted``.  The file is an XSpace: planes (1), each with a name (2)
    and a map of event metadata (4: id -> {name 2, stats 5}) and of stat
    metadata (5: id -> {name 2}); the metadata plane's module entries
    hold an HloProto whose first field is the HloModuleProto."""
    from jax._src.lib import xla_client

    with open(path, "rb") as f:
        data = f.read()
    found = {}
    for num, plane in _fields(data):
        if num != 1:
            continue
        fields = list(_fields(plane))
        if b"".join(v for k, v in fields if k == 2).decode() != METADATA_PLANE:
            continue
        stat_names = {}
        for k, v in fields:
            if k == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = meta.get(2, b"").decode()
        for k, v in fields:
            if k != 4:
                continue
            meta = list(_fields(dict(_fields(v)).get(2, b"")))
            name = b"".join(x for j, x in meta if j == 2).decode()
            if base_name(name) not in wanted:
                continue
            for j, stat in meta:
                st = dict(_fields(stat)) if j == 5 else {}
                if stat_names.get(st.get(1)) == HLO_PROTO_STAT and 6 in st:
                    proto = dict(_fields(st[6])).get(1)
                    module = xla_client._xla.HloModule \
                        .from_serialized_hlo_module_proto(proto)
                    opts = xla_client._xla.HloPrintOptions()
                    opts.print_metadata = True
                    found[name] = module.to_string(opts)
    return found


def stage_maps(path: str) -> Dict[str, dict]:
    """{module name: {op name: stage}} by the program's own map, or {}
    when the program has none."""
    try:
        from infw.kernels.jaxpath import hlo_stage_map
    except ImportError:
        return {}
    return {m: hlo_stage_map(text) for m, text in hlo_modules(path).items()}


# -- events -------------------------------------------------------------------------


def read_events(path: str):
    """(device ops [(name, start_ns, dur_ns)], modules [(name, start_ns,
    end_ns)] of the first device plane, program spans [(name, start_ns,
    end_ns, seq or None)] of the host planes, the trace's length in ns)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, prog, length = [], [], [], None
    device_done = False
    for plane in pd.planes:
        if plane.name == "Task Environment":
            st = dict(plane.stats)
            if "profile_start_time" in st and "profile_stop_time" in st:
                length = float(st["profile_stop_time"] - st["profile_start_time"])
        elif trace._DEVICE_PLANE.match(plane.name) and not device_done:
            for ln in plane.lines:
                if ln.name == trace.OPS_LINE:
                    ops = [(trace.op_name(ev.name), float(ev.start_ns),
                            float(ev.duration_ns)) for ev in ln.events]
                elif ln.name == MODULES_LINE:
                    modules = [(ev.name, float(ev.start_ns),
                                float(ev.start_ns + ev.duration_ns))
                               for ev in ln.events]
            device_done = bool(ops)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for ev in ln.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        seq = dict(ev.stats).get("seq")
                        prog.append((ev.name, float(ev.start_ns),
                                     float(ev.start_ns + ev.duration_ns),
                                     None if seq is None else int(seq)))
    prog.sort(key=lambda s: s[1])
    return ops, modules, prog, length


# -- reductions ---------------------------------------------------------------------


def stage_seconds(ops, modules, maps: Dict[str, dict]) -> Optional[dict]:
    """Device seconds by stage over the ops that ran inside a resident
    module (joined by time to the ``XLA Modules`` line, then by name to
    the module's stage map; an op the map lacks is ``unmapped``), with
    the ops' summed and united seconds, the whole device's united
    seconds, and the ten longest ops with their stage.  None when no
    resident module ran or none has a map."""
    mods = [m for m in modules if base_name(m[0]) in RESIDENT_MODULES
            and m[0] in maps]
    if not mods or not ops:
        return None
    mods.sort(key=lambda m: m[1])
    m_start = np.array([m[1] for m in mods])
    m_end = np.array([m[2] for m in mods])
    by_stage: Dict[str, float] = {}
    by_op: Dict[tuple, float] = {}
    inside = []
    for name, start, dur in ops:
        i = int(np.searchsorted(m_start, start, side="right")) - 1
        if i < 0 or start >= m_end[i]:
            continue
        stage = maps[mods[i][0]].get(name, UNMAPPED)
        by_stage[stage] = by_stage.get(stage, 0.0) + dur
        by_op[(name, stage)] = by_op.get((name, stage), 0.0) + dur
        inside.append((start, start + dur))
    if not inside:
        return None
    st = np.array([o[1] for o in ops])
    en = st + np.array([o[2] for o in ops])
    a = np.array(inside)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {
        "stage_s": {k: v * 1e-9 for k, v in sorted(by_stage.items())},
        "resident_op_s": sum(by_stage.values()) * 1e-9,
        "resident_busy_s": spans.covered(spans.union(a[:, 0], a[:, 1]),
                                         -np.inf, np.inf) * 1e-9,
        "device_busy_s": spans.covered(spans.union(st, en), -np.inf,
                                       np.inf) * 1e-9,
        "top_ops": [[n, s, d * 1e-9] for (n, s), d in top],
    }


def idle_by_program_span(ops, prog) -> Optional[dict]:
    """Seconds of the device's idle gaps (between consecutive ops) by the
    innermost program span open at each gap's middle, ``unspanned``
    where none is open.  None when no program span was recorded."""
    if len(ops) < 2 or not prog:
        return None
    st = np.array([o[1] for o in ops])
    gaps = spans.gaps(spans.union(st, st + np.array([o[2] for o in ops])))
    p_start = np.array([p[1] for p in prog])
    p_end = np.array([p[2] for p in prog])
    out: Dict[str, float] = {}
    for lo, hi in gaps:
        mid = 0.5 * (lo + hi)
        # the innermost open span is the open one that started last
        open_ = np.nonzero((p_start <= mid) & (mid < p_end))[0]
        label = prog[open_[np.argmax(p_start[open_])]][0] if len(open_) else UNSPANNED
        out[label] = out.get(label, 0.0) + (hi - lo) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def whole_turns(prog):
    """[start, end) of the whole loop turns the trace holds: from the
    first recorded ``infw.loop.wait`` span's start to the last's (a span
    open when the trace starts or stops is not recorded)."""
    waits = [p for p in prog if p[0] == WAIT_SPAN]
    if len(waits) < 2:
        return None
    return waits[0][1], waits[-1][1]


def reduce_file(path: str) -> Optional[dict]:
    """Both reductions of one trace file, and what the readers need;
    None when the program recorded neither spans nor stages."""
    ops, modules, prog, length = read_events(path)
    maps = stage_maps(path)
    stages = stage_seconds(ops, modules, maps)
    idle = idle_by_program_span(ops, prog)
    if stages is None and not prog:
        return None
    return {"stages": stages, "idle_by_program_span": idle, "spans": prog,
            "trace_ns": length}


def reduce_run(run) -> Optional[dict]:
    """``reduce_file`` of a traced run's trace, once per run; the first
    call prints the reductions on stderr as the ``stages`` line."""
    if not getattr(run, "traced", False) or not getattr(run, "trace_dir", None):
        return None
    if not hasattr(run, "program_stages"):
        path = trace.find_xplane(run.trace_dir)
        red = reduce_file(path) if path else None
        run.program_stages = red
        if red is not None:
            print("stages " + json.dumps({
                k: v for k, v in red.items() if k != "spans"}),
                file=sys.stderr)
    return run.program_stages


def delivered_mpkt(run) -> Optional[float]:
    """Millions of packets delivered in the profiled stretch (the base
    of ``device_ms_per_mpkt.sat``)."""
    d = run.recs.deliver
    inside = (d >= run.prof[0]) & (d < run.prof[1])
    packets = float(np.sum(run.recs.packets[inside]))
    return packets / 1e6 if packets > 0 else None


def stage_ms_per_mpkt(run, names) -> Optional[float]:
    """Device ms in the named stages per million packets delivered in
    the profiled stretch; None where no stage is named."""
    red = reduce_run(run)
    if red is None or red["stages"] is None:
        return None
    s = red["stages"]["stage_s"]
    if set(s) <= {"other", UNMAPPED}:
        return None  # the program names none of its stages
    mpkt = delivered_mpkt(run)
    if mpkt is None:
        return None
    return sum(s.get(k, 0.0) for k in names) * 1e3 / mpkt


def span_us_per_record(run, name: str) -> Optional[float]:
    """Mean microseconds of the ``name`` spans of the records whose
    ``infw.ring.readback`` span the trace holds."""
    red = reduce_run(run)
    if red is None:
        return None
    seqs = {p[3] for p in red["spans"] if p[0] == READBACK_SPAN}
    durs = [p[2] - p[1] for p in red["spans"] if p[0] == name and p[3] in seqs]
    if not durs:
        return None
    return float(np.mean(durs)) * 1e-3
