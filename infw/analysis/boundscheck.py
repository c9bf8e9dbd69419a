"""Static bounds/overflow verifier over every registered kernel
entrypoint's jaxpr — the TPU dataplane's analogue of the eBPF
verifier's load-time memory-safety gate.

XLA never faults on a bad index: ``gather``/``scatter`` silently clamp
(or drop) out-of-bounds accesses and narrow integer arithmetic
silently wraps, so an index or overflow bug in a kernel produces
WRONG VERDICTS, not crashes — the one failure class none of the
runtime passes (rules/jaxcheck/statecheck/lockcheck) can see.  This
module closes the gap with an abstract interpretation of each
entrypoint's jaxpr under an interval + known-bits domain:

- every array abstracts to ONE value interval ``[lo, hi]`` over all
  its elements (plus an optional maybe-bits mask constraining the
  non-negative values — what survives ``x & mask`` decodes like the
  spliced page table's ``page | bank << 30`` rows);
- input intervals seed from the DECLARED table contracts
  (``contracts.TENSOR_BOUNDS`` — the same resolvers statecheck
  enforces on every install), while wire/payload/tenant operands stay
  dtype-top: the pass proves safety for ANY attacker-controlled input
  given contract-valid tables;
- transfer functions propagate through the integer fragment
  (add/mul/shift/bitops/select/cumsum/reduce/dot/...), loop-carried
  values reach a fixpoint by join + widening, and ``select_n`` applies
  predicate refinement (a ``where(x >= 0, f(x), c)`` re-evaluates
  ``f`` with ``x`` restricted to the true/false half);
- at every ``gather``/``scatter``/``dynamic_slice`` eqn the index
  interval must fit the operand extent.  An index that is neither
  PROVEN in-range nor GUARDED (the repo's explicit discipline: an
  ``(i >= 0) & (i < extent)`` test in the same program, with the
  gather result masked downstream) is a finding — XLA's clamp could
  engage with no test anywhere to notice;
- dtype-aware wrap detection flags arithmetic whose result interval
  provably escapes the dtype, with an attribution policy that skips
  pure accumulation of already-full-range values (u32 stats counters)
  but keeps multiplicative mixing (FNV-1a) and narrowing restages
  (the int8 defect class).  Intentional wrap is allowed only through
  the justification-required suppression file
  (``boundscheck_suppressions.txt`` — same format as lockcheck's).

Findings on entrypoints with a registered witness harness are
concretized: the harness materializes a boundary state/batch from the
interval frontier and replays production dispatch vs the CPU oracle,
so a reported hazard ships with an executable divergence — or, when
the replay stays bit-identical, is downgraded to info severity
(reported but non-fatal, the proven-unreachable residue).

Pallas kernel bodies are opaque to this pass (counted per entry as
``pallas_opaque``): their VMEM/block-spec safety is jaxcheck's
domain; boundscheck covers the XLA surface around them.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import contracts
from . import _suppress

__all__ = [
    "AbsVal", "Finding", "EntryReport", "audit_entry", "audit_all",
    "summarize", "interp_closed_jaxpr", "seed_absvals",
    "default_suppressions_path", "WITNESS_HARNESSES",
]

#: loop-carry joins before a still-growing component widens to
#: dtype-top (termination bound for the while/scan fixpoint)
WIDEN_AFTER = 3

#: depth bound for select_n predicate refinement re-evaluation
REFINE_DEPTH = 8

_INF = float("inf")


def default_suppressions_path() -> str:
    return _suppress.sibling_path("boundscheck_suppressions.txt")


# -- the abstract domain -----------------------------------------------------


def _dtype_range(dt) -> Tuple[int, int]:
    dt = np.dtype(dt)
    if dt == np.bool_:
        return (0, 1)
    ii = np.iinfo(dt)
    return (int(ii.min), int(ii.max))


def _bits_for(lo: int, hi: int) -> Optional[int]:
    """Maybe-bits implied by an interval: meaningful only for
    non-negative ranges (negative values are unconstrained by
    convention)."""
    if lo < 0 or hi < 0:
        return None
    m = 0
    while m < hi:
        m = (m << 1) | 1
    return m


class AbsVal:
    """Abstract value of one jaxpr array: a value interval over ALL
    elements, an optional maybe-bits mask for the non-negative
    elements, comparison provenance (for select_n refinement and the
    guarded-gather recognizer), and a shallow expression node (for
    refinement re-evaluation).

    ``tested_ub``/``tested_lb`` are SHARED (by reference) through
    value-narrowing ops (clip/max/min/convert), so a range test
    recorded on ``win`` is visible on ``clip(win, 0)`` regardless of
    program order."""

    __slots__ = ("dtype", "lo", "hi", "bits", "tested_ub", "tested_lb",
                 "cmps", "expr", "is_float", "const")

    def __init__(self, dtype, lo=None, hi=None, bits=None,
                 is_float=False, const=None,
                 tested_ub=None, tested_lb=None):
        self.dtype = np.dtype(dtype)
        self.is_float = is_float or self.dtype.kind == "f"
        if self.is_float:
            self.lo, self.hi = -_INF, _INF
            self.bits = None
        else:
            dlo, dhi = _dtype_range(self.dtype)
            self.lo = dlo if lo is None else max(int(lo), dlo)
            self.hi = dhi if hi is None else min(int(hi), dhi)
            if self.lo > self.hi:           # infeasible — keep sane
                self.lo, self.hi = dlo, dhi
            ib = _bits_for(self.lo, self.hi)
            self.bits = ib if bits is None else (
                bits if ib is None else (bits & ib))
            if self.bits is not None:       # bits imply a hi
                self.hi = min(self.hi, self.bits)
        self.tested_ub = set() if tested_ub is None else tested_ub
        self.tested_lb = set() if tested_lb is None else tested_lb
        self.cmps = None    # comparison provenance (bool preds)
        self.expr = None    # (prim_name, operand AbsVals, params)
        self.const = const  # python int when a known scalar constant

    # -- queries --

    def informative(self) -> bool:
        if self.is_float:
            return False
        return (self.lo, self.hi) != _dtype_range(self.dtype)

    def key(self):
        return (self.lo, self.hi, self.bits)

    def __repr__(self):
        b = f" bits={self.bits:#x}" if self.bits is not None else ""
        return f"<[{self.lo}, {self.hi}]{b} {self.dtype}>"


def _top(dtype) -> AbsVal:
    return AbsVal(dtype)


def _eff_bits(a: AbsVal) -> Optional[int]:
    """Bits constraining a value's NON-NEGATIVE elements: the declared
    mask if present, else interval-implied; an all-negative value has
    an empty non-negative part (mask 0)."""
    if a.bits is not None:
        return a.bits
    if a.hi < 0:
        return 0
    return _bits_for(max(a.lo, 0), a.hi)


def _join(a: AbsVal, b: AbsVal, dtype=None) -> AbsVal:
    dtype = dtype or a.dtype
    if a.is_float or b.is_float:
        return AbsVal(dtype, is_float=True)
    ba, bb = _eff_bits(a), _eff_bits(b)
    bits = (ba | bb) if (ba is not None and bb is not None) else None
    out = AbsVal(dtype, min(a.lo, b.lo), max(a.hi, b.hi), bits=bits)
    out.tested_ub = a.tested_ub & b.tested_ub
    out.tested_lb = a.tested_lb & b.tested_lb
    return out


def _narrowed(src: AbsVal, dtype, lo, hi, bits=None) -> AbsVal:
    """A derived value that can only be <= the source (clip/max/min/
    value-preserving convert): shares the source's tested sets so
    guard tests flow through the derivation."""
    out = AbsVal(dtype, lo, hi, bits=bits,
                 tested_ub=src.tested_ub, tested_lb=src.tested_lb)
    return out


# -- findings / reports ------------------------------------------------------


@dataclass
class Finding:
    check: str           # oob-gather | oob-scatter | oob-dynamic-slice
    #                    # | int-wrap | audit-info
    severity: str        # error | warning | info
    entry: str
    subject: str         # suppression-matchable: entry:prim:tag
    message: str
    eqn: str = ""
    region: str = ""     # e.g. "jit/scan.body"
    interval: str = ""
    extent: str = ""
    count: int = 1       # identical findings folded per entry
    witness: Optional[dict] = None
    suppressed_by: Optional[str] = None

    def to_dict(self) -> dict:
        d = {k: getattr(self, k) for k in (
            "check", "severity", "entry", "subject", "message", "eqn",
            "region", "interval", "extent", "count")}
        if self.witness is not None:
            d["witness"] = self.witness
        if self.suppressed_by is not None:
            d["suppressed_by"] = self.suppressed_by
        return d


@dataclass
class EntryReport:
    entry: str
    kind: str
    findings: List[Finding] = field(default_factory=list)
    suppressed: List[Finding] = field(default_factory=list)
    stats: Dict[str, int] = field(default_factory=dict)
    error: Optional[str] = None

    @property
    def errors(self) -> int:
        return sum(1 for f in self.findings if f.severity == "error")

    def to_dict(self) -> dict:
        return {
            "entry": self.entry, "kind": self.kind,
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [f.to_dict() for f in self.suppressed],
            "stats": dict(self.stats), "error": self.error,
            "errors": self.errors,
        }


def _src_of(eqn) -> str:
    """The user-most infw source frame of an eqn (``file.py:line``), so
    findings point at the kernel line, not the jax internals."""
    try:
        frames = eqn.source_info.traceback.frames
    except Exception:
        return ""
    for fr in frames:
        fn = (getattr(fr, "file_name", "") or "").replace("\\", "/")
        if "/infw/" in fn and "/infw/analysis/" not in fn:
            return f"{fn.rsplit('/', 1)[-1]}:{fr.line_num}"
    return ""


def _eqn_slice(eqn, limit: int = 400) -> str:
    try:
        s = str(eqn)
    except Exception:
        s = f"<{eqn.primitive.name}>"
    s = " ".join(s.split())
    if len(s) > limit:
        s = s[: limit - 3] + "..."
    src = _src_of(eqn)
    return f"{s}  @ {src}" if src else s


class _Ctx:
    """Per-audit interpretation context: finding sink, stats, and the
    report/quiet switch (fixpoint warm-up passes run quiet; only the
    final stabilized pass reports)."""

    def __init__(self, entry: str):
        self.entry = entry
        self.report = True
        self.findings: Dict[Tuple[str, str, str], Finding] = {}
        self.stats = {
            "eqns": 0, "index_sites": 0, "proved": 0, "guarded": 0,
            "pallas_opaque": 0, "unknown_prims": 0,
        }

    def finding(self, check, severity, subject, message, eqn="",
                region="", interval="", extent=""):
        if not self.report:
            return
        key = (check, subject, region)
        if key in self.findings:
            self.findings[key].count += 1
            return
        self.findings[key] = Finding(
            check=check, severity=severity, entry=self.entry,
            subject=subject, message=message, eqn=eqn, region=region,
            interval=interval, extent=extent)


# -- transfer functions ------------------------------------------------------


def _const_of(av: AbsVal) -> Optional[int]:
    if av.is_float:
        return None
    if av.const is not None:
        return av.const
    if av.lo == av.hi:
        return av.lo
    return None


def _wrap_result(ctx: _Ctx, prim: str, out_dtype, lo, hi,
                 operands: Sequence[AbsVal], eqn, region: str,
                 accumulation: bool = False) -> AbsVal:
    """Clamp an unbounded arithmetic result into its dtype; if the
    true range escapes the dtype the values WRAP, so the sound result
    is dtype-top — and it is an int-wrap finding when EVERY variable
    operand was range-bounded: the author had provably-in-range values
    and the combination still escapes (the int8-restage defect class).
    An operand already spanning the full dtype ring means the code
    works in modular arithmetic on purpose (u32 counters, hash state)
    — the wrap is the semantics, not a bug, so no finding."""
    dt = np.dtype(out_dtype)
    if dt.kind not in "iu" or (lo is None):
        return AbsVal(out_dtype, is_float=dt.kind == "f")
    dlo, dhi = _dtype_range(dt)
    if lo >= dlo and hi <= dhi:
        return AbsVal(out_dtype, lo, hi)
    vars_ = [o for o in operands
             if not o.is_float and _const_of(o) is None]
    silent = not vars_ or not all(o.informative() for o in vars_)
    if not silent:
        consts = [c for c in (_const_of(o) for o in operands)
                  if c is not None]
        tag = f"{dt.name}:c{consts[0]}" if consts else dt.name
        src = _src_of(eqn)
        subject = f"{ctx.entry}:{prim}:{tag}"
        if src:
            subject += f"@{src}"
        ctx.finding(
            "int-wrap", "error",
            subject,
            f"{prim} result [{lo}, {hi}] escapes {dt.name} "
            f"[{dlo}, {dhi}] — silent modular wrap",
            eqn=_eqn_slice(eqn), region=region,
            interval=f"[{lo}, {hi}]", extent=f"{dt.name}")
    return _top(out_dtype)


def _shift_amounts(s: AbsVal, width: int) -> Optional[Tuple[int, int]]:
    if s.is_float:
        return None
    lo, hi = max(s.lo, 0), min(s.hi, width - 1)
    if s.lo < 0 or s.hi >= width:
        # may be an out-of-width shift (undefined in XLA) — stay
        # conservative, no finding (future work)
        return None
    return (lo, hi)


def _arith(ctx, prim, eqn, region, ins: List[AbsVal], out_aval) -> AbsVal:
    """Binary/unary integer arithmetic with corner-combination
    interval evaluation and wrap checking."""
    name = prim
    dt = out_aval.dtype
    a = ins[0]
    b = ins[1] if len(ins) > 1 else None
    if a.is_float or (b is not None and b.is_float):
        return AbsVal(dt, is_float=True)
    if name == "add":
        return _wrap_result(ctx, name, dt, a.lo + b.lo, a.hi + b.hi,
                            ins, eqn, region, accumulation=True)
    if name == "sub":
        return _wrap_result(ctx, name, dt, a.lo - b.hi, a.hi - b.lo,
                            ins, eqn, region, accumulation=True)
    if name == "mul":
        cs = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        return _wrap_result(ctx, name, dt, min(cs), max(cs), ins, eqn,
                            region)
    if name == "max":
        out = _narrowed(a, dt, max(a.lo, b.lo), max(a.hi, b.hi))
        # an `x < t` test survives max(x, y) only when the other side
        # is provably below t.  For the canonical clip-lower idiom
        # (max with a non-positive constant, either operand order)
        # share the variable side's set BY REFERENCE so tests recorded
        # later in program order stay visible.
        if _const_of(a) is not None and a.hi <= 0:
            out.tested_ub = b.tested_ub
        elif _const_of(b) is not None and b.hi <= 0:
            out.tested_ub = a.tested_ub
        else:
            out.tested_ub = ({t for t in a.tested_ub if b.hi < t}
                             | {t for t in b.tested_ub if a.hi < t})
        return out
    if name == "min":
        out = _narrowed(a, dt, min(a.lo, b.lo), min(a.hi, b.hi))
        out.tested_ub = a.tested_ub | b.tested_ub
        return out
    if name == "div":
        if b.lo >= 1:
            cs = [a.lo // b.lo, a.lo // b.hi, a.hi // b.lo, a.hi // b.hi]
            # python floor-div vs XLA trunc-div differ on negatives —
            # pad the hull by one step to stay sound
            return AbsVal(dt, min(cs) - 1 if a.lo < 0 else min(cs),
                          max(cs) + 1 if a.lo < 0 else max(cs))
        return _top(dt)
    if name == "rem":
        d = _const_of(b)
        if d is not None and d > 0:
            if a.lo >= 0:
                return AbsVal(dt, 0, min(d - 1, a.hi))
            return AbsVal(dt, -(d - 1), d - 1)
        return _top(dt)
    if name == "and":
        # x & y: if either side is known non-negative the result is in
        # [0, that side's hi]; bits intersect (a possibly-negative
        # side contributes all-ones)
        amask = _eff_bits(a) if a.lo >= 0 else -1
        bmask = _eff_bits(b) if b.lo >= 0 else -1
        mask = amask & bmask
        if mask >= 0:
            out = AbsVal(dt, 0, mask, bits=mask)
            return out
        return _top(dt)
    if name == "or" or name == "xor":
        if a.lo >= 0 and b.lo >= 0:
            m = _eff_bits(a) | _eff_bits(b)
            return AbsVal(dt, 0, m, bits=m)
        return _top(dt)
    if name == "not":
        return _top(dt)
    if name == "neg":
        return _wrap_result(ctx, name, dt, -a.hi, -a.lo, ins, eqn, region)
    if name == "shift_left":
        sh = _shift_amounts(b, np.dtype(dt).itemsize * 8)
        if sh is None or a.lo < 0:
            return _top(dt)
        return _wrap_result(ctx, name, dt, a.lo << sh[0], a.hi << sh[1],
                            ins, eqn, region)
    if name == "shift_right_logical":
        width = np.dtype(dt).itemsize * 8
        sh = _shift_amounts(b, width)
        if sh is None:
            return _top(dt)
        if a.lo >= 0:
            return AbsVal(dt, a.lo >> sh[1], a.hi >> sh[0])
        # negative operands reinterpret as unsigned before shifting
        umax = (1 << width) - 1
        return AbsVal(dt, 0 if sh[0] > 0 else _dtype_range(dt)[0],
                      umax >> sh[0] if sh[0] > 0 else _dtype_range(dt)[1])
    if name == "shift_right_arithmetic":
        sh = _shift_amounts(b, np.dtype(dt).itemsize * 8)
        if sh is None:
            return _top(dt)
        cs = [a.lo >> sh[0], a.lo >> sh[1], a.hi >> sh[0], a.hi >> sh[1]]
        return AbsVal(dt, min(cs), max(cs))
    if name == "abs":
        return AbsVal(dt, 0 if a.lo <= 0 <= a.hi else min(abs(a.lo),
                      abs(a.hi)), max(abs(a.lo), abs(a.hi)))
    if name in ("population_count", "clz"):
        return AbsVal(dt, 0, np.dtype(a.dtype).itemsize * 8)
    return _top(dt)


_CMP_PRIMS = {"lt", "le", "gt", "ge", "eq", "ne"}


def _record_cmp(prim: str, a: AbsVal, b: AbsVal, out: AbsVal):
    """Comparison provenance: derive interval facts about the
    variable side under the true/false outcome, record guard tests.

    cmps entries are (target, t_lo, t_hi, f_lo, f_hi): target in
    [t_lo, t_hi] when the predicate is TRUE, [f_lo, f_hi] when FALSE
    (None bound = no information)."""
    ca, cb = _const_of(a), _const_of(b)
    facts = []
    if cb is not None and ca is None and not a.is_float:
        x, c = a, cb
        if prim == "lt":     # x < c
            facts = [(x, None, c - 1, c, None)]
        elif prim == "le":
            facts = [(x, None, c, c + 1, None)]
        elif prim == "ge":   # x >= c
            facts = [(x, c, None, None, c - 1)]
        elif prim == "gt":
            facts = [(x, c + 1, None, None, c)]
        if prim in ("lt", "le"):
            x.tested_ub.add(c if prim == "lt" else c + 1)
        if prim in ("ge", "gt") and c >= 0:
            x.tested_lb.add(c)
    elif ca is not None and cb is None and not b.is_float:
        x, c = b, ca
        if prim == "gt":     # c > x  ==  x < c
            facts = [(x, None, c - 1, c, None)]
        elif prim == "ge":
            facts = [(x, None, c, c + 1, None)]
        elif prim == "lt":   # c < x  ==  x > c
            facts = [(x, c + 1, None, None, c)]
        elif prim == "le":
            facts = [(x, c, None, None, c - 1)]
        if prim in ("gt", "ge"):
            x.tested_ub.add(c if prim == "gt" else c + 1)
        if prim in ("lt", "le") and c >= -1:
            x.tested_lb.add(max(c, 0))
    if facts:
        out.cmps = facts


def _refine_eval(node: AbsVal, refined: Dict[int, AbsVal],
                 depth: int = REFINE_DEPTH) -> AbsVal:
    """Re-evaluate a value's shallow expression tree with some leaves
    replaced by refined copies (select_n predicate refinement).
    Returns the node unchanged when nothing below it refines."""
    if id(node) in refined:
        return refined[id(node)]
    if depth <= 0 or node.expr is None:
        return node
    prim, children, params = node.expr
    new = [_refine_eval(c, refined, depth - 1) for c in children]
    if all(n is c for n, c in zip(new, children)):
        return node
    out = _apply_pure(prim, new, node.dtype, params)
    return out if out is not None else node


def _apply_pure(prim: str, ins: List[AbsVal], dtype, params) -> \
        Optional[AbsVal]:
    """Side-effect-free re-application of a small arithmetic subset
    (used only by refinement re-evaluation — no findings are emitted
    from here)."""

    class _Null:
        entry = ""
        report = False

        def finding(self, *a, **k):
            pass

    class _Aval:
        def __init__(self, dt):
            self.dtype = dt

    nul = _Null()
    if prim in ("add", "sub", "mul", "max", "min", "div", "rem", "and",
                "or", "xor", "neg", "abs", "shift_left",
                "shift_right_logical", "shift_right_arithmetic",
                "population_count"):
        return _arith(nul, prim, None, "", ins, _Aval(dtype))
    if prim == "convert_element_type":
        src = ins[0]
        if src.is_float or np.dtype(dtype).kind == "f":
            return AbsVal(dtype, is_float=np.dtype(dtype).kind == "f")
        dlo, dhi = _dtype_range(dtype)
        if src.lo >= dlo and src.hi <= dhi:
            return _narrowed(src, dtype, src.lo, src.hi, bits=src.bits)
        return _top(dtype)
    if prim in ("broadcast_in_dim", "reshape", "squeeze", "transpose",
                "slice", "rev", "copy", "expand_dims"):
        s = ins[0]
        return _narrowed(s, dtype, s.lo, s.hi, bits=s.bits)
    return None


# -- index-site checks -------------------------------------------------------


def _guarded(idx: AbsVal, limit: int) -> bool:
    """The repo's explicit gather discipline: the index (or a value it
    narrows from) was range-tested against this extent somewhere in
    the program, and is known/tested non-negative."""
    lo_ok = idx.lo >= 0 or bool(idx.tested_lb)
    ub_ok = idx.hi <= limit or any(t <= limit + 1 for t in idx.tested_ub)
    return lo_ok and ub_ok


def _check_index(ctx: _Ctx, check: str, prim: str, eqn, region: str,
                 idx: AbsVal, limit: int, extent_str: str,
                 mode: str = ""):
    """``idx`` must be provably within [0, limit] (limit already
    accounts for the slice/window size).  Proven and guarded sites
    count in stats; the rest are findings."""
    ctx.stats["index_sites"] += 1
    if idx.is_float:
        pass
    elif idx.lo >= 0 and idx.hi <= limit:
        ctx.stats["proved"] += 1
        return
    elif _guarded(idx, limit):
        ctx.stats["guarded"] += 1
        return
    ctx.finding(
        check, "error",
        f"{ctx.entry}:{prim}:ext{extent_str}",
        f"index interval [{idx.lo}, {idx.hi}] is not provably within "
        f"[0, {limit}] and carries no range guard — XLA "
        f"{mode or 'clamp'} semantics can engage silently",
        eqn=_eqn_slice(eqn), region=region,
        interval=f"[{idx.lo}, {idx.hi}]", extent=extent_str)


def _is_fill_mode(eqn) -> bool:
    """FILL_OR_DROP index semantics: an out-of-range index yields the
    fill value (gather) or drops the update (scatter) — an EXPLICIT
    author choice with no wrong-memory access, unlike the silent CLIP
    redirect or PROMISE_IN_BOUNDS undefined behavior."""
    return "FILL_OR_DROP" in str(eqn.params.get("mode", ""))


def _gather_transfer(ctx, eqn, region, ins: List[AbsVal]) -> AbsVal:
    operand_av, indices_av = ins
    operand = eqn.invars[0].aval
    dnums = eqn.params["dimension_numbers"]
    slice_sizes = eqn.params["slice_sizes"]
    mode = str(eqn.params.get("mode", ""))
    fill = _is_fill_mode(eqn)
    in_range = True
    if not indices_av.is_float:
        for d in dnums.start_index_map:
            limit = operand.shape[d] - slice_sizes[d]
            if not (indices_av.lo >= 0 and indices_av.hi <= limit):
                in_range = False
    if fill:
        ctx.stats["index_sites"] += 1
        if in_range:
            # clip-before-take idiom: the fill path is provably dead,
            # so the fill value never joins the result
            ctx.stats["proved"] += 1
        else:
            ctx.stats["filled"] = ctx.stats.get("filled", 0) + 1
    else:
        for d in dnums.start_index_map:
            limit = operand.shape[d] - slice_sizes[d]
            _check_index(ctx, "oob-gather", "gather", eqn, region,
                         indices_av, limit,
                         f"{operand.shape[d]}", mode=mode)
    out = AbsVal(eqn.outvars[0].aval.dtype,
                 is_float=np.dtype(operand.dtype).kind == "f")
    if not out.is_float and not operand_av.is_float:
        out = AbsVal(out.dtype, operand_av.lo, operand_av.hi,
                     bits=operand_av.bits)
        if fill and not in_range:
            fv = eqn.params.get("fill_value", None)
            if fv is not None:
                out = _join(out, _absval_of_literal(
                    np.asarray(fv, out.dtype)), out.dtype)
    return out


def _scatter_transfer(ctx, eqn, region, ins: List[AbsVal]) -> AbsVal:
    operand_av, indices_av, updates_av = ins[:3]
    operand = eqn.invars[0].aval
    updates = eqn.invars[2].aval
    dnums = eqn.params["dimension_numbers"]
    prim = eqn.primitive.name
    mode = str(eqn.params.get("mode", ""))
    if _is_fill_mode(eqn):
        ctx.stats["index_sites"] += 1
        ctx.stats["filled"] = ctx.stats.get("filled", 0) + 1
    else:
        # window extent along each indexed operand dim: the row/element
        # scatters in this codebase carry window extent 1 on indexed dims
        for d in dnums.scatter_dims_to_operand_dims:
            _check_index(ctx, "oob-scatter", prim, eqn, region,
                         indices_av, operand.shape[d] - 1,
                         f"{operand.shape[d]}", mode=mode)
    dt = eqn.outvars[0].aval.dtype
    if operand_av.is_float or updates_av.is_float:
        return AbsVal(dt, is_float=np.dtype(dt).kind == "f")
    if prim == "scatter-add":
        # one output element accumulates at most one element from each
        # update WINDOW, so the count is over non-window update dims
        n = 1
        for d, ext in enumerate(updates.shape):
            if d not in dnums.update_window_dims:
                n *= ext
        lo = operand_av.lo + min(0, n * updates_av.lo)
        hi = operand_av.hi + max(0, n * updates_av.hi)
        return _wrap_result(ctx, prim, dt, lo, hi,
                            [operand_av, updates_av], eqn, region,
                            accumulation=True)
    return _join(operand_av, updates_av, dt)


def _dynamic_slice_transfer(ctx, eqn, region, ins: List[AbsVal]) -> AbsVal:
    operand = eqn.invars[0].aval
    sizes = eqn.params["slice_sizes"]
    for d, start_av in enumerate(ins[1:]):
        limit = operand.shape[d] - sizes[d]
        if limit == 0 and _const_of(start_av) == 0:
            ctx.stats["index_sites"] += 1
            ctx.stats["proved"] += 1
            continue
        _check_index(ctx, "oob-dynamic-slice", "dynamic_slice", eqn,
                     region, start_av, limit, f"{operand.shape[d]}")
    src = ins[0]
    if src.is_float:
        return AbsVal(eqn.outvars[0].aval.dtype, is_float=True)
    return AbsVal(eqn.outvars[0].aval.dtype, src.lo, src.hi,
                  bits=src.bits)


# -- the jaxpr walker --------------------------------------------------------


def _absval_of_literal(val) -> AbsVal:
    arr = np.asarray(val)
    if arr.dtype.kind == "f":
        return AbsVal(arr.dtype, is_float=True)
    if arr.size == 0:
        return _top(arr.dtype)
    lo, hi = int(arr.min()), int(arr.max())
    av = AbsVal(arr.dtype, lo, hi)
    if arr.size == 1:
        av.const = int(arr.reshape(-1)[0])
    return av


def _read(env: Dict, v) -> AbsVal:
    from jax.extend.core import Literal

    if isinstance(v, Literal):
        return _absval_of_literal(v.val)
    return env[v]


def _out_top(eqn) -> List[AbsVal]:
    outs = []
    for ov in eqn.outvars:
        aval = getattr(ov, "aval", None)
        dt = getattr(aval, "dtype", np.dtype(np.int32))
        outs.append(AbsVal(dt, is_float=np.dtype(dt).kind == "f"))
    return outs


def interp_closed_jaxpr(closed, in_avs: Sequence[AbsVal], ctx: _Ctx,
                        region: str = "") -> List[AbsVal]:
    consts = [_absval_of_literal(c) if not hasattr(c, "aval")
              else _absval_of_literal(np.asarray(c))
              for c in closed.consts]
    return _interp(closed.jaxpr, list(consts) + list(in_avs), ctx, region)


def _interp(jaxpr, in_avs: Sequence[AbsVal], ctx: _Ctx,
            region: str) -> List[AbsVal]:
    env: Dict[Any, AbsVal] = {}
    invars = list(jaxpr.constvars) + list(jaxpr.invars)
    if len(invars) != len(in_avs):
        raise ValueError(
            f"arity mismatch in {region or 'top'}: {len(invars)} vars, "
            f"{len(in_avs)} abstract values")
    for v, av in zip(invars, in_avs):
        env[v] = av
    for eqn in jaxpr.eqns:
        ctx.stats["eqns"] += 1
        ins = [_read(env, v) for v in eqn.invars]
        outs = _eqn_transfer(eqn, ins, ctx, region)
        for ov, av in zip(eqn.outvars, outs):
            env[ov] = av
    out = []
    for v in jaxpr.outvars:
        out.append(_read(env, v))
    return out


def _subjaxpr(p):
    """Normalize a params entry to a ClosedJaxpr-like (jaxpr, consts)."""
    if hasattr(p, "jaxpr"):
        return p
    return None


def _fixpoint_region(body_closed, n_consts: int, const_avs, carry_avs,
                     extra_avs, ctx, region: str,
                     carry_out_slice) -> Tuple[List[AbsVal], List[AbsVal]]:
    """Shared while/scan carry fixpoint: iterate the body jaxpr
    quietly, joining carries; widen still-growing components to
    dtype-top after WIDEN_AFTER joins; then one reporting pass."""
    carries = list(carry_avs)
    prev_report = ctx.report
    ctx.report = False
    try:
        for it in range(WIDEN_AFTER + 2):
            outs = interp_closed_jaxpr(
                body_closed, list(const_avs) + carries + list(extra_avs),
                ctx, region)
            new_carries = list(outs[carry_out_slice])
            changed = False
            merged = []
            for old, new in zip(carries, new_carries):
                j = _join(old, new)
                if j.key() != old.key():
                    changed = True
                    if it >= WIDEN_AFTER:
                        j = AbsVal(old.dtype,
                                   is_float=old.is_float)  # widen: top
                merged.append(j)
            carries = merged
            if not changed:
                break
        # narrowing descent: from the post-fixpoint, re-run the body
        # and re-join with the entry carries.  Sound for monotone
        # transfer, and it recovers carries the widening threw to top
        # whose body output is intrinsically bounded (a clip()- or
        # mask-saturated loop counter).
        for _ in range(2):
            outs = interp_closed_jaxpr(
                body_closed, list(const_avs) + carries + list(extra_avs),
                ctx, region)
            nxt = [_join(c0, o) for c0, o in
                   zip(carry_avs, outs[carry_out_slice])]
            if all(n.key() == c.key() for n, c in zip(nxt, carries)):
                break
            carries = nxt
    finally:
        ctx.report = prev_report
    outs = interp_closed_jaxpr(
        body_closed, list(const_avs) + carries + list(extra_avs), ctx,
        region)
    final_carries = [
        _join(c, o) for c, o in zip(carries, outs[carry_out_slice])]
    return final_carries, outs


def _eqn_transfer(eqn, ins: List[AbsVal], ctx: _Ctx,
                  region: str) -> List[AbsVal]:
    prim = eqn.primitive.name
    params = eqn.params

    # -- structured control flow / calls --
    if prim == "jit":
        sub = params.get("jaxpr")
        if sub is not None:
            return interp_closed_jaxpr(sub, ins, ctx, region)
        return _out_top(eqn)
    if prim in ("custom_jvp_call", "custom_vjp_call", "remat",
                "checkpoint", "closed_call", "core_call", "xla_call"):
        sub = params.get("call_jaxpr") or params.get("jaxpr")
        if sub is not None and hasattr(sub, "jaxpr"):
            try:
                return interp_closed_jaxpr(sub, ins, ctx, region)
            except ValueError:
                return _out_top(eqn)
        return _out_top(eqn)
    if prim == "shard_map":
        sub = params.get("jaxpr")
        if sub is not None:
            try:
                if hasattr(sub, "jaxpr"):
                    return interp_closed_jaxpr(sub, ins, ctx, region)
                return _interp(sub, ins, ctx, region)
            except ValueError:
                return _out_top(eqn)
        return _out_top(eqn)
    if prim == "while":
        cond = params["cond_jaxpr"]
        body = params["body_jaxpr"]
        cn, bn = params["cond_nconsts"], params["body_nconsts"]
        cond_consts = ins[:cn]
        body_consts = ins[cn:cn + bn]
        carry0 = ins[cn + bn:]
        carries, _ = _fixpoint_region(
            body, bn, body_consts, carry0, [], ctx,
            region + "/while.body", slice(0, len(carry0)))
        # run cond once (reporting) for its own index sites
        interp_closed_jaxpr(cond, list(cond_consts) + carries, ctx,
                            region + "/while.cond")
        return [_join(a, b) for a, b in zip(carry0, carries)]
    if prim == "scan":
        body = params["jaxpr"]
        nc, nk = params["num_consts"], params["num_carry"]
        consts_avs = ins[:nc]
        carry0 = ins[nc:nc + nk]
        xs = ins[nc + nk:]
        # a stacked xs element abstracts to the whole-array interval
        carries, outs = _fixpoint_region(
            body, nc, consts_avs, carry0, xs, ctx,
            region + "/scan.body", slice(0, nk))
        final = [_join(a, b) for a, b in zip(carry0, carries)]
        ys = outs[nk:]
        return final + list(ys)
    if prim == "cond":
        branches = params["branches"]
        opers = ins[1:]
        outs = None
        for i, br in enumerate(branches):
            o = interp_closed_jaxpr(br, opers, ctx,
                                    region + f"/cond.br{i}")
            outs = o if outs is None else [
                _join(a, b) for a, b in zip(outs, o)]
        return outs if outs is not None else _out_top(eqn)
    if prim == "pallas_call":
        ctx.stats["pallas_opaque"] += 1
        return _out_top(eqn)

    # -- index sites --
    if prim == "gather":
        return [_gather_transfer(ctx, eqn, region, ins)]
    if prim.startswith("scatter"):
        return [_scatter_transfer(ctx, eqn, region, ins)]
    if prim == "dynamic_slice":
        return [_dynamic_slice_transfer(ctx, eqn, region, ins)]
    if prim == "dynamic_update_slice":
        operand = eqn.invars[0].aval
        update = eqn.invars[1].aval
        for d, start_av in enumerate(ins[2:]):
            limit = operand.shape[d] - update.shape[d]
            if limit == 0 and _const_of(start_av) == 0:
                continue
            _check_index(ctx, "oob-dynamic-slice",
                         "dynamic_update_slice", eqn, region, start_av,
                         limit, f"{operand.shape[d]}")
        return [_join(ins[0], ins[1], eqn.outvars[0].aval.dtype)]

    # -- comparisons --
    if prim in _CMP_PRIMS:
        out = AbsVal(np.bool_, 0, 1)
        _record_cmp(prim, ins[0], ins[1], out)
        return [out]

    # -- selection with predicate refinement --
    if prim == "select_n":
        pred, cases = ins[0], ins[1:]
        if len(cases) == 2 and pred.cmps:
            f_case, t_case = cases
            t_ref: Dict[int, AbsVal] = {}
            f_ref: Dict[int, AbsVal] = {}
            t_dead = f_dead = False
            for (target, t_lo, t_hi, f_lo, f_hi) in pred.cmps:
                if target.is_float:
                    continue
                if t_lo is not None or t_hi is not None:
                    lo = target.lo if t_lo is None else max(target.lo, t_lo)
                    hi = target.hi if t_hi is None else min(target.hi, t_hi)
                    if lo > hi:
                        t_dead = True   # branch provably unreachable
                    else:
                        t_ref[id(target)] = _narrowed(
                            target, target.dtype, lo, hi,
                            bits=target.bits)
                if f_lo is not None or f_hi is not None:
                    lo = target.lo if f_lo is None else max(target.lo, f_lo)
                    hi = target.hi if f_hi is None else min(target.hi, f_hi)
                    if lo > hi:
                        f_dead = True
                    else:
                        f_ref[id(target)] = _narrowed(
                            target, target.dtype, lo, hi,
                            bits=target.bits)
            t_val = _refine_eval(t_case, t_ref) if t_ref else t_case
            f_val = _refine_eval(f_case, f_ref) if f_ref else f_case
            dt_out = eqn.outvars[0].aval.dtype
            if t_dead and not f_dead:
                return [f_val]
            if f_dead and not t_dead:
                return [t_val]
            return [_join(f_val, t_val, dt_out)]
        out = cases[0]
        for c in cases[1:]:
            out = _join(out, c, eqn.outvars[0].aval.dtype)
        return [out]

    # -- logical combination of predicates (carry conjunction facts) --
    if prim == "and" and np.dtype(eqn.outvars[0].aval.dtype) == np.bool_:
        out = AbsVal(np.bool_, 0, 1)
        facts = []
        for o in ins:
            if o.cmps:
                # under TRUE all conjuncts hold; under FALSE nothing
                facts.extend((t, tl, th, None, None)
                             for (t, tl, th, _fl, _fh) in o.cmps)
        if facts:
            out.cmps = facts
        return [out]
    if prim in ("or", "xor", "not") and \
            np.dtype(eqn.outvars[0].aval.dtype) == np.bool_:
        return [AbsVal(np.bool_, 0, 1)]

    # -- shape/value-preserving --
    if prim in ("broadcast_in_dim", "reshape", "squeeze", "transpose",
                "rev", "copy", "expand_dims", "slice", "device_put",
                "stop_gradient", "copy_p", "sharding_constraint",
                "optimization_barrier"):
        if prim == "optimization_barrier":
            return [
                _narrowed(s, eqn.outvars[i].aval.dtype, s.lo, s.hi,
                          bits=s.bits) if not s.is_float else s
                for i, s in enumerate(ins)]
        s = ins[0]
        dt = eqn.outvars[0].aval.dtype
        if s.is_float:
            return [AbsVal(dt, is_float=True)]
        out = _narrowed(s, dt, s.lo, s.hi, bits=s.bits)
        out.expr = (prim, tuple(ins), None)
        out.cmps = s.cmps  # predicate provenance survives reshaping
        out.const = s.const
        return [out]
    if prim == "concatenate":
        out = ins[0]
        for o in ins[1:]:
            out = _join(out, o, eqn.outvars[0].aval.dtype)
        return [out]
    if prim == "pad":
        return [_join(ins[0], ins[1], eqn.outvars[0].aval.dtype)]
    if prim == "iota":
        dim = params["dimension"]
        n = eqn.outvars[0].aval.shape[dim]
        return [AbsVal(eqn.outvars[0].aval.dtype, 0, max(n - 1, 0))]
    if prim == "convert_element_type":
        src = ins[0]
        dt = eqn.outvars[0].aval.dtype
        if np.dtype(dt).kind == "f":
            return [AbsVal(dt, is_float=True)]
        if src.is_float:
            return [_top(dt)]
        dlo, dhi = _dtype_range(dt)
        if src.lo >= dlo and src.hi <= dhi:
            out = _narrowed(src, dt, src.lo, src.hi, bits=src.bits)
            out.expr = (prim, tuple(ins), None)
            out.cmps = src.cmps
            out.const = src.const
            return [out]
        if src.informative():
            loc = _src_of(eqn)
            subject = (f"{ctx.entry}:convert:{np.dtype(src.dtype).name}->"
                       f"{np.dtype(dt).name}")
            if loc:
                subject += f"@{loc}"
            ctx.finding(
                "int-wrap", "error",
                subject,
                f"narrowing convert of [{src.lo}, {src.hi}] "
                f"{np.dtype(src.dtype).name} into {np.dtype(dt).name} "
                f"[{dlo}, {dhi}] — values wrap silently",
                eqn=_eqn_slice(eqn), region=region,
                interval=f"[{src.lo}, {src.hi}]",
                extent=np.dtype(dt).name)
        return [_top(dt)]
    if prim == "bitcast_convert_type":
        src = ins[0]
        dt = eqn.outvars[0].aval.dtype
        if np.dtype(dt).kind == "f" or src.is_float:
            return [AbsVal(dt, is_float=np.dtype(dt).kind == "f")]
        dlo, dhi = _dtype_range(dt)
        if src.lo >= 0 and src.hi <= dhi:
            return [AbsVal(dt, src.lo, src.hi, bits=src.bits)]
        return [_top(dt)]

    # -- reductions --
    if prim in ("reduce_max", "reduce_min"):
        s = ins[0]
        dt = eqn.outvars[0].aval.dtype
        if s.is_float:
            return [AbsVal(dt, is_float=True)]
        return [AbsVal(dt, s.lo, s.hi, bits=s.bits)]
    if prim in ("reduce_and", "reduce_or"):
        return [AbsVal(eqn.outvars[0].aval.dtype, 0, 1)]
    if prim in ("reduce_sum", "cumsum"):
        s = ins[0]
        dt = eqn.outvars[0].aval.dtype
        if s.is_float:
            return [AbsVal(dt, is_float=True)]
        shape = eqn.invars[0].aval.shape
        if prim == "reduce_sum":
            axes = params.get("axes", ())
            n = 1
            for ax in axes:
                n *= shape[ax]
        else:
            n = shape[params.get("axis", 0)]
        n = max(int(n), 1)
        return [_wrap_result(ctx, prim, dt, min(s.lo, n * s.lo),
                             max(s.hi, n * s.hi), [s], eqn, region,
                             accumulation=True)]
    if prim in ("argmax", "argmin"):
        axes = params.get("axes", ())
        shape = eqn.invars[0].aval.shape
        n = shape[axes[0]] if axes else max(shape or (1,))
        return [AbsVal(eqn.outvars[0].aval.dtype, 0, max(n - 1, 0))]
    if prim == "dot_general":
        a, b = ins
        dt = eqn.outvars[0].aval.dtype
        if a.is_float or b.is_float or np.dtype(dt).kind == "f":
            return [AbsVal(dt, is_float=np.dtype(dt).kind == "f")]
        dnums = params["dimension_numbers"]
        (lc, _rc), _batch = dnums
        k = 1
        for ax in lc:
            k *= eqn.invars[0].aval.shape[ax]
        cs = [a.lo * b.lo, a.lo * b.hi, a.hi * b.lo, a.hi * b.hi]
        return [_wrap_result(ctx, prim, dt, k * min(cs + [0]),
                             k * max(cs + [0]), ins, eqn, region)]

    # -- plain arithmetic --
    if prim in ("add", "sub", "mul", "max", "min", "div", "rem", "and",
                "or", "xor", "not", "neg", "abs", "shift_left",
                "shift_right_logical", "shift_right_arithmetic",
                "population_count", "clz"):
        out = _arith(ctx, prim, eqn, region, ins, eqn.outvars[0].aval)
        out.expr = (prim, tuple(ins), None)
        return [out]
    if prim == "clamp":
        lo_av, x, hi_av = ins
        if x.is_float:
            return [AbsVal(eqn.outvars[0].aval.dtype, is_float=True)]
        return [_narrowed(x, eqn.outvars[0].aval.dtype,
                          max(x.lo, lo_av.lo), min(x.hi, hi_av.hi))]

    # -- unknown: sound top, never a finding --
    ctx.stats["unknown_prims"] += 1
    return _out_top(eqn)


# -- seeding from declared contracts -----------------------------------------


def seed_absvals(args, bounds_meta) -> List[AbsVal]:
    """Abstract values for an entrypoint's positional args: leaves of
    annotated args seed from contracts.TENSOR_BOUNDS (matching pytree
    leaf field names), everything else is dtype-top (attacker-
    controlled or unpromised)."""
    import jax.tree_util as jtu

    role_by_arg: Dict[int, Tuple[str, Any]] = {}
    for entry in bounds_meta or ():
        idx, role = entry[0], entry[1]
        spec_thunk = entry[2] if len(entry) > 2 else None
        role_by_arg[idx] = (role, spec_thunk)

    def leaf_absval(leaf, bound) -> AbsVal:
        dt = getattr(leaf, "dtype", None) or np.asarray(leaf).dtype
        if bound is not None and np.dtype(dt).kind in "iu":
            return AbsVal(dt, bound.lo, bound.hi, bits=bound.bits)
        return AbsVal(dt, is_float=np.dtype(dt).kind == "f")

    out: List[AbsVal] = []
    for i, arg in enumerate(args):
        role = role_by_arg.get(i)
        fields: Dict[str, contracts.TensorBound] = {}
        if role is not None:
            spec = role[1]() if role[1] is not None else None
            fields = contracts.resolve_bounds(role[0], arg, spec=spec)
        if hasattr(arg, "_fields"):        # NamedTuple container
            for fname in arg._fields:
                b = fields.get(fname)
                for leaf in jtu.tree_leaves(getattr(arg, fname)):
                    out.append(leaf_absval(leaf, b))
        else:                              # bare array / plain tree
            b = fields.get("")
            for leaf in jtu.tree_leaves(arg):
                out.append(leaf_absval(leaf, b))
    return out


# -- audits ------------------------------------------------------------------


def audit_entry(ep, batch: int = 256, witness: bool = True,
                suppressions: Optional[list] = None) -> EntryReport:
    """Trace one registered entrypoint at ``batch`` lanes, seed the
    declared bounds, interpret the jaxpr, and (optionally) replay
    error findings through the entry's witness harness."""
    import jax

    from ..kernels import EntrypointUnavailable

    rep = EntryReport(entry=ep.name, kind=ep.kind)
    try:
        fn, args = ep.build(batch)
    except EntrypointUnavailable as e:
        rep.findings.append(Finding(
            "audit-info", "info", ep.name, ep.name,
            f"entrypoint unavailable at batch {batch}: {e}"))
        return rep
    except Exception as e:  # build crashed — that IS a finding
        rep.error = f"build failed: {type(e).__name__}: {e}"
        return rep

    ctx = _Ctx(ep.name)
    try:
        closed = jax.make_jaxpr(fn)(*args)
        # jaxpr invars are the flattened args — seed_absvals returns
        # them leaf-aligned
        flat = seed_absvals(args, getattr(ep, "bounds", ()))
        n_in = len(closed.jaxpr.invars)
        if len(flat) != n_in:
            # argument flattening mismatch (kwargs/static args) —
            # fall back to dtype-top seeding off the jaxpr avals
            flat = [AbsVal(v.aval.dtype,
                           is_float=np.dtype(v.aval.dtype).kind == "f")
                    for v in closed.jaxpr.invars]
            ctx.stats["seed_fallback"] = 1
        interp_closed_jaxpr(closed, flat, ctx)
    except Exception as e:
        rep.error = f"audit failed: {type(e).__name__}: {e}"
        return rep

    rep.stats = ctx.stats
    findings = sorted(
        ctx.findings.values(),
        key=lambda f: ({"error": 0, "warning": 1, "info": 2}[f.severity],
                       f.subject, f.region))

    # witness replay: concretize error findings through the entry's
    # harness — divergence confirms, bit-identity downgrades
    if witness and any(f.severity == "error" for f in findings):
        harness = WITNESS_HARNESSES.get(ep.name)
        if harness is not None:
            try:
                w = harness(ep, findings)
            except Exception as e:
                w = {"ran": False,
                     "error": f"{type(e).__name__}: {e}"}
            for f in findings:
                if f.severity != "error":
                    continue
                f.witness = w
                if w.get("ran") and not w.get("diverged"):
                    f.severity = "info"
                    f.message += (" [witness replay stayed "
                                  "bit-identical to the oracle — "
                                  "downgraded to unreached]")

    supp = suppressions if suppressions is not None else \
        _suppress.load_suppressions(default_suppressions_path())
    for f in findings:
        hit = _suppress.match(supp, f.check, f.subject)
        if hit is not None:
            f.suppressed_by = hit[2]
            rep.suppressed.append(f)
        else:
            rep.findings.append(f)
    return rep


def audit_all(names: Optional[Sequence[str]] = None, batch: int = 256,
              witness: bool = True,
              suppressions_path: Optional[str] = None) -> List[EntryReport]:
    from .. import kernels

    supp = _suppress.load_suppressions(
        suppressions_path or default_suppressions_path())
    eps = kernels.kernel_entrypoints()
    if names:
        eps = [e for e in eps if e.name in set(names)]
    return [audit_entry(e, batch=batch, witness=witness,
                        suppressions=supp) for e in eps]


def summarize(reports: Sequence[EntryReport]) -> dict:
    return {
        "entries": len(reports),
        "errors": sum(r.errors for r in reports),
        "warnings": sum(1 for r in reports for f in r.findings
                        if f.severity == "warning"),
        "infos": sum(1 for r in reports for f in r.findings
                     if f.severity == "info"),
        "suppressed": sum(len(r.suppressed) for r in reports),
        "audit_errors": sum(1 for r in reports if r.error),
        "index_sites": sum(r.stats.get("index_sites", 0)
                           for r in reports),
        "proved": sum(r.stats.get("proved", 0) for r in reports),
        "guarded": sum(r.stats.get("guarded", 0) for r in reports),
        "pallas_opaque": sum(r.stats.get("pallas_opaque", 0)
                             for r in reports),
    }


# -- witness harnesses -------------------------------------------------------
#
# A harness materializes a boundary state/input batch at the interval
# frontier the finding reasons about and replays PRODUCTION dispatch
# against the CPU oracle.  Returns {"ran": bool, "diverged": bool,
# "detail": str, "lanes": int}.


def _witness_arena_splice(ep, findings) -> dict:
    """Boundary state for the spliced-arena entry: drive one more
    splice-map update so a tenant lands on bank 1 (the page-table
    interval frontier — bit 30 set), then replay mixed-tenant wire
    batches through the production fused classify vs the per-tenant
    CPU oracle."""
    import jax

    from .. import oracle, testing
    from ..compiler import IncrementalTables
    from ..kernels import _fixture_tables
    from ..kernels import jaxpath

    rng = np.random.default_rng(33)
    t0 = _fixture_tables(False)
    upd = IncrementalTables.from_content(dict(t0.content), rule_width=4)
    deep = sorted(
        (k for k in t0.content if k.prefix_len > 16),
        key=lambda k: (k.ingress_ifindex, k.prefix_len, k.ip_data),
    )
    if not deep:
        return {"ran": False,
                "error": "fixture has no deep keys to splice-edit"}
    upd.apply({deep[0]: testing.random_rules(rng, 4)})
    t1 = upd.snapshot()
    spec = jaxpath.arena_spec_for(
        "ctrie", (t0, t1), pages=4, max_tenants=8,
        plane_slots=256, plane_node_rows=16, plane_target_rows=16,
        plane_joined_rows=16, splice_slots=64,
    )
    # extremal GEOMETRY, not just extremal values: with a lut span
    # divisible by 4 the bank bit's contribution to pg0 * SL is
    # 2^30 * SL = 0 (mod 2^32), so an unmasked page id cancels out of
    # the int32 root-lut index and the corruption is latent.  A 6-row
    # span keeps 2^31 of it, which is exactly the frontier the
    # interval finding reasons about — the witness must replay where
    # the abstract escape is concrete.
    spec = spec._replace(lut_rows=6)
    alloc = jaxpath.ArenaAllocator(spec)
    alloc.load_tenant(0, t0)
    alloc.load_tenant(1, t1)

    def bank_of(t):
        return (int(np.asarray(alloc.arena.page_table)[t])
                >> jaxpath._SPLICE_BANK_SHIFT) & 1

    # frontier edits: keep landing deep-key updates on tenant 1 until
    # a bank flip puts bit 30 on its page-table row — the page-table
    # value frontier the dropped mask exposes
    t1b = t1
    for i in range(1, len(deep) + 4):
        if bank_of(1) == 1:
            break
        key = deep[i % len(deep)]
        upd.apply({key: testing.random_rules(rng, 4)})
        t1b = upd.snapshot()
        alloc.load_tenant(1, t1b)
    if bank_of(1) != 1:
        return {"ran": False,
                "error": "could not drive tenant 1 onto splice bank 1"}

    from .. import packets
    tabs = {0: t0, 1: t1b}
    per = 48
    parts, tags, want = [], [], []
    for t, tab in sorted(tabs.items()):
        b = testing.random_batch(np.random.default_rng(7 + t), tab, per)
        parts.append(b)
        tags.append(np.full(per, t, np.int32))
        want.append(oracle.classify(tab, b).results)
    batch = packets.concat(parts)
    tenant = np.concatenate(tags)
    want = np.concatenate(want)
    fn = jaxpath.jitted_classify_arena_wire_fused(
        "ctrie", spec.pages, spec.d_max, spec=spec)
    fused = fn(alloc.arena, jax.device_put(batch.pack_wire()),
               jax.device_put(tenant))
    res16, _stats = jaxpath.split_wire_outputs(
        np.asarray(fused), len(batch))
    results, _xdp = jaxpath.host_finalize_wire(
        res16, np.asarray(batch.kind))
    bad = int(np.sum(results != want))
    return {
        "ran": True,
        "diverged": bad > 0,
        "lanes": bad,
        "detail": (
            f"tenant 1 on splice bank 1: {bad}/{len(batch)} lanes "
            f"diverge from the per-tenant CPU oracle"),
    }


def _witness_acmatch(ep, findings) -> dict:
    """Boundary payloads for the standalone AC matcher: lay every
    compiled pattern at extremal offsets (the deep-state frontier of
    the DFA interval) and replay the device bitmap against the naive
    substring oracle."""
    import jax

    from ..kernels import _acmatch_standalone_model
    from ..kernels import acmatch

    model = _acmatch_standalone_model()
    spec = model.spec
    pats = model.patterns
    lanes = []
    for i, p in enumerate(pats):
        pay = np.zeros(spec.plen, np.uint8)
        off = min(i % 7, max(spec.plen - len(p), 0))
        pay[off: off + len(p)] = np.frombuffer(p, np.uint8)
        lanes.append(pay)
    # plus a lane chaining two patterns (failure-link frontier)
    chain = np.zeros(spec.plen, np.uint8)
    joined = (pats[0] + pats[-1])[: spec.plen]
    chain[: len(joined)] = np.frombuffer(joined, np.uint8)
    lanes.append(chain)
    pay = np.stack(lanes)
    plen = np.full(len(lanes), spec.plen, np.int32)
    trans, mmap = acmatch.model_device(model)
    fn = acmatch.jitted_acmatch(spec)
    got = np.asarray(fn(trans, mmap, jax.device_put(pay),
                        jax.device_put(plen)))
    want = acmatch.host_match_bitmap(model, pay, plen)
    bad = int(np.sum(got != want))
    return {
        "ran": True,
        "diverged": bad > 0,
        "lanes": bad,
        "detail": (
            f"{bad}/{len(lanes)} frontier payload lanes diverge from "
            f"the naive substring oracle"),
    }


WITNESS_HARNESSES = {
    "classify-wire/arena-splice-trie": _witness_arena_splice,
    "payload/acmatch-standalone": _witness_acmatch,
}
