"""Static auditor for the jitted/Pallas hot path.

Captures jaxprs of every registered entrypoint
(``infw.kernels.kernel_entrypoints``) on a canonical shape ladder and
asserts, without a TPU:

- **no x64 leaks**: no float64/complex128/int64/uint64 aval anywhere in
  the program — a stray Python float or an accidentally enabled x64
  mode silently doubles transfer and VMEM cost and (on TPU) deoptimizes
  every integer path;
- **no host callbacks** in the packet path: ``pure_callback`` /
  ``io_callback`` / debug callbacks / infeed-outfeed would serialize the
  async dispatch pipeline on every chunk;
- **recompile stability**: building an entrypoint twice returns the SAME
  jitted object (the lru-cached factory contract), tracing the same
  shape twice produces an identical jaxpr (no trace-time value
  dependence), and executing the bench shape ladder plus a repeat shape
  compiles exactly once per distinct shape (``_cache_size``);
- **no implicit transfers**: a warmed entrypoint must execute under
  ``jax.transfer_guard("disallow")`` — H2D staging is explicitly scoped
  to the prepare/plan half of the dispatch (device_put), so any implicit
  host<->device round trip inside the jitted hot path (an uncommitted
  numpy operand, a host fallback) fails the strict audit, including the
  mesh entrypoints on the 8-virtual-device pool;
- **donation honored**: an entrypoint that declares donated operands
  (``KernelEntrypoint.donate`` — the resident serving loop's aliased
  flow columns/epoch) must compile to a program whose
  ``input_output_alias`` map actually aliases every declared donated
  array leaf; a donated buffer XLA silently copies (un-donates) means
  the "zero-alloc steady state" the resident loop advertises is
  fiction, and the audit fails.  A resident-loop entrypoint that
  declares NO donated operands fails too (the registry-level rule);
- **VMEM budget**: for each ``pallas_call``, the resident block-spec
  bytes (double-buffered for grid-blocked operands) must fit the
  documented per-core budget (``pallas_walk.DEFAULT_VMEM_BUDGET`` with
  headroom, see that constant's rationale).

Failures carry the offending jaxpr slice so the report is actionable
without re-tracing.  CLI: ``tools/infw_lint.py jax``.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

#: default bench shape ladder for audits (batch sizes); multiples of the
#: Pallas BLOCK_B=256 so shape-dependent padding does not add noise
DEFAULT_LADDER = (256, 1024)

#: dtypes that must never appear in the packet path (x64 leaks)
_WIDE_DTYPES = ("float64", "complex128", "int64", "uint64")

#: primitives that would put a host round trip in the packet path
_CALLBACK_PRIMS = (
    "pure_callback", "io_callback", "python_callback", "callback",
    "debug_callback", "outside_call", "host_callback_call",
    "infeed", "outfeed",
)


@dataclass
class AuditFinding:
    entry: str
    check: str       # "x64-leak" | "host-callback" | "vmem-budget" |
                     # "recompile" | "trace-determinism" | "unavailable"
    severity: str    # "error" | "warning" | "info"
    message: str
    detail: str = ""  # offending jaxpr slice

    def to_dict(self) -> dict:
        d = {
            "entry": self.entry,
            "check": self.check,
            "severity": self.severity,
            "message": self.message,
        }
        if self.detail:
            d["detail"] = self.detail
        return d


@dataclass
class EntryReport:
    entry: str
    kind: str
    shapes: List[int] = field(default_factory=list)
    n_eqns: int = 0
    n_pallas_calls: int = 0
    vmem_bytes: int = 0
    findings: List[AuditFinding] = field(default_factory=list)

    def to_dict(self) -> dict:
        return {
            "entry": self.entry,
            "kind": self.kind,
            "shapes": list(self.shapes),
            "eqns": self.n_eqns,
            "pallasCalls": self.n_pallas_calls,
            "vmemBytes": self.vmem_bytes,
            "findings": [f.to_dict() for f in self.findings],
        }


# --- jaxpr walking ----------------------------------------------------------


def _iter_eqns(jaxpr, _depth=0):
    """Yield every eqn in a jaxpr including nested call/scan/cond/jit
    bodies (depth-bounded defensively)."""
    if _depth > 32:
        return
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            sub = getattr(v, "jaxpr", v)
            if hasattr(sub, "eqns"):
                yield from _iter_eqns(sub, _depth + 1)
            elif isinstance(v, (list, tuple)):
                for item in v:
                    s = getattr(item, "jaxpr", item)
                    if hasattr(s, "eqns"):
                        yield from _iter_eqns(s, _depth + 1)


def _eqn_avals(eqn):
    for v in list(eqn.invars) + list(eqn.outvars):
        aval = getattr(v, "aval", None)
        if aval is not None and hasattr(aval, "dtype"):
            yield aval


def _eqn_slice(eqn, limit: int = 400) -> str:
    try:
        s = str(eqn)
    except Exception:  # pragma: no cover - jaxpr printing is best-effort
        s = f"<{eqn.primitive}>"
    return s if len(s) <= limit else s[: limit - 3] + "..."


def check_wide_dtypes(entry: str, jaxpr) -> List[AuditFinding]:
    out = []
    seen = set()
    for eqn in _iter_eqns(jaxpr.jaxpr):
        for aval in _eqn_avals(eqn):
            name = str(aval.dtype)
            if name in _WIDE_DTYPES and name not in seen:
                seen.add(name)
                out.append(AuditFinding(
                    entry=entry,
                    check="x64-leak",
                    severity="error",
                    message=(
                        f"{name} aval in the packet path "
                        f"(primitive {eqn.primitive})"
                    ),
                    detail=_eqn_slice(eqn),
                ))
    return out


def check_host_callbacks(entry: str, jaxpr) -> List[AuditFinding]:
    out = []
    for eqn in _iter_eqns(jaxpr.jaxpr):
        if str(eqn.primitive) in _CALLBACK_PRIMS:
            out.append(AuditFinding(
                entry=entry,
                check="host-callback",
                severity="error",
                message=(
                    f"host callback primitive {eqn.primitive} in the "
                    "packet path"
                ),
                detail=_eqn_slice(eqn),
            ))
    return out


def _block_bytes(bm, grid) -> int:
    """Resident VMEM bytes of one pallas block mapping: block shape ×
    itemsize, double-buffered when the operand is streamed over the grid
    (block smaller than the full array)."""
    import numpy as np

    shape = tuple(
        d if isinstance(d, int) else 1
        for d in getattr(bm, "block_shape", ()) or ()
    )
    sds = getattr(bm, "array_shape_dtype", None)
    itemsize = np.dtype(getattr(sds, "dtype", "int32")).itemsize
    n = int(np.prod(shape)) * itemsize if shape else itemsize
    full = tuple(getattr(sds, "shape", ())) if sds is not None else ()
    streamed = bool(grid) and full != () and shape != full
    return n * (2 if streamed else 1)


def pallas_vmem_estimate(eqn) -> Tuple[int, List[str]]:
    """(estimated resident VMEM bytes, per-operand description lines)
    for one pallas_call eqn, from its block specs."""
    gm = eqn.params.get("grid_mapping")
    lines: List[str] = []
    total = 0
    if gm is None:  # pragma: no cover - param layout drift
        return 0, ["<no grid_mapping param; estimate unavailable>"]
    grid = getattr(gm, "grid", ())
    for bm in list(getattr(gm, "block_mappings", ())):
        b = _block_bytes(bm, grid)
        total += b
        sds = getattr(bm, "array_shape_dtype", None)
        lines.append(
            f"block {getattr(bm, 'block_shape', None)} of "
            f"{getattr(sds, 'shape', None)} {getattr(sds, 'dtype', None)}: "
            f"{b} B"
        )
    return total, lines


def check_pallas_vmem(
    entry: str, jaxpr, budget: int
) -> Tuple[List[AuditFinding], int, int]:
    """Returns (findings, n_pallas_calls, max vmem estimate)."""
    out = []
    n = 0
    worst = 0
    for eqn in _iter_eqns(jaxpr.jaxpr):
        if str(eqn.primitive) != "pallas_call":
            continue
        n += 1
        est, lines = pallas_vmem_estimate(eqn)
        worst = max(worst, est)
        if est > budget:
            out.append(AuditFinding(
                entry=entry,
                check="vmem-budget",
                severity="error",
                message=(
                    f"pallas_call block specs estimate {est} B resident "
                    f"VMEM > budget {budget} B"
                ),
                detail="\n".join(lines + [_eqn_slice(eqn)]),
            ))
    return out, n, worst


# --- per-entry audit --------------------------------------------------------


def audit_entry(
    ep,
    ladder: Sequence[int] = DEFAULT_LADDER,
    vmem_budget: Optional[int] = None,
    execute: bool = True,
) -> EntryReport:
    """Audit one KernelEntrypoint across the shape ladder.

    ``execute=False`` skips the run-twice recompile check (trace-only,
    for hosts where even tiny executions are unwanted)."""
    import jax

    from ..kernels import EntrypointUnavailable
    from ..kernels.pallas_walk import DEFAULT_VMEM_BUDGET

    budget = DEFAULT_VMEM_BUDGET if vmem_budget is None else vmem_budget
    rep = EntryReport(entry=ep.name, kind=ep.kind)
    try:
        fn0, _ = ep.build(int(ladder[0]))
        fn1, _ = ep.build(int(ladder[0]))
    except EntrypointUnavailable as e:
        rep.findings.append(AuditFinding(
            entry=ep.name, check="unavailable", severity="info",
            message=str(e),
        ))
        return rep
    if fn0 is not fn1:
        rep.findings.append(AuditFinding(
            entry=ep.name,
            check="recompile",
            severity="error",
            message=(
                "builder returned a different jitted object for the same "
                "static config — the jit cache is keyed on an unstable "
                "factory argument and every chunk recompiles"
            ),
        ))

    for b in ladder:
        try:
            fn, args = ep.build(int(b))
        except EntrypointUnavailable as e:
            # a builder may decline a specific ladder size (e.g. the
            # delta encoder refusing a corpus) without voiding the sizes
            # that did build
            rep.findings.append(AuditFinding(
                entry=ep.name, check="unavailable", severity="info",
                message=f"batch {b}: {e}",
            ))
            continue
        jaxpr = jax.make_jaxpr(fn)(*args)
        rep.shapes.append(int(b))
        rep.n_eqns += sum(1 for _ in _iter_eqns(jaxpr.jaxpr))
        rep.findings.extend(check_wide_dtypes(ep.name, jaxpr))
        rep.findings.extend(check_host_callbacks(ep.name, jaxpr))
        vf, n_pallas, worst = check_pallas_vmem(ep.name, jaxpr, budget)
        rep.findings.extend(vf)
        rep.n_pallas_calls += n_pallas
        rep.vmem_bytes = max(rep.vmem_bytes, worst)
        if b == ladder[0]:
            again = jax.make_jaxpr(fn)(*args)
            if str(jaxpr) != str(again):
                rep.findings.append(AuditFinding(
                    entry=ep.name,
                    check="trace-determinism",
                    severity="warning",
                    message=(
                        "tracing the same canonical shape twice produced "
                        "different jaxprs — trace-time value dependence "
                        "will thrash the compile cache"
                    ),
                ))

    rep.findings.extend(_donation_lint(ep, ladder))
    if execute:
        rep.findings.extend(_recompile_lint(ep, ladder))
        rep.findings.extend(_transfer_lint(ep, ladder))
    return rep


def _count_donated_leaves(args, donate) -> int:
    import jax

    n = 0
    for i in donate:
        if i < len(args):
            n += len(jax.tree.leaves(args[i]))
    return n


def _alias_map_entries(compiled_text: str) -> int:
    """Number of aliased parameters in a compiled HLO module header's
    ``input_output_alias={ {out}: (param, {idx}, kind), ... }`` map —
    each entry carries one ``}: (`` marker (the map nests braces, so a
    span regex can't stop at the first close)."""
    import re

    i = compiled_text.find("input_output_alias={")
    if i < 0:
        return 0
    # ``}: (`` appears once per map entry and nowhere else on the
    # module header line (entry_computation_layout uses ``->(``)
    return len(re.findall(r"\}:\s*\(", compiled_text[i:]))


def _donation_lint(ep, ladder: Sequence[int]) -> List[AuditFinding]:
    """Compile the first ladder shape and verify the declared donated
    operands survived into the program's input_output_alias map — a
    declared-but-unaliased donation means XLA silently copies a buffer
    the serving loop believes it rewrites in place (jax also warns
    'Some donated buffers were not usable' at dispatch; this lint fails
    CI without needing a warning filter).  Also enforces the
    registry-level rule that every resident-loop entrypoint declares
    its donated operands."""
    from ..kernels import EntrypointUnavailable

    out: List[AuditFinding] = []
    donate = tuple(getattr(ep, "donate", ()) or ())
    if "resident" in ep.name and not donate:
        out.append(AuditFinding(
            entry=ep.name,
            check="donation",
            severity="error",
            message=(
                "resident-loop entrypoint declares no donated operands "
                "(KernelEntrypoint.donate) — the zero-alloc serving "
                "contract is unverifiable"
            ),
        ))
    if not donate:
        return out
    try:
        fn, args = ep.build(int(ladder[0]))
    except EntrypointUnavailable:
        return out  # already reported by the trace pass
    except Exception as e:
        out.append(AuditFinding(
            entry=ep.name, check="donation", severity="info",
            message=f"build failed for donation lint: {e}",
        ))
        return out
    want = _count_donated_leaves(args, donate)
    try:
        text = fn.lower(*args).compile().as_text()
    except Exception as e:
        out.append(AuditFinding(
            entry=ep.name, check="donation", severity="info",
            message=f"compile/as_text unavailable for donation lint: {e}",
        ))
        return out
    got = _alias_map_entries(text.splitlines()[0] if text else "")
    if got < want:
        out.append(AuditFinding(
            entry=ep.name,
            check="donation",
            severity="error",
            message=(
                f"{want - got} of {want} declared donated buffer(s) "
                "were silently copied (not in the compiled program's "
                "input_output_alias map) — the donated pool is "
                "reallocating on every dispatch"
            ),
            detail=(text.splitlines()[0][:400] if text else ""),
        ))
    if "superbatch" in ep.name and "while(" not in text:
        # The K-admission epoch program must actually lower to a
        # device-side loop: an unrolled program compiles K copies of
        # the serving step (code size and compile time scale with K)
        # and leaves no loop carry for XLA to alias the donated flow/
        # epoch/sketch/score state through.
        out.append(AuditFinding(
            entry=ep.name,
            check="superbatch-loop",
            severity="error",
            message=(
                "superbatch entrypoint compiled without a device-side "
                "while op — the K-admission epoch loop unrolled, so "
                "the donated carry cannot alias across admissions"
            ),
        ))
    return out


@functools.lru_cache(maxsize=None)
def _donation_defect_jit():
    import jax
    import jax.numpy as jnp

    # the output can never alias the donated operand (different dtype
    # and size), so XLA must drop the donation — the acceptance fixture
    # the donation lint has to fail on
    return jax.jit(lambda x: (x.astype(jnp.int8))[:1], donate_argnums=(0,))


def donation_defect_entrypoint():
    """A deliberately defective donating entrypoint: the declared
    donated operand cannot alias any output, so the compiled program
    silently copies it — ``tools/infw_lint.py jax
    --inject-donation-defect`` must then exit nonzero (the donation-lint
    acceptance, wired into ``make state-check``)."""
    import jax
    import numpy as np

    from ..kernels import KernelEntrypoint

    def build(b: int):
        return _donation_defect_jit(), (
            jax.device_put(np.zeros(int(b), np.int32)),
        )

    return KernelEntrypoint("defect/undonated-buffer", "xla", build,
                            donate=(0,))


@functools.lru_cache(maxsize=None)
def _superbatch_defect_jit():
    import jax

    # donates and aliases cleanly, but the compiled program contains no
    # loop at all — the superbatch-loop lint's acceptance fixture
    return jax.jit(lambda x: x + 1, donate_argnums=(0,))


def superbatch_defect_entrypoint():
    """A deliberately loop-free 'superbatch' entrypoint: donation
    aliases fine, but the compiled program has no while op, so the
    superbatch-loop lint (the ISSUE-16 device-side epoch-loop contract)
    must fail — rides ``--inject-donation-defect`` alongside the
    unaliasable-donation fixture."""
    import jax
    import numpy as np

    from ..kernels import KernelEntrypoint

    def build(b: int):
        return _superbatch_defect_jit(), (
            jax.device_put(np.zeros(int(b), np.int32)),
        )

    return KernelEntrypoint("defect/superbatch-unrolled", "xla", build,
                            donate=(0,))


def _transfer_lint(ep, ladder: Sequence[int]) -> List[AuditFinding]:
    """Execute each ladder shape under ``jax.transfer_guard("disallow")``
    after one unguarded warm run (which compiles the executable and
    stages/commits every operand — the explicitly scoped device_put half
    of the dispatch).  The guarded re-run must then be transfer-free:
    any failure means an implicit host<->device transfer inside the hot
    path, which would serialize the async dispatch pipeline per chunk."""
    import jax

    from ..kernels import EntrypointUnavailable

    out: List[AuditFinding] = []
    donates = bool(getattr(ep, "donate", ()) or ())
    for b in dict.fromkeys(int(x) for x in ladder):
        try:
            fn, args = ep.build(b)
            jax.block_until_ready(fn(*args))  # warm OUTSIDE the guard
            if donates:
                # donation consumed the warm run's operands; rebuild
                # fresh ones (their device_put is the explicitly scoped
                # staging half, so it happens before the guard)
                fn, args = ep.build(b)
        except EntrypointUnavailable:
            continue  # already reported by the trace pass
        except Exception:
            continue  # build/run failures belong to the other lints
        try:
            with jax.transfer_guard("disallow"):
                jax.block_until_ready(fn(*args))
        except Exception as e:
            msg = str(e).splitlines()[0][:300]
            out.append(AuditFinding(
                entry=ep.name,
                check="implicit-transfer",
                severity="error",
                message=(
                    f"batch {b}: implicit transfer in the packet path "
                    f"(jax.transfer_guard('disallow')): {msg}"
                ),
            ))
    return out


@functools.lru_cache(maxsize=None)
def _transfer_defect_jit():
    import jax

    return jax.jit(lambda x: x + 1)


def transfer_defect_entrypoint():
    """A deliberately defective entrypoint whose operand stays HOST-side
    (an uncommitted numpy array), so every call implicitly transfers —
    the acceptance fixture proving the strict audit actually fails on an
    implicit transfer (``tools/infw_lint.py jax
    --inject-transfer-defect`` / ``make state-check``)."""
    import numpy as np

    from ..kernels import KernelEntrypoint

    def build(b: int):
        return _transfer_defect_jit(), (np.zeros(int(b), np.int32),)

    return KernelEntrypoint("defect/implicit-transfer", "xla", build)


def _recompile_lint(ep, ladder: Sequence[int]) -> List[AuditFinding]:
    """Execute the ladder plus a repeat of its first shape; the jit cache
    must hold exactly one executable per distinct shape."""
    import jax

    try:
        fn, args0 = ep.build(int(ladder[0]))
        size0 = fn._cache_size()
    except AttributeError:
        return [AuditFinding(
            entry=ep.name, check="recompile", severity="info",
            message="_cache_size unavailable on this jax; lint skipped",
        )]
    except Exception as e:  # EntrypointUnavailable already reported
        return [AuditFinding(
            entry=ep.name, check="recompile", severity="info",
            message=f"build failed for recompile lint: {e}",
        )]
    from ..kernels import EntrypointUnavailable

    shapes = list(dict.fromkeys(int(b) for b in ladder))
    ran = []
    for b in shapes + [shapes[0]]:
        try:
            fn2, args = ep.build(b)
        except EntrypointUnavailable:
            continue  # already reported by the trace pass
        jax.block_until_ready(fn2(*args))
        if b not in ran:
            ran.append(b)
    if not ran:
        return []
    grew = fn._cache_size() - size0
    if grew > len(ran):
        return [AuditFinding(
            entry=ep.name,
            check="recompile",
            severity="error",
            message=(
                f"{grew} compilations for {len(ran)} distinct ladder "
                "shapes — a repeated shape recompiled (unstable static "
                "argument or weak-type drift)"
            ),
        )]
    return []


def audit_all(
    names: Optional[Sequence[str]] = None,
    ladder: Sequence[int] = DEFAULT_LADDER,
    vmem_budget: Optional[int] = None,
    execute: bool = True,
    include_transfer_defect: bool = False,
    include_donation_defect: bool = False,
) -> List[EntryReport]:
    """Audit every registered entrypoint (or the named subset).

    ``include_transfer_defect`` appends the deliberately defective
    host-operand entrypoint — the audit must then FAIL (the injected
    acceptance of the transfer lint).  ``include_donation_defect``
    appends the declared-but-unaliasable donation entrypoint AND the
    loop-free superbatch entrypoint — the donation and superbatch-loop
    lints' acceptance, same contract."""
    from ..kernels import kernel_entrypoints

    eps = list(kernel_entrypoints())
    if include_transfer_defect:
        eps.append(transfer_defect_entrypoint())
    if include_donation_defect:
        eps.append(donation_defect_entrypoint())
        eps.append(superbatch_defect_entrypoint())
    reports = []
    for ep in eps:
        if names and ep.name not in names:
            continue
        reports.append(
            audit_entry(ep, ladder=ladder, vmem_budget=vmem_budget,
                        execute=execute)
        )
    return reports


def all_findings(reports: Sequence[EntryReport]) -> List[AuditFinding]:
    out: List[AuditFinding] = []
    for r in reports:
        out.extend(r.findings)
    return out


def summarize(reports: Sequence[EntryReport]) -> Dict[str, int]:
    sev = {"error": 0, "warning": 0, "info": 0}
    for f in all_findings(reports):
        sev[f.severity] = sev.get(f.severity, 0) + 1
    return {
        "entries": len(reports),
        "pallasCalls": sum(r.n_pallas_calls for r in reports),
        **sev,
    }
