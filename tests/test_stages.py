"""The resident step's named stages: every instruction of its compiled
program maps to one (jaxpath.hlo_stage_map), the module is named after
the step, and the scopes change the program's metadata and nothing
else."""
import contextlib
import re

import jax
import pytest

from infw import kernels
from infw.kernels import jaxpath

#: instructions that run no work of their own
STRUCTURAL = ("parameter", "constant", "tuple", "get-tuple-element")
_OPCODE = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s([a-z][\w\-]*)\(")
_METADATA = re.compile(r",\s*metadata=\{[^}]*\}")
_FRAME_TABLES = re.compile(
    r"^(?:FileNames|FunctionNames|FileLocations|StackFrames)\n(?:\d+ .*\n)*",
    re.M)


def strip_metadata(hlo_text: str) -> str:
    """The text without instruction metadata and the stack-frame tables
    that the metadata points into."""
    return _METADATA.sub("", _FRAME_TABLES.sub("", hlo_text))


def opcodes(hlo_text: str) -> dict:
    return {m.group(1): m.group(2) for m in map(_OPCODE.match, hlo_text.splitlines())
            if m is not None}


def unnamed(hlo_text: str) -> list:
    """Instructions that do work and map to no stage."""
    stages = jaxpath.hlo_stage_map(hlo_text)
    ops = opcodes(hlo_text)
    assert set(ops) <= set(stages)
    return [n for n, s in stages.items()
            if s == "other" and ops.get(n) not in STRUCTURAL]


def resident_args(b: int, deep: bool, k: int = 0):
    cfg, flow, gens, pages, epoch, max_age, zeros = kernels._resident_operands(b)
    wire = kernels._fixture_wire(b)
    if k:
        wire = jax.numpy.stack([wire] * k)
        zeros = jax.numpy.stack([zeros] * k)
    return cfg, (flow, gens, pages, epoch, kernels._fixture_device_tables(deep),
                 wire, zeros, zeros, max_age)


def test_hlo_stage_map_rules():
    text = """HloModule jit_resident_step, entry_computation_layout={()->u32[8]}

%fused_computation (param_0: u32[8]) -> u32[8] {
  %param_0 = u32[8]{0} parameter(0)
  ROOT %add.1 = u32[8]{0} add(%param_0, %param_0), metadata={op_name="jit(resident_step)/lpm/jit(inner)/rule_scan/add"}
}

ENTRY %main (p: u32[8]) -> (u32[8], u32[8]) {
  %p = u32[8]{0} parameter(0), metadata={op_name="p"}
  %copy.1 = u32[8]{0} copy(%p)
  %fusion.2 = u32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused_computation
  %negate.3 = u32[8]{0} negate(%fusion.2), metadata={op_name="jit(resident_step)/flow_insert/neg" stack_frame_id=4}
  %copy.4 = u32[8]{0} copy(%negate.3)
  ROOT %tuple.5 = (u32[8]{0}, u32[8]{0}) tuple(%copy.4, %fusion.2)
}
"""
    assert jaxpath.hlo_stage_map(text) == {
        # the innermost scope that names a stage
        "param_0": "rule_scan", "add.1": "rule_scan",
        # no metadata: a user's stage, else an operand's
        "p": "rule_scan", "copy.1": "rule_scan",
        # a fusion with no metadata: its fused computation's root's stage
        "fusion.2": "rule_scan", "negate.3": "flow_insert",
        "copy.4": "flow_insert", "tuple.5": "flow_insert",
    }


#: the LPM's stages: the dense compare is lpm alone, the trie walk's ops
#: all sit in the three stages inside it
DENSE_LPM = {"lpm"}
WALK_LPM = {"lpm_root", "lpm_level", "lpm_resolve"}


#: gathers and scatters of the flow tier in one resident step: the
#: probe's way-gather and its counter add, last-seen max and RST min
#: scatters; the insert's way-gather, winner max and seed add scatters,
#: its gather of both at the chosen slot and its one set scatter
FLOW_GATHERS_SCATTERS = 9


@pytest.mark.parametrize("path,deep,k", [("dense", False, 0),
                                         ("trie", True, 0),
                                         ("dense", False, 2)],
                         ids=["dense", "trie", "superbatch"])
def test_flow_tier_gathers_and_scatters(path, deep, k):
    """The flow state is one packed matrix: the resident step's
    flow_probe and flow_insert stages hold at most nine gather and
    scatter instructions (fifteen over four matrices), once per step
    in the superbatch's loop too, and the matrix stays donated: the
    step's first output aliases its first operand."""
    cfg, args = resident_args(256, deep, k)
    factory = (jaxpath.jitted_resident_superbatch if k
               else jaxpath.jitted_resident_step)
    text = factory(cfg.entries, cfg.ways, path).lower(*args).compile().as_text()
    stages = jaxpath.hlo_stage_map(text)
    flow = [n for n, op in opcodes(text).items()
            if op in ("gather", "scatter")
            and stages[n] in ("flow_probe", "flow_insert")]
    assert 0 < len(flow) <= FLOW_GATHERS_SCATTERS, flow
    head = text.splitlines()[0]
    assert re.search(r"input_output_alias=\{ \{0\}: \(0, \{\}, may-alias\)",
                     head), head[:300]
    state = f"s32[{jaxpath.FLOW_ROWS},{cfg.capacity}]"
    assert f"entry_computation_layout={{({state}" in head


@pytest.mark.parametrize("path,deep,k,lpm", [("dense", False, 0, DENSE_LPM),
                                             ("trie", True, 0, WALK_LPM),
                                             ("dense", False, 2, DENSE_LPM)],
                         ids=["dense", "trie", "superbatch"])
def test_cpu_resident_step_maps_every_instruction(path, deep, k, lpm):
    cfg, args = resident_args(256, deep, k)
    factory = (jaxpath.jitted_resident_superbatch if k
               else jaxpath.jitted_resident_step)
    text = factory(cfg.entries, cfg.ways, path).lower(*args).compile().as_text()
    name = "jit_resident_superbatch" if k else "jit_resident_step"
    assert text.startswith(f"HloModule {name},")
    assert unnamed(text) == []
    stages = set(jaxpath.hlo_stage_map(text).values())
    assert lpm | {"rule_scan", "flow_probe", "flow_insert"} <= stages
    assert not (DENSE_LPM | WALK_LPM) - lpm & stages
    assert stages <= set(jaxpath.RESIDENT_STAGES) | {"other"}


def test_scopes_add_metadata_only(monkeypatch):
    """The step compiled with its scopes, and with each scope a null
    context, differ only in instruction metadata."""
    cfg, args = resident_args(256, False)

    def compiled_text():
        fn = jaxpath.jitted_resident_step.__wrapped__(cfg.entries, cfg.ways,
                                                      "dense")
        return fn.lower(*args).compile().as_text()

    scoped = compiled_text()
    monkeypatch.setattr(jax, "named_scope", lambda _name: contextlib.nullcontext())
    plain = compiled_text()
    assert "/flow_probe/" in scoped and "/flow_probe/" not in plain
    stripped = strip_metadata(scoped)
    assert "metadata=" not in stripped and "FileNames" not in stripped
    assert len(opcodes(stripped)) == len(opcodes(scoped)) > 100
    assert stripped == strip_metadata(plain)


def test_walk_scopes_add_metadata_only(monkeypatch):
    """The trie step's walk stages (lpm_root, lpm_level, lpm_resolve
    inside lpm) change its metadata and nothing else."""
    cfg, args = resident_args(256, True)

    def compiled_text():
        fn = jaxpath.jitted_resident_step.__wrapped__(cfg.entries, cfg.ways,
                                                      "trie")
        return fn.lower(*args).compile().as_text()

    scoped = compiled_text()
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope", lambda name: (
        contextlib.nullcontext() if name.startswith("lpm_") else real(name)))
    plain = compiled_text()
    assert "/lpm/lpm_level/" in scoped and "/lpm_level/" not in plain
    assert "/lpm/" in plain
    assert strip_metadata(scoped) == strip_metadata(plain)
