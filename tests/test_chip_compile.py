"""Ahead-of-time compiles of the serving kernels for a described TPU v5e.

The TPU compiler is installed with JAX, and it compiles for a chip that
is described (``v5e:2x2``) rather than attached: what Mosaic or XLA
would refuse on the chip — an unaligned slice, too much VMEM, a program
that does not fit HBM, a kernel that cannot be partitioned — fails here,
where interpret mode lets it through.  Nothing runs, so these tests say
nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process at a time may load the TPU library, and every test
worker imports every test file.  The persistent compilation cache is off
around the compiles, since an entry written for a described chip cannot
be read back without one.
"""
import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from infw import kernels, testing
from infw.daemon import DEFAULT_INGEST_CHUNK
from infw.flow import FlowConfig, FlowTier
from infw.kernels import jaxpath, pallas_dense, pallas_walk
from infw.parallel import mesh as meshmod


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def cache_off():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo, cache_off):
    return SingleDeviceSharding(topo.devices[0])


def shapes(tree, sharding):
    """The pytree's arrays as shapes placed on ``sharding``."""
    return jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                       sharding=sharding),
        tree,
    )


def compile_for(fn, *args):
    compiled = fn.lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def test_dense_wire_kernel_at_reference_capacity(one_chip):
    """The dense Pallas kernel the daemon serves 1000 CIDRs x 100-wide
    rule lists with, at the daemon's ingest chunk."""
    tables = testing.random_tables_fast(
        np.random.default_rng(101), n_entries=1000, width=100,
        v6_fraction=0.5, ifindexes=(2, 3, 4),
    )
    pt = pallas_dense.build_pallas_tables(tables)
    block_b = pallas_dense.choose_block_b(pt.mdt.shape[1])
    fn = pallas_dense.jitted_classify_pallas_wire_fused(False, block_b)
    wire = jax.ShapeDtypeStruct((DEFAULT_INGEST_CHUNK, 6), jnp.uint32,
                                sharding=one_chip)
    compiled = compile_for(fn, shapes(pt, one_chip), wire)
    assert "tpu_custom_call" in compiled.as_text()


def test_compressed_walk_kernel(one_chip):
    """classify/pallas-cwalk, the fused skip-node walk of --compressed."""
    wt, meta = kernels._fixture_cwalk_tables()
    fn = pallas_walk.jitted_classify_cwalk(meta["d_max"], False)
    compile_for(fn, shapes(wt, one_chip),
                shapes(kernels._fixture_device_batch(1024), one_chip))


def test_arena_walk_kernel(one_chip):
    """classify/pallas-arena-walk, the paged walk of --tenants."""
    alloc = kernels._fixture_arena("ctrie")
    planes = pallas_walk.build_arena_cwalk_planes(alloc.host_nodes())
    assert planes is not None
    fn = pallas_walk.jitted_classify_arena_cwalk_wire_fused(
        alloc.spec.pages, alloc.spec.d_max, False
    )
    wire, tenant = kernels._fixture_arena_wire(1024)
    compile_for(fn, *shapes((alloc.arena, planes, wire, tenant), one_chip))


def test_resident_step(one_chip):
    """The resident fused admission program at batch rung 1024, with its
    flow columns and epoch donated: a module named after the step, every
    instruction that does work in one of its named stages."""
    cfg, flow, gens, pages, epoch, max_age, zeros = (
        kernels._resident_operands(1024)
    )
    fn = jaxpath.jitted_resident_step(
        cfg.entries, cfg.ways, "trie", False, None, 0, False
    )
    args = (flow, gens, pages, epoch, kernels._fixture_device_tables(True),
            kernels._fixture_wire(1024), zeros, zeros, max_age)
    text = compile_for(fn, *shapes(args, one_chip)).as_text()
    assert text.startswith("HloModule jit_resident_step,")
    stages = jaxpath.hlo_stage_map(text)
    structural = ("parameter", "constant", "tuple", "get-tuple-element")
    opcode = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s([a-z][\w\-]*)\(")
    for m in filter(None, map(opcode.match, text.splitlines())):
        if m.group(2) not in structural:
            assert stages[m.group(1)] != "other", m.group(0)[:200]


@pytest.fixture(scope="module")
def flow_step(one_chip):
    """(flow config, optimized HLO text, {instruction: stage}) of the
    resident step at the benchmark cell's shapes: dense path,
    65,536-packet records, a 16,384-slot 4-way flow table, flow state
    donated."""
    b = 65536
    cfg = FlowConfig.make(entries=16384)
    tier = FlowTier(cfg)
    lane = jax.ShapeDtypeStruct((b,), jnp.int32)
    fn = jaxpath.jitted_resident_step(cfg.entries, cfg.ways, "dense")
    args = (tier._flow, tier._gens_dev, tier._pages_dev, np.int32(0),
            kernels._fixture_device_tables(False),
            jax.ShapeDtypeStruct((b, 7), jnp.uint32), lane, lane,
            tier._max_age_dev)
    text = compile_for(fn, *shapes(args, one_chip)).as_text()
    return cfg, text, jaxpath.hlo_stage_map(text)


def test_resident_step_flow_stages_lane_dense(flow_step):
    """The resident step at the benchmark cell's shapes: no copy in the
    flow_probe or flow_insert stage has the batch and the ways as its
    only dimensions above one.  Stored slot-major, (C, k), each
    way-gathered column came out as (B, W, 1) with the ways on the lanes
    and took such a relayout copy before its consumers.  The packed
    state's way-gather still leaves the gather as (W * B, 16) rows and
    is relayouted to (16, W, B) planes; this test does not pin that."""
    cfg, text, stages = flow_step
    b = 65536
    assert {"flow_probe", "flow_insert"} <= set(stages.values())
    copy = re.compile(
        r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+\w+\[([\d,]*)\]\S*\s+copy\(")
    for m in filter(None, map(copy.match, text.splitlines())):
        dims = sorted(int(d) for d in m.group(2).split(",") if d)
        if stages[m.group(1)] in ("flow_probe", "flow_insert"):
            assert [d for d in dims if d > 1] != sorted([cfg.ways, b]), (
                m.group(0)[:200])


def test_resident_step_flow_tier_native_gathers(flow_step):
    """At the same shapes the chip's compiler keeps the flow tier's nine
    gathers and scatters as gathers and scatters of the packed matrix's
    whole columns.  It would expand one that moves a window of some of
    a column's rows into a loop over its 65,536 or 262,144 indices."""
    _cfg, text, stages = flow_step
    opcode = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s([a-z][\w\-]*)\(")
    flow = [m.group(2)
            for m in filter(None, map(opcode.match, text.splitlines()))
            if stages[m.group(1)] in ("flow_probe", "flow_insert")]
    assert sorted(op for op in flow if op in ("gather", "scatter")) == (
        ["gather"] * 3 + ["scatter"] * 6)
    assert "while" not in flow and "dynamic-update-slice" not in flow


def _resident_step_args(b: int, tdev, sharding):
    """The resident step's operands at the benchmark cells' flow geometry
    (a 16,384-slot 4-way table) and ``b``-packet 7-word records."""
    cfg = FlowConfig.make(entries=16384)
    tier = FlowTier(cfg)
    lane = jax.ShapeDtypeStruct((b,), jnp.int32)
    args = (tier._flow, tier._gens_dev, tier._pages_dev, np.int32(0), tdev,
            jax.ShapeDtypeStruct((b, 7), jnp.uint32), lane, lane,
            tier._max_age_dev)
    return cfg, shapes(args, sharding)


@pytest.mark.parametrize("tail", ["targets", "joined"])
def test_resident_trie_step_at_blocklist_shapes(one_chip, tail):
    """The resident step on the trie path at the blocklist-100k cell's
    shapes: 65,536-packet records, a DIR-16 root and 4 node levels (/48
    deepest), 8 rule slots an entry; with the target-index tail that
    table takes (its leaf-pushed targets trip the joined duplication
    gate) and with the joined-row tail (3 + 5 x 8 words).  Every
    instruction that does work maps to a stage, the walk to its own."""
    from test_trie_walk import WALK_STAGES, routed_tables

    tdev = jaxpath.device_tables(routed_tables(), pad=True)
    assert len(tdev.trie_levels) == 5
    if tail == "joined":
        tdev = tdev._replace(joined=jax.ShapeDtypeStruct(
            (tdev.trie_targets.shape[0], 3 + 5 * 8), jnp.uint16))
    cfg, args = _resident_step_args(65536, tdev, one_chip)
    fn = jaxpath.jitted_resident_step(cfg.entries, cfg.ways, "trie")
    text = compile_for(fn, *args).as_text()
    stages = jaxpath.hlo_stage_map(text)
    assert WALK_STAGES <= set(stages.values())
    structural = ("parameter", "constant", "tuple", "get-tuple-element")
    opcode = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s+=\s+.*?\s([a-z][\w\-]*)\(")
    for m in filter(None, map(opcode.match, text.splitlines())):
        if m.group(2) not in structural:
            assert stages[m.group(1)] != "other", m.group(0)[:200]


def test_refcap_dense_step_scopes_add_metadata_only(one_chip, monkeypatch):
    """The resident step at the refcap-1k cell's shapes (dense path, 1,024
    entries x 100 rule slots, 65,536-packet records) compiles for the
    chip to the same optimized HLO with its stage scopes as without
    them, apart from instruction metadata."""
    from test_stages import strip_metadata

    tables = testing.random_tables_fast(
        np.random.default_rng(101), n_entries=1024, width=100,
        v6_fraction=0.5, ifindexes=(2, 3, 4),
    )
    cfg, args = _resident_step_args(
        65536, jaxpath.device_tables(tables, pad=True), one_chip)

    def compiled_text():
        fn = jaxpath.jitted_resident_step.__wrapped__(cfg.entries, cfg.ways,
                                                      "dense")
        return compile_for(fn, *args).as_text()

    scoped = compiled_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda _name: contextlib.nullcontext())
    plain = compiled_text()
    assert "/rule_scan/" in scoped and "/rule_scan/" not in plain
    assert strip_metadata(scoped) == strip_metadata(plain)


def test_mesh_walk_on_four_chips(topo, cache_off):
    """classify-mesh/walk-wire: the fused deep walk per shard on a 4x1
    ("data", "rules") mesh of the described chips."""
    mesh = Mesh(np.asarray(topo.devices).reshape(4, 1), ("data", "rules"))
    wt = kernels._fixture_walk_tables()
    fn = meshmod.jitted_mesh_wire(mesh, "walk", wt, interpret=False)
    wire = jax.ShapeDtypeStruct(
        (1024, 7), jnp.uint32, sharding=NamedSharding(mesh, P("data", None))
    )
    compiled = compile_for(fn, shapes(wt, NamedSharding(mesh, P())), wire)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.slow
def test_fused_deep_walk_kernel(one_chip):
    """classify/pallas-walk, the fused deep walk of the trie path (slow:
    its compile takes tens of seconds)."""
    fn = pallas_walk.jitted_classify_walk(False)
    compile_for(fn, shapes(kernels._fixture_walk_tables(), one_chip),
                shapes(kernels._fixture_device_batch(1024), one_chip))
