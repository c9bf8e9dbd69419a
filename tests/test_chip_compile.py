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
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from infw import kernels, testing
from infw.daemon import DEFAULT_INGEST_CHUNK
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
    flow columns and epoch donated."""
    cfg, flow, gens, pages, epoch, max_age, zeros = (
        kernels._resident_operands(1024)
    )
    fn = jaxpath.jitted_resident_step(
        cfg.entries, cfg.ways, "trie", False, None, 0, False
    )
    args = (flow, gens, pages, epoch, kernels._fixture_device_tables(True),
            kernels._fixture_wire(1024), zeros, zeros, max_age)
    compile_for(fn, *shapes(args, one_chip))


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
