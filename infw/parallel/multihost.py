"""Multi-host distribution: the DCN half of the parallelism story.

The reference scales out with a DaemonSet — one dataplane per node, state
fanned out through the Kubernetes API, zero cross-node traffic in the hot
path (/root/reference/bindata/manifests/daemon/daemonset.yaml:1-24,
controllers/ingressnodefirewallnodestate_controller.go:62-64).  The
TPU-native equivalent is a JAX multi-process job:

- **process group**: one daemon process per host, joined through
  ``jax.distributed.initialize`` (coordinator address + process id — the
  role the API server's watch connections play for the DaemonSet).
- **mesh layout**: the global ("data", "rules") mesh is built so the
  "rules" axis — which carries the per-packet pmax/psum winner combine of
  parallel.mesh — always lies WITHIN one host's devices (ICI), and only
  the "data" axis crosses hosts (DCN).  Per-packet combines never leave
  the host; the only cross-host collective is the final per-batch stats
  psum, a (1024, 6) int32 — the scaling-book recipe of keeping
  bandwidth-bound collectives on ICI.
- **ingest**: each host parses ITS OWN traffic (its NIC, its frames
  files) and contributes the process-local shard of the global batch via
  ``jax.make_array_from_process_local_data`` — exactly the DaemonSet
  posture where each node classifies only the packets that arrived on it.
- **rule broadcast**: every host compiles the same ruleset (desired state
  is replicated through the control plane, as NodeState CRs are) and
  places its table shards on its local devices.

Single-process validation: all of this degrades to the virtual CPU mesh
(process_count == 1) where the same code paths — global mesh, local-data
assembly, sharded classify — run end to end; the driver's
dryrun_multichip exercises them without multi-host hardware.
"""
from __future__ import annotations

import logging
import os
from typing import Optional, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..packets import PacketBatch
from ..kernels.jaxpath import DeviceBatch

log = logging.getLogger("infw.parallel.multihost")


def init_distributed(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> bool:
    """Join the process group.  Env contract (mirroring the daemon's
    NODE_NAME-style env wiring, cmd/daemon/daemon.go:69-84):

        INFW_COORDINATOR    host:port of process 0
        INFW_NUM_PROCESSES  total daemon processes
        INFW_PROCESS_ID     this process's rank

    Explicit arguments override env.  Returns True if a multi-process
    group was initialized, False for the single-process (no-op) case —
    callers proceed identically either way; ``jax.devices()`` simply spans
    all hosts afterwards."""
    coord = coordinator_address or os.environ.get("INFW_COORDINATOR", "")
    n = num_processes if num_processes is not None else int(
        os.environ.get("INFW_NUM_PROCESSES", "1")
    )
    pid = process_id if process_id is not None else int(
        os.environ.get("INFW_PROCESS_ID", "0")
    )
    if not coord or n <= 1:
        log.info("single-process mode (no coordinator configured)")
        return False
    # NOTE: must not touch jax.devices()/default_backend() here — backend
    # initialization before distributed.initialize would pin the process
    # to its local devices only.  Read the platform from config/env.
    platforms = (
        getattr(jax.config, "jax_platforms", None)
        or os.environ.get("JAX_PLATFORMS", "")
        or ""
    )
    if platforms.startswith("cpu"):
        # cross-process collectives on the CPU backend (Gloo, the stand-in
        # for DCN in the 2-process tests and the compose multi-host dryrun)
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(
        coordinator_address=coord, num_processes=n, process_id=pid
    )
    log.info(
        "joined process group: rank %d/%d via %s (%d global devices)",
        pid, n, coord, len(jax.devices()),
    )
    return True


def make_global_mesh(rules_shards: Optional[int] = None) -> Mesh:
    """("data", "rules") mesh over all global devices with the "rules"
    axis contained inside each process's local devices, so the per-packet
    winner combine (pmax/psum over "rules") rides ICI and only the "data"
    axis — which needs no per-packet collective — crosses DCN.

    ``rules_shards`` defaults to all of one host's local devices (max
    rules capacity per packet-shard); it must divide the local device
    count to preserve host containment."""
    from .mesh import validate_mesh_axes

    devices = jax.devices()
    local = jax.local_device_count()
    shards = rules_shards or local
    # Same rule set (and wording) as parallel.mesh.make_mesh, applied to
    # the LOCAL device count: the rules axis must fit within, and divide,
    # one host's devices so the per-packet combine stays on ICI.
    validate_mesh_axes(local, shards, local, what="local devices (ICI)")
    # Global devices ordered process-major: rows of the mesh fill one
    # host's devices before moving to the next, keeping each "rules" group
    # process-local.
    arr = np.array(devices).reshape(len(devices) // shards, shards)
    return Mesh(arr, ("data", "rules"))


def process_local_rows(mesh: Mesh, n_global: int) -> Tuple[int, int]:
    """The [start, stop) slice of the global batch this process feeds —
    its share of the "data" axis (its own NIC's packets)."""
    data_shards = mesh.shape["data"]
    rows_per_shard = n_global // data_shards
    mine = [
        i for i in range(data_shards)
        if mesh.devices[i, 0].process_index == jax.process_index()
    ]
    if not mine:
        return 0, 0
    return mine[0] * rows_per_shard, (mine[-1] + 1) * rows_per_shard


def global_batch_from_local(
    mesh: Mesh, local_batch: PacketBatch, n_global: int
) -> DeviceBatch:
    """Assemble the globally "data"-sharded DeviceBatch from each
    process's local packets (jax.make_array_from_process_local_data —
    the multi-host replacement of parallel.mesh.shard_batch, which
    device_puts a fully host-resident batch).  ``n_global`` must be a
    multiple of the data-shard count and equal sum of local sizes across
    processes; in single-process mode the local batch IS the global
    batch."""

    def put(a: np.ndarray, spec) -> jax.Array:
        sharding = NamedSharding(mesh, spec)
        global_shape = (n_global,) + a.shape[1:]
        return jax.make_array_from_process_local_data(sharding, a, global_shape)

    return DeviceBatch(
        kind=put(np.asarray(local_batch.kind), P("data")),
        l4_ok=put(np.asarray(local_batch.l4_ok), P("data")),
        ifindex=put(np.asarray(local_batch.ifindex), P("data")),
        ip_words=put(
            np.asarray(local_batch.ip_words, np.uint32), P("data", None)
        ),
        proto=put(np.asarray(local_batch.proto), P("data")),
        dst_port=put(np.asarray(local_batch.dst_port), P("data")),
        icmp_type=put(np.asarray(local_batch.icmp_type), P("data")),
        icmp_code=put(np.asarray(local_batch.icmp_code), P("data")),
        pkt_len=put(np.asarray(local_batch.pkt_len), P("data")),
    )


def classify_multihost_trie(
    mesh: Mesh,
    placed,
    local_batch: PacketBatch,
    n_global: Optional[int] = None,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The multi-host forward step: this process contributes its local
    packets, the mesh classifies the global batch against the
    rules-sharded tries (parallel.mesh.shard_tables_trie handle), and the
    process reads back ONLY its own rows (results stay "data"-sharded;
    addressable shards are local).  Stats come back fully replicated —
    the one DCN collective.

    ``placed`` is the ShardedTrieTables from shard_tables_trie(mesh) —
    compile/place once per ruleset, stream batches against it.  Tail
    chunks of arbitrary length are fine: every process pads its local
    slice to the per-shard row count (all processes must still agree on
    the padded local length — they do when local batches are equal-sized,
    the steady state of symmetric ingest)."""
    from .mesh import make_sharded_trie_classifier

    data_shards = mesh.shape["data"]
    local_shards = max(
        sum(
            1 for i in range(data_shards)
            if mesh.devices[i, 0].process_index == jax.process_index()
        ),
        1,
    )
    b = len(local_batch)
    bp = ((b + local_shards - 1) // local_shards) * local_shards
    local_padded = local_batch.pad_to(bp)
    n = n_global if n_global is not None else bp * (data_shards // local_shards)
    db = global_batch_from_local(mesh, local_padded, n)
    results, xdp, stats = make_sharded_trie_classifier(
        mesh, len(placed.trie_levels)
    )(placed, db)

    def local_rows(garr: jax.Array) -> np.ndarray:
        # One addressable shard per device: the 4 "rules"-axis replicas of
        # each data shard all appear — dedupe by row slice before
        # concatenating in row order.
        by_start = {}
        for s in garr.addressable_shards:
            by_start.setdefault(s.index[0].start or 0, s)
        return np.concatenate(
            [np.asarray(by_start[k].data) for k in sorted(by_start)]
        )

    return local_rows(results)[:b], local_rows(xdp)[:b], np.asarray(stats)
