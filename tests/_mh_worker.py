"""Worker process for the 2-process multi-host integration test.

Launched by tests/test_multihost.py as `python _mh_worker.py <rank> <port>
<outdir>`: joins a real jax.distributed process group (Gloo collectives
over localhost — the CPU stand-in for DCN), builds the global
("data", "rules") mesh over 2 hosts x 4 virtual devices, classifies its
process-local half of a deterministic global batch against rules-sharded
tries, and writes its rows + stats for the parent to verify.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np

rank, port, outdir = int(sys.argv[1]), sys.argv[2], sys.argv[3]
os.environ["JAX_PLATFORMS"] = "cpu"
# preserve inherited XLA flags; replace only the device-count setting
_flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
          if "xla_force_host_platform_device_count" not in f]
os.environ["XLA_FLAGS"] = " ".join(
    _flags + ["--xla_force_host_platform_device_count=4"]
)

import jax

# the caller's environment may already have imported jax for another
# platform; force the CPU through the config as well as the env var
jax.config.update("jax_platforms", "cpu")

from infw import testing
from infw.parallel import multihost
from infw.parallel.mesh import shard_tables_trie

import _mh_params as mp

ok = multihost.init_distributed(f"localhost:{port}", 2, rank)
assert ok, "process group did not initialize"
assert len(jax.devices()) == 8 and jax.local_device_count() == 4, (
    jax.devices(), jax.local_device_count(),
)

rng = np.random.default_rng(mp.SEED)
tables = testing.random_tables(rng, n_entries=mp.N_ENTRIES, width=mp.WIDTH,
                               overlap_fraction=mp.OVERLAP)
batch = testing.random_batch(rng, tables, n_packets=mp.N_PACKETS)  # same on both ranks

mesh = multihost.make_global_mesh()  # data=2 (one shard per host) x rules=4
assert mesh.shape == {"data": 2, "rules": 4}
# every "rules" group must be contained in one process (ICI containment)
for row in mesh.devices:
    assert len({d.process_index for d in row}) == 1

lo, hi = multihost.process_local_rows(mesh, len(batch))
local = batch.slice(lo, hi)
placed = shard_tables_trie(tables, mesh)
res, xdp, stats = multihost.classify_multihost_trie(mesh, placed, local, len(batch))
np.savez(
    os.path.join(outdir, f"rank{rank}.npz"),
    res=res, xdp=xdp, stats=stats, lo=lo, hi=hi,
)
print(f"rank {rank} rows [{lo},{hi}) done", flush=True)
