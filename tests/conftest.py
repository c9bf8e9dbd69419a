"""Test configuration.

Forces JAX onto a virtual 8-device CPU mesh so multi-chip sharding paths
compile and execute in CI without TPU hardware (the driver separately
dry-runs the multichip path via __graft_entry__.dryrun_multichip).

INFW_TPU_E2E=1 keeps the REAL device platform instead — used to run the
e2e reachability tables (and any other gated tests) against the actual
TPU dataplane, the analogue of pointing the reference's functional suite
at a live cluster instead of envtest.
"""
import os

_KEEP_DEVICE = os.environ.get("INFW_TPU_E2E") == "1"

# Force, don't setdefault: tests run on the virtual CPU mesh whatever
# platform the environment names.  jax may already be imported by the
# time this runs, so the env var alone can be too late — update the
# config as well.
if not _KEEP_DEVICE:
    os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

if not _KEEP_DEVICE:
    jax.config.update("jax_platforms", "cpu")

import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
