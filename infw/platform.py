"""Platform detection.

Equivalent of the reference's pkg/platform
(/root/reference/pkg/platform/platform.go): a probe of the running
environment whose result feeds the deployment render (the reference
probes the discovery API for the `route.openshift.io` group to decide
OpenShift vs vanilla k8s, :94-101, consumed at
ingressnodefirewallconfig_controller.go:138).  Here the meaningful
environment facts are the accelerator platform: which JAX backend is
live, the device kind, and how many chips are attached — consumed by the
daemon's ``--backend tpu`` launch check and by ``chip_smoke.py``.  Only a
process that is to hold the chip may probe it (the manager never does).

This module also places JAX's persistent compilation cache.
"""
from __future__ import annotations

import os
from dataclasses import dataclass
from typing import List, Optional


@dataclass(frozen=True)
class PlatformInfo:
    """PlatformInfo (pkg/platform/types.go)."""

    backend: str           # "tpu" | "cpu" | "gpu" | ...
    device_kind: str       # e.g. "TPU v5 lite"
    num_devices: int
    device_platforms: List[str]

    @property
    def is_tpu(self) -> bool:
        """The IsOpenShift() analogue: the capability bit deployment
        rendering branches on (types.go:32)."""
        return self.backend == "tpu"


def get_platform_info() -> PlatformInfo:
    """GetPlatformInfo (platform.go:34-104): probe the live JAX backend.
    Raises when JAX cannot bring one up — a failed probe must never read
    as a CPU host.  Probing initializes the backend, which then holds the
    accelerator for this process."""
    import jax

    devices = jax.devices()
    return PlatformInfo(
        backend=jax.default_backend(),
        device_kind=devices[0].device_kind,
        num_devices=len(devices),
        device_platforms=sorted({d.platform for d in devices}),
    )


#: the checkout's own compile-cache directory (listed in .gitignore)
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache when the backend is a
    TPU, so a restarted daemon or a repeated run reloads its executables
    instead of compiling them again.  The directory is
    ``$JAX_COMPILATION_CACHE_DIR`` when that is set (JAX reads it itself,
    and no other directory is set here), else ``<checkout>/.jax_cache``.
    Returns the directory in use, or None off the TPU."""
    import jax

    if jax.default_backend() != "tpu":
        return None
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = CHECKOUT_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every executable, however fast its compile was
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir
