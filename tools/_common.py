"""Shared arg/env bootstrap for the tools/ scripts.

Every script here used to copy-paste three things: the sys.path insert
that makes ``import infw`` work when run as ``python tools/<x>.py``, the
``on_tpu = jax.default_backend() == "tpu"`` + compile-cache preamble,
and ad-hoc ``argv[1]/argv[2]`` scale parsing.  One copy, imported as
``from _common import ...`` (the script's own directory is always on
sys.path when run as a script).
"""
from __future__ import annotations

import os
import sys
from typing import Optional, Tuple

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def repo_root() -> str:
    return _REPO_ROOT


def setup_repo_path() -> str:
    """Make ``import infw`` work from a script run in tools/."""
    if _REPO_ROOT not in sys.path:
        sys.path.insert(0, _REPO_ROOT)
    return _REPO_ROOT


def jax_setup() -> bool:
    """Import jax, enable the persistent compile cache on real TPU
    (infw.platform.enable_compile_cache), and return ``on_tpu``."""
    setup_repo_path()
    from infw.platform import enable_compile_cache

    return enable_compile_cache() is not None


def scale_args(
    argv,
    tpu_entries: int,
    cpu_entries: int,
    default_width: int = 8,
    on_tpu: Optional[bool] = None,
) -> Tuple[int, int]:
    """The profile scripts' common ``[n_entries] [width]`` positional
    parsing with backend-dependent defaults."""
    if on_tpu is None:
        on_tpu = jax_setup()
    n_entries = (
        int(argv[1]) if len(argv) > 1 else (tpu_entries if on_tpu else cpu_entries)
    )
    width = int(argv[2]) if len(argv) > 2 else default_width
    return n_entries, width
