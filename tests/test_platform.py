"""Where the compile cache goes, how Pallas picks interpret mode, and the
native reference library's source-hash build key."""
import hashlib
import os

import jax
import pytest

from infw import platform
from infw.kernels import pallas_dense


@pytest.fixture
def cache_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_compile_cache_directory(tmp_path, monkeypatch, cache_config,
                                 env_dir):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = platform.CHECKOUT_CACHE_DIR
        assert want == os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(
                platform.__file__))), ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
        jax.config.update("jax_compilation_cache_dir", want)
    assert platform.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_no_compile_cache_off_the_tpu(cache_config):
    before = jax.config.jax_compilation_cache_dir
    assert platform.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("backend, want", [("cpu", True), ("tpu", False),
                                           ("gpu", None)])
def test_interpret_mode_only_on_the_cpu(monkeypatch, backend, want):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if want is None:
        with pytest.raises(RuntimeError):
            pallas_dense.default_interpret()
    else:
        assert pallas_dense.default_interpret() is want


def test_native_library_keyed_on_source_hash():
    from infw.backend import cpu_ref

    with open(cpu_ref._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    path = cpu_ref._build_library()
    assert os.path.basename(path) == f"libinfwref-{digest}.so"
    assert os.path.exists(path)
