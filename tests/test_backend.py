"""The data plane's backend choice and compile-cache placement
(``repro.kernels.backend``)."""
import os

import jax
import pytest

from repro.core import analytics
from repro.kernels import backend


@pytest.mark.parametrize("platform,on_device,interpret", [
    ("cpu", False, True),
    ("tpu", True, False),
])
def test_supported_platforms(monkeypatch, platform, on_device, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    assert backend.platform() == platform
    assert backend.on_device() is on_device
    assert backend.interpret() is interpret
    assert backend.interpret(not interpret) is (not interpret)
    assert analytics._use_kernel() is on_device


@pytest.mark.parametrize("platform", ["gpu", "cuda", "rocm", "METAL"])
def test_other_platforms_raise(monkeypatch, platform):
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    for ask in (backend.platform, backend.on_device, backend.interpret,
                analytics._use_kernel):
        with pytest.raises(RuntimeError, match=platform):
            ask()
    # an explicit choice names no platform and needs none
    assert backend.interpret(True) is True


@pytest.mark.parametrize("env_dir", [None, "cache-from-env"])
def test_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    from jax.experimental.compilation_cache import compilation_cache

    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    was = jax.config.jax_compilation_cache_dir
    try:
        assert backend.use_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        compilation_cache.reset_cache()
