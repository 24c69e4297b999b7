"""Decisions the platform makes in one place (``core/platform.py``): whether
Pallas kernels run interpreted, and where the persistent compile cache
lives."""
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from repro.core import platform


@pytest.mark.parametrize("kind,interpret", [("cpu", True), ("gpu", True),
                                            ("tpu", False)])
def test_pallas_interpret_follows_the_device_platform(kind, interpret):
    assert platform.pallas_interpret(SimpleNamespace(platform=kind)) is \
        interpret


def test_pallas_interpret_defaults_to_the_first_device():
    assert platform.pallas_interpret() is (
        jax.devices()[0].platform != "tpu")


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_uses_the_environment_when_set(
        monkeypatch, tmp_path, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert platform.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set


def test_compile_cache_defaults_to_the_checkout(monkeypatch,
                                                cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = platform.enable_compile_cache()
    repo = Path(__file__).resolve().parents[1]
    assert path == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
