"""Platform resolution and compile-cache placement
(`gsky_tpu.device`): the CPU only when asked for, never as a
fallback; the cache directory left to jax when the environment names
one, else a fixed path — and none on the CPU."""

import os

import jax
import pytest

from gsky_tpu import device


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    """Unresolved module state, and jax's cache config put back."""
    monkeypatch.setattr(device, "_resolved", None)
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_entry_size_bytes",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_no_tpu_and_not_told_cpu_raises(monkeypatch):
    # this process runs on the CPU (tests/conftest.py); without the
    # instruction in the environment that is an error, not a fallback
    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(device.PlatformError, match="'cpu'"):
        device.ensure_platform()


def test_told_cpu(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    plat = device.ensure_platform()
    assert plat["platform"] == "cpu"
    assert plat["device_count"] == len(jax.devices())
    assert plat["cache_dir"] is None
    assert device.ensure_platform() is plat       # resolved once


def test_cache_dir_is_left_to_jax_when_the_environment_names_one(
        monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    device._place_compilation_cache("tpu")
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1


def test_cache_dir_is_a_fixed_path_on_a_tpu(monkeypatch, tmp_path):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(device, "DEFAULT_CACHE_DIR",
                        str(tmp_path / ".jax_cache"))
    assert device._place_compilation_cache("tpu") == \
        str(tmp_path / ".jax_cache")
    assert (tmp_path / ".jax_cache").is_dir()


def test_default_cache_dir_is_inside_the_checkout():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert os.path.dirname(device.DEFAULT_CACHE_DIR) == repo
