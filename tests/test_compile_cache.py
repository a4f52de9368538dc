"""Where the persistent compile cache goes: ``JAX_COMPILATION_CACHE_DIR`` when
it is set, the checkout's ``.jax_cache/`` otherwise, and nowhere on CPU.

``enable`` configures once per process, so each test resets its latch and
records the config updates instead of applying them."""
import jax
import pytest

from repro import compile_cache


@pytest.fixture
def updates(monkeypatch):
    seen = {}
    monkeypatch.setattr(compile_cache, "_enabled", False)
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: seen.__setitem__(name, value))
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.delenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS",
                       raising=False)
    return seen


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def test_cpu_backend_configures_nothing(updates):
    compile_cache.enable()
    assert updates == {}


def test_default_cache_is_the_checkouts_jax_cache(updates, on_tpu):
    compile_cache.enable()
    assert updates["jax_compilation_cache_dir"] == str(compile_cache.CACHE_DIR)
    assert compile_cache.CACHE_DIR.name == ".jax_cache"
    assert (compile_cache.CACHE_DIR.parent / "pyproject.toml").exists()
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_env_cache_dir_is_left_to_jax(updates, on_tpu, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    monkeypatch.setenv("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "2")
    compile_cache.enable()
    assert updates == {}


def test_enable_configures_once(updates, on_tpu):
    compile_cache.enable()
    updates.clear()
    compile_cache.enable()
    assert updates == {}
