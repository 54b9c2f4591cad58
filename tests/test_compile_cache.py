"""Where the persistent compile cache goes (utils/compile_cache.py):
one function decides, and the three cases are pinned here — placed
from outside, the fixed in-checkout default, and off on the CPU."""

import os
import sys
import types

import pytest

from uda_tpu.utils import compile_cache


class _FakeConfig:
    """Stands in for jax.config: records update() calls."""

    def __init__(self):
        self.jax_platforms = None
        self.updates = {}

    def update(self, name, value):
        self.updates[name] = value


@pytest.fixture
def fresh(monkeypatch):
    """compile_cache as a new process would see it, against a jax whose
    config only records what is asked of it."""
    cfg = _FakeConfig()
    monkeypatch.setattr(compile_cache, "_enabled", False)
    monkeypatch.setitem(sys.modules, "jax",
                        types.SimpleNamespace(config=cfg))
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    return cfg


def test_env_dir_is_left_to_jax(fresh, monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path / "cc"))
    assert compile_cache.enable() is True
    assert "jax_compilation_cache_dir" not in fresh.updates
    assert not (tmp_path / "cc").exists()   # JAX's to create, not ours
    assert compile_cache.cache_dir() == str(tmp_path / "cc")
    # what gets cached is still ours to say
    assert fresh.updates["jax_persistent_cache_min_compile_time_secs"] == 0


def test_unset_uses_fixed_checkout_path(fresh, monkeypatch):
    made = []
    monkeypatch.setattr(os, "makedirs",
                        lambda d, exist_ok=False: made.append(d))
    assert compile_cache.enable() is True
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    want = os.path.join(repo, ".jax_cache")
    assert fresh.updates["jax_compilation_cache_dir"] == want
    assert made == [want]
    assert compile_cache.cache_dir() == want
    # idempotent: a second call touches nothing
    fresh.updates.clear()
    assert compile_cache.enable() is True
    assert fresh.updates == {}


@pytest.mark.parametrize("via", ["env", "config"])
def test_cpu_is_off(fresh, monkeypatch, via):
    if via == "env":
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    else:
        fresh.jax_platforms = "cpu"
    assert compile_cache.enable() is False
    assert fresh.updates == {}

