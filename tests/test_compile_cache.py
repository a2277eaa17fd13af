"""The persistent compile cache's placement rule (`repro.compile_cache`)."""
from pathlib import Path

import jax

from repro.compile_cache import use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


def _record_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_env_var_set_leaves_the_cache_to_jax(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    calls = _record_updates(monkeypatch)
    assert use_compile_cache() == "/elsewhere/cache"
    assert calls == []


def test_default_is_one_fixed_ignored_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _record_updates(monkeypatch)
    path = str(ROOT / ".jax_cache")
    assert use_compile_cache() == path
    assert use_compile_cache() == path
    assert calls == [("jax_compilation_cache_dir", path)] * 2
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
