"""``repro.compile_cache``: one fixed cache directory per checkout."""
from pathlib import Path

import jax

from repro import compile_cache

REPO = Path(__file__).resolve().parents[1]


def test_enable_uses_env_dir_or_the_fixed_in_repo_dir(monkeypatch, tmp_path):
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.append((name, value)))
    # the environment names the directory: JAX reads it, nothing is set
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.enable() == str(tmp_path)
    assert updates == []
    # otherwise: <repo>/.jax_cache, the same path on every call, ignored
    # by git
    monkeypatch.delenv(compile_cache.ENV_VAR)
    first, second = compile_cache.enable(), compile_cache.enable()
    assert first == second == str(REPO / ".jax_cache")
    assert updates == [("jax_compilation_cache_dir", first)] * 2
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
