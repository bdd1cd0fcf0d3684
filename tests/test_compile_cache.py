"""The persistent compile cache helper of the entry points.

``enable_compile_cache`` honours ``JAX_COMPILATION_CACHE_DIR`` and sets no
other directory; without it the cache lives at the fixed, git-ignored
``<repo>/.jax_cache``; importing ``repro`` sets no cache at all.
"""

import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.cache import REPO_ROOT, enable_compile_cache

_ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    """Restore the process's cache directory after the test."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_env_dir_is_honoured_and_nothing_else_set(monkeypatch, cache_config,
                                                  tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_and_git_ignored(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert REPO_ROOT == _ROOT
    assert path == str(_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert enable_compile_cache() == path          # the same on every call
    ignored = (_ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_import_sets_no_cache():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env["PYTHONPATH"] = str(_ROOT / "src")
    prog = ("import jax, repro, repro.launch.serve, repro.launch.cache; "
            "print(jax.config.jax_compilation_cache_dir)")
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, env=env, timeout=120)
    assert res.returncode == 0, res.stderr[-2000:]
    assert res.stdout.strip().splitlines()[-1] == "None"
