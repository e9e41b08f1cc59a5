"""The persistent compilation cache's path rule (``repro.compile_cache``)."""
import subprocess
import sys
import tempfile
from pathlib import Path

import jax
import pytest

from repro.compile_cache import ENV_VAR, compile_cache_dir, enable_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def config_updates(monkeypatch):
    """Record ``jax.config.update`` calls instead of applying them, so the
    test process never turns the cache on."""
    calls = []
    monkeypatch.setattr(jax.config, "update", lambda *a: calls.append(a))
    return calls


def test_env_set_is_left_to_jax(monkeypatch, config_updates):
    monkeypatch.setenv(ENV_VAR, "/srv/jax-cache")
    assert compile_cache_dir() == ("/srv/jax-cache", True)
    assert enable_compile_cache() == "/srv/jax-cache"
    assert config_updates == []


def test_unset_goes_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv(ENV_VAR, raising=False)
    want = str(ROOT / ".jax_cache")
    assert compile_cache_dir() == (want, False)
    assert enable_compile_cache() == want
    assert config_updates == [("jax_compilation_cache_dir", want)]


def test_unset_path_is_fixed(monkeypatch):
    """The path is part of the cache key: never a temp name, pid or time."""
    monkeypatch.delenv(ENV_VAR, raising=False)
    first, _ = compile_cache_dir()
    assert compile_cache_dir()[0] == first
    assert not first.startswith(tempfile.gettempdir())
    assert compile_cache_dir({})[0] == first


def test_import_sets_no_cache():
    """Importing the library leaves the cache off; only entry points
    call ``enable_compile_cache``."""
    code = ("import jax, repro.api, repro.core, repro.serve, "
            "repro.compile_cache; print(jax.config.jax_compilation_cache_dir)")
    env = {"PYTHONPATH": str(ROOT / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-c", code], env=env, cwd=ROOT,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"
