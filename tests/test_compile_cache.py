"""The persistent compilation cache is placed from outside, or at a fixed
path in the checkout — never on ``import repro``."""

import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache as cc

    saved = (jax.config.jax_enable_compilation_cache,
             jax.config.jax_compilation_cache_dir)
    yield
    jax.config.update("jax_enable_compilation_cache", saved[0])
    jax.config.update("jax_compilation_cache_dir", saved[1])
    cc.reset_cache()


def test_cache_follows_environment(monkeypatch, tmp_path, restore_cache_config):
    monkeypatch.setenv(compile_cache.ENV_DIR, str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    assert jax.config.jax_enable_compilation_cache


def test_cache_defaults_to_fixed_checkout_path(monkeypatch, restore_cache_config):
    monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable_compile_cache() == path  # same every call


def test_import_repro_sets_no_cache():
    code = (
        "import jax, repro, repro.core, repro.checkpoint, repro.serving;"
        "print(jax.config.jax_compilation_cache_dir)"
    )
    env = {"PYTHONPATH": str(REPO / "src"), "JAX_PLATFORMS": "cpu",
           "PATH": "/usr/bin:/bin"}
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "None"
