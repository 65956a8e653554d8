"""Entry-point plumbing: the compile-cache rule and ``chip_smoke.py``'s
refusal to report success without a TPU."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.launch import compile_cache

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no directory of its own
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_defaults_to_fixed_ignored_path(monkeypatch,
                                                       restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.compile_cache_dir() == want
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("where", ["repo", "script_alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    """On the CPU — in the checkout, or copied out of it — the smoke run
    exits non-zero and never prints its ``ok`` line."""
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "script_alone":
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=120,
                       cwd=tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
