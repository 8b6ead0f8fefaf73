"""Where the program runs and where it keeps its compile cache.

The platform is whatever JAX reports (``JAX_PLATFORMS=cpu`` is how a
caller asks for the host), and the persistent compile cache lives at
``$JAX_COMPILATION_CACHE_DIR`` when that is set, else at the fixed
``.jax_cache/`` of the checkout.
"""
import os
import subprocess
import sys

import jax
import pytest

from consensus_specs_tpu.utils import bls, jax_env

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_config(monkeypatch):
    """setup_compile_cache pushes into the live jax.config: put the
    session's cache directory back afterwards."""
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       os.environ.get("JAX_COMPILATION_CACHE_DIR", ""))
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_dir", [None, "outside"])
def test_compile_cache_dir(tmp_path, monkeypatch, restore_cache_config,
                           env_dir):
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(_REPO, ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    assert jax_env.cache_dir() == want
    assert jax_env.setup_compile_cache() == want
    assert os.environ["JAX_COMPILATION_CACHE_DIR"] == want
    assert jax.config.jax_compilation_cache_dir == want


@pytest.mark.parametrize("platform,want", [
    ("tpu", "jax"), ("cpu", "native")])
def test_use_fastest_follows_jax_platform(monkeypatch, platform, want):
    from consensus_specs_tpu.ops import native_bls
    if want == "native" and not native_bls.available():
        want = "py"
    monkeypatch.setattr(jax, "default_backend", lambda: platform)
    try:
        bls.use_fastest()
        assert bls.backend_name() == want
    finally:
        bls.use_py()


def test_chip_smoke_refuses_cpu():
    """No silent CPU run: on the host platform the smoke exits nonzero
    and prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                          env=env, capture_output=True, timeout=300)
    assert proc.returncode != 0
    assert b'"ok"' not in proc.stdout
    assert b"no TPU" in proc.stderr


def test_chip_smoke_needs_the_repo(tmp_path):
    """A lone copy of the script is not the system: it must fail."""
    lone = tmp_path / "chip_smoke.py"
    lone.write_bytes(open(os.path.join(_REPO, "chip_smoke.py"), "rb").read())
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, str(lone)], cwd=str(tmp_path),
                          env=env, capture_output=True, timeout=300)
    assert proc.returncode != 0
    assert b'"ok"' not in proc.stdout
