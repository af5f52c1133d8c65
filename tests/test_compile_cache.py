"""The persistent compilation cache has one home: ``JAX_COMPILATION_CACHE_DIR``
when it is set, else the fixed ``.jax_cache/`` at the checkout root."""
import os
import subprocess
import sys

import jax

from repro import compile_cache

ROOT = os.path.join(os.path.dirname(__file__), "..")


def test_unset_env_uses_the_fixed_checkout_dir(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = compile_cache.configure_compile_cache()
        assert path == compile_cache.DEFAULT_DIR
        assert jax.config.jax_compilation_cache_dir == path
        assert path == os.path.join(os.path.abspath(ROOT), ".jax_cache")
        # called again (another entry point), the same directory
        assert compile_cache.configure_compile_cache() == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_receives_the_cache_and_nothing_else(tmp_path):
    """A fresh process with the variable set writes its compiles there,
    and the helper sets no directory of its own."""
    cache = tmp_path / "cache"
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        PYTHONPATH=os.path.join(ROOT, "src"),
        JAX_COMPILATION_CACHE_DIR=str(cache),
        # cache even a tiny compile
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
    )
    script = (
        "import jax\n"
        "from repro.compile_cache import configure_compile_cache\n"
        "print(configure_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
        "jax.jit(lambda x: x * 2 + 1)(3.0).block_until_ready()\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", script], env=env, capture_output=True,
        text=True, timeout=120, cwd=str(tmp_path),
    )
    assert out.returncode == 0, out.stderr
    assert out.stdout.split() == [str(cache), str(cache)]
    assert any(cache.iterdir())
