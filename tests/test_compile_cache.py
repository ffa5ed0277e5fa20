"""Where the persistent compilation cache goes (`launch.compile_cache`).

Each case runs in a fresh interpreter: JAX reads JAX_COMPILATION_CACHE_DIR
when it is imported, and the cache, once used, stays for the process.
"""
import os
import subprocess
import sys
import textwrap

from repro.launch import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, **env) -> str:
    full = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    full.update(PYTHONPATH=os.path.join(REPO, "src"), JAX_PLATFORMS="cpu",
                **env)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, timeout=300,
                         env=full, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout.strip()


def _listing(path: str):
    return sorted(os.listdir(path)) if os.path.isdir(path) else []


def test_env_dir_receives_entries_and_nothing_else(tmp_path):
    before = _listing(compile_cache.CHECKOUT_CACHE)
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache
        print(compile_cache.enable_compile_cache())
        jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64))).block_until_ready()
    """, JAX_COMPILATION_CACHE_DIR=str(tmp_path),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    assert out == str(tmp_path)
    assert os.listdir(tmp_path), "no cache entry written"
    assert _listing(compile_cache.CHECKOUT_CACHE) == before


def test_unset_env_uses_the_fixed_checkout_dir():
    out = _run("""
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache
        path = compile_cache.enable_compile_cache()
        print(path, jax.config.jax_compilation_cache_dir)
        jax.jit(lambda x: jnp.cos(x) @ x)(jnp.ones((64, 64))).block_until_ready()
    """, JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0")
    path, configured = out.split()
    assert path == configured == compile_cache.CHECKOUT_CACHE
    assert path == os.path.join(REPO, ".jax_cache")
    assert _listing(path), "no cache entry written"
