"""The persistent compilation cache lands where the entry points put it:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<repo>/.jax_cache``. Each case
runs in a fresh interpreter, since the cache directory is process state."""

import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

_CODE = r"""
import sys
import jax, jax.numpy as jnp
from repro.utils.compile_cache import enable_compile_cache
where = enable_compile_cache()
print(where)
print(jax.config.jax_compilation_cache_dir)
if sys.argv[1] == "compile":
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.jit(lambda x: jnp.sin(x) * 2)(jnp.ones(3)).block_until_ready()
"""


def _run(mode: str, cache_dir: str | None) -> list[str]:
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"), JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = cache_dir
    out = subprocess.run([sys.executable, "-c", _CODE, mode], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    return out.stdout.split()


def test_cache_goes_where_the_env_says(tmp_path):
    where = tmp_path / "jax-cache"
    helper_dir, jax_dir = _run("compile", str(where))
    assert helper_dir == jax_dir == str(where)
    assert any(where.iterdir()), "no cache entry written"


def test_cache_defaults_to_the_repo():
    from repro.utils.compile_cache import DEFAULT_CACHE_DIR
    assert DEFAULT_CACHE_DIR == ROOT / ".jax_cache"
    helper_dir, jax_dir = _run("configure", None)
    assert helper_dir == jax_dir == str(ROOT / ".jax_cache")
