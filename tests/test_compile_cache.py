"""Where the persistent compilation cache goes: ``JAX_COMPILATION_CACHE_DIR``
when it is set, else the fixed ``.jax_cache/`` at the repository root.
Each case runs in a child process, because JAX reads the variable when it
is imported and keeps a cache once it has opened one."""
import os
import subprocess
import sys
from pathlib import Path

from repro.runtime import compile_cache

ROOT = Path(__file__).resolve().parents[1]

_CHILD = """
import sys
import jax, jax.numpy as jnp
from repro.runtime.compile_cache import use_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
print(use_compile_cache(sys.argv[1]))
jax.jit(lambda x: x * 3 + 1)(jnp.arange(8.0)).block_until_ready()
"""


def _run(default_dir, env_dir=None):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT / "src")] + sys.path))
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", _CHILD, str(default_dir)],
                         env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    return out.stdout.strip().splitlines()[-1]


def _files(d: Path):
    return [p for p in d.rglob("*") if p.is_file()] if d.exists() else []


def test_env_variable_wins(tmp_path):
    env_dir, default = tmp_path / "from_env", tmp_path / "default"
    assert _run(default, env_dir) == str(env_dir)
    assert _files(env_dir) and not _files(default)


def test_default_directory_without_env(tmp_path):
    default = tmp_path / "default"
    assert _run(default) == str(default)
    assert _files(default)


def test_default_is_the_ignored_repo_directory():
    assert compile_cache.DEFAULT_DIR == ROOT / ".jax_cache"
    assert ".jax_cache/" in (ROOT / ".gitignore").read_text().split()
