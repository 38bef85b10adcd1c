"""Where ``enable_compile_cache`` puts the persistent compilation cache.

Each case compiles in a fresh interpreter (the cache directory is fixed
at a process's first compile) and lists the directories afterwards.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]

PROGRAM = textwrap.dedent("""
    import sys
    from pathlib import Path
    import jax, jax.numpy as jnp
    from repro.launch import compile_cache
    compile_cache.REPO_CACHE_DIR = Path(sys.argv[1])
    print(compile_cache.enable_compile_cache())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.block_until_ready(jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((8, 8))))
""")


def test_default_dir_is_the_ignored_repo_dir():
    assert compile_cache.REPO_CACHE_DIR == REPO / ".jax_cache"
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_land_in_one_dir(tmp_path, env_set):
    repo_dir, env_dir = tmp_path / "repo_cache", tmp_path / "env_cache"
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=str(REPO / "src"))
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run([sys.executable, "-c", PROGRAM, str(repo_dir)],
                         env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    used, unused = (env_dir, repo_dir) if env_set else (repo_dir, env_dir)
    assert out.stdout.split()[0] == str(used)
    assert any(p.name.endswith("-cache") for p in used.iterdir())
    assert not unused.exists()
