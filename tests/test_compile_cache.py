"""Where `enable_compile_cache` puts JAX's persistent compilation cache.

Each case runs in a fresh process (the cache is set up once per process)
on a copy of `compile_cache.py` placed in a stand-in checkout, so no
entry lands in the real one.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src" / "repro" / "launch"

SCRIPT = """
import importlib.util, sys
import jax, jax.numpy as jnp
spec = importlib.util.spec_from_file_location("cc", sys.argv[1])
cc = importlib.util.module_from_spec(spec)
spec.loader.exec_module(cc)
print(cc.enable_compile_cache())
jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
"""


@pytest.mark.parametrize("env_set", [True, False])
def test_cache_entries_land_in_one_dir(tmp_path, env_set):
    checkout = tmp_path / "checkout"
    launch = checkout / "src" / "repro" / "launch"
    launch.mkdir(parents=True)
    shutil.copy(SRC / "compile_cache.py", launch)
    env_dir = tmp_path / "env_cache"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if env_set:
        env["JAX_COMPILATION_CACHE_DIR"] = str(env_dir)
    out = subprocess.run(
        [sys.executable, "-c", SCRIPT, str(launch / "compile_cache.py")],
        env=env, capture_output=True, text=True, timeout=120, check=True)
    want, other = ((env_dir, checkout / ".jax_cache") if env_set
                   else (checkout / ".jax_cache", env_dir))
    assert out.stdout.strip() == str(want)
    assert any(want.iterdir())
    assert not other.exists()
