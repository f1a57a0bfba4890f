import os

import jax
import pytest


@pytest.fixture
def no_disk_cache(monkeypatch):
    """Runs of the harness here keep JAX's persistent cache off, and leave
    the cache settings as they found them."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", os.devnull)
    names = ("jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in was.items():
        jax.config.update(n, v)
