import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]

SMALL_ROWS = 1 << 14


@pytest.fixture
def no_compile_cache():
    """Keep the rehearsals' CPU programs out of the persistent
    compilation cache in the checkout, and restore JAX's settings."""
    import jax
    saved = {k: jax.config.values[k] for k in (
        "jax_enable_compilation_cache", "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def small(config_name: str) -> dict:
    """A configuration of the benchmark at SMALL_ROWS page_views rows."""
    import harness
    config = harness.config_of(harness.benchmark(), config_name)
    return dict(config, page_views_rows=SMALL_ROWS)
