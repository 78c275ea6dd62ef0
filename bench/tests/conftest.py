import pytest


@pytest.fixture
def own_compile_cache():
    """The harness turns JAX's persistent compilation cache on for the
    whole process, in the tree it runs from (a test's temporary one); put
    the previous settings back afterwards."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    names = ("jax_compilation_cache_dir", "jax_compilation_cache_max_size",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    prev = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in prev.items():
        jax.config.update(n, v)
    compilation_cache.reset_cache()
