"""Shared setup for the benchmark scripts."""



def configure_jax():
    """Enable the persistent compile cache (``JAX_COMPILATION_CACHE_DIR``
    where set, else ``<checkout>/.jax_cache``) so repeated bench runs
    skip their compiles. Call before any jax computation."""
    import jax

    from quiver_tpu.utils.compile_cache import place_compile_cache
    place_compile_cache()
    return jax
