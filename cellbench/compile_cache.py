"""Where the compile cache goes, for every program: where the system under
test's own ``utils/compile_cache`` puts it, ``$JAX_COMPILATION_CACHE_DIR``
or ``<checkout>/.jax_cache``, a fixed path inside the checkout.  With
``programs/`` and ``scope_split.py`` the third place under ``cellbench/``
that imports from ``quiver_tpu``: a path, no code under test."""


def cache_dir():
    from quiver_tpu.utils import compile_cache

    return compile_cache.enable()
