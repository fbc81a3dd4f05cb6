"""PRNG key helper.

Default is threefry2x32 everywhere: reproducible streams, and the hot
sampler bypasses per-draw key RNG entirely via ``sample_rng="hash"``
(counter-hash uniforms, ``ops/sample.py``), so keys only feed cheap
split/fold_in.  threefry against rbg on the chip: not measured.

The reference's analogue is per-thread curand Philox
(``cuda_random.cu.hpp:12-20``) — likewise a counter hash.
"""

from __future__ import annotations

__all__ = ["make_key", "default_impl"]


def default_impl() -> str:
    """Default PRNG impl; ``QUIVER_TPU_PRNG`` overrides."""
    import os

    return os.environ.get("QUIVER_TPU_PRNG") or "threefry2x32"


def make_key(seed: int = 0, impl: str | None = None):
    """A ``jax.random`` key using the backend-appropriate implementation.

    Pass ``impl="threefry2x32"`` to force reproducible keys on TPU, or set
    ``QUIVER_TPU_PRNG=threefry2x32|rbg`` to override globally.
    """
    import jax

    return jax.random.key(seed, impl=impl or default_impl())
