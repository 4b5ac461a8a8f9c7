"""Seeded inputs. The same seed gives the same bytes; every seed gives the
same sizes."""

from __future__ import annotations

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def device_key(seed: int, stream: int):
    """A JAX key that holds every bit of a seed of up to 64 bits."""
    import jax

    seed = int(seed)
    words = np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF],
                     np.uint32)
    return jax.random.fold_in(
        jax.random.wrap_key_data(words, impl="threefry2x32"), int(stream))
