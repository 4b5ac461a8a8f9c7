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


def length_groups(rows: int, lengths) -> "list[tuple[int, int]]":
    """-> [(length, rows of that length), ...], longest first, from a
    traffic file's `rows` and `lengths` (one length, or [[length, share],
    ...]). Shares fix the counts (what rounding leaves goes to the first
    length listed), so every seed scores the same sizes; the seed draws
    what the rows hold."""
    if isinstance(lengths, (int, float)):
        return [(int(lengths), int(rows))]
    total = sum(share for _length, share in lengths)
    counts = [int(rows * share / total) for _length, share in lengths]
    counts[0] += int(rows) - sum(counts)
    return sorted(((int(length), n) for (length, _s), n
                   in zip(lengths, counts) if n), reverse=True)
