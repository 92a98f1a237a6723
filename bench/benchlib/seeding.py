"""One seed, many independent streams.

``--seed`` may be any whole number up to a little over 2**31, more than a
signed 32-bit integer holds, so it is hashed into 32-bit words before it
reaches JAX."""

from __future__ import annotations

import numpy as np

STREAMS = {"weights": 1, "traffic": 2, "inputs": 3, "sample": 4,
           "engine": 5}


def words(seed: int, stream: str) -> tuple[int, int]:
    """Two non-negative 31-bit words for ``(seed, stream)``."""
    ss = np.random.SeedSequence([int(seed) & (2**64 - 1), STREAMS[stream]])
    a, b = ss.generate_state(2, np.uint32)
    return int(a) >> 1, int(b) >> 1


def rng(seed: int, stream: str) -> np.random.Generator:
    return np.random.default_rng(words(seed, stream))


def key(seed: int, stream: str):
    import jax
    a, b = words(seed, stream)
    return jax.random.fold_in(jax.random.PRNGKey(a), b)
