"""``--seed`` is any whole number, wider than 32 bits or negative; these
helpers turn it into numpy generators and JAX keys without collisions
(``jax.random.PRNGKey`` alone keeps only the low 32 bits of a wide
seed)."""
from __future__ import annotations

from typing import List

import numpy as np

# JAX key streams drawn from one seed
WEIGHTS = 0
IMAGES = 2


def seed_words(seed: int) -> List[int]:
    s = int(seed) % (1 << 64)
    return [s & 0xFFFFFFFF, s >> 32]


def rng(seed: int, *stream: int) -> np.random.Generator:
    return np.random.default_rng(seed_words(seed) + [int(x) for x in stream])


def jax_key(seed: int, stream: int):
    """Key of one stream: the weights (``WEIGHTS``), which the model is
    initialized from and the reference re-derives, or the image pool."""
    import jax
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return key if stream == WEIGHTS else jax.random.fold_in(key, stream)
