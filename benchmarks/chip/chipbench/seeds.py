"""Every random draw of a run derives from its `--seed` through here.

Seeds may exceed 32 signed bits, so they are never handed to a 32-bit
API directly: a numpy SeedSequence mixes the seed with a tag naming
the draw, and yields a generator or a 31-bit integer for JAX."""
from __future__ import annotations

import numpy as np


def _sequence(seed: int, tag: str) -> np.random.SeedSequence:
    return np.random.SeedSequence([int(seed), *tag.encode()])


def rng(seed: int, tag: str) -> np.random.Generator:
    """A numpy generator for the draw named `tag`."""
    return np.random.default_rng(_sequence(seed, tag))


def int31(seed: int, tag: str) -> int:
    """A non-negative integer below 2**31 for the draw named `tag`."""
    return int(_sequence(seed, tag).generate_state(1)[0] >> 1)
