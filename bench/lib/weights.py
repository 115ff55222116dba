"""What every family's weights start from: a PRNG key made from the run's
seed."""
from __future__ import annotations

import jax
import numpy as np


def key_from_seed(seed: int):
    """A PRNG key from any non-negative whole number (the driver's seeds
    exceed 32 bits)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0]) >> 1
    return jax.random.PRNGKey(word)
