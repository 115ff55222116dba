"""Zero-padding to the tile grid, shared by every kernel module (leaf module:
kernels/* and kernels/ops.py both import from here without cycles)."""
from __future__ import annotations

import jax.numpy as jnp


def pad_to_multiple(x, multiple, axis):
    """Zero-pad `axis` up to the next multiple (no-op when already aligned).
    The pad-and-slice half of every kernel's arbitrary-shape support."""
    pad = (-x.shape[axis]) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)
