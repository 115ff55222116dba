"""Reference logits of a configuration, through its family's plain reference
(`bench/families/<family>/reference.py`, which imports nothing of the
program).

`logits(..., precision="highest")` runs every float32 matmul at full
precision. `precision="default"` runs them at the chip's default precision,
which the configuration states (on a TPU one bfloat16 pass, accumulated in
float32). `dtype=bfloat16` stores weights and activations in bfloat16: the
control, the precision below the configuration's float32.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp
import numpy as np


@functools.lru_cache(maxsize=None)
def _jitted(ref, cfg_json: str, dtype_name: str, precision: str):
    cfg = json.loads(cfg_json)
    dtype = jnp.dtype(dtype_name)

    def run(weights, images):
        with jax.default_matmul_precision(precision):
            return ref.forward(weights, images, cfg, dtype)
    return jax.jit(run)


def logits(family, weights, images: np.ndarray, cfg: dict, dtype=jnp.float32,
           precision: str = "highest", block: int = 32) -> np.ndarray:
    """Reference logits of `images` (N, H, W, C) uint8, `block` images at a
    time so that any N fits. `family` is `spec.load_family`'s."""
    ref = family.reference
    fn = _jitted(ref, json.dumps({k: cfg[k] for k in ref.cache_keys
                                  if k in cfg}, sort_keys=True),
                 jnp.dtype(dtype).name, precision)
    out = []
    for start in range(0, len(images), block):
        chunk = images[start:start + block]
        pad = block - len(chunk)
        if pad:
            chunk = np.concatenate(
                [chunk, np.zeros((pad,) + chunk.shape[1:], chunk.dtype)])
        out.append(np.asarray(fn(weights, chunk))[:block - pad])
    return np.concatenate(out)
