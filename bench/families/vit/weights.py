"""Seeded random weights of a ViT configuration, made on the device in one
jitted call, in float32 as the program serves them.

The tree has the layout of the program's dense ViT parameters (patch
embedding, blocks of q/k/v/o and up/down linears with biases, two layer
norms per block, final norm, head), so the program can convert it; the
reference reads the same tree. Two further groups, one per block, are for
the shiftadd arm: the MoE router's kernel and the V branch's depthwise
convolution. The program's conversion leaves both at zero (the state at the
start of finetuning); a served model has them trained, so the benchmark sets
them from the seed as well, and the output check covers them.

Pixel normalisation (x / 127.5 - 1) is folded into the patch embedding, so
the model takes the uint8 pixels a client sends and its activations stay of
order one.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.lib.weights import key_from_seed

DWCONV_WIDTH = 3


def _linear(key, d_in, d_out):
    kw, kb = jax.random.split(key)
    w = jax.random.truncated_normal(kw, -2.0, 2.0, (d_in, d_out)) * d_in ** -0.5
    return {"kernel": w, "bias": 0.02 * jax.random.normal(kb, (d_out,))}


def _norm(key, d):
    ks, kb = jax.random.split(key)
    return {"scale": 1.0 + 0.1 * jax.random.normal(ks, (d,)),
            "bias": 0.05 * jax.random.normal(kb, (d,))}


def _make(key, *, patch_dim, d, d_ff, n_layers, n_classes, n_experts):
    keys = jax.random.split(key, n_layers + 4)
    pe = _linear(keys[0], patch_dim, d)
    pe = {"kernel": pe["kernel"] / 127.5,
          "bias": pe["bias"] - jnp.sum(pe["kernel"], axis=0)}
    blocks, routers, dwconvs = [], [], []
    for i in range(n_layers):
        k = jax.random.split(keys[1 + i], 10)
        blocks.append({
            "mixer": {name: _linear(k[j], d, d)
                      for j, name in enumerate(("q", "k", "v", "o"))},
            "feed": {"up": _linear(k[4], d, d_ff),
                     "down": _linear(k[5], d_ff, d)},
            "norm1": _norm(k[6], d),
            "norm2": _norm(k[7], d),
        })
        routers.append(jax.random.normal(k[8], (d, n_experts)) * d ** -0.5)
        kc, kb = jax.random.split(k[9])
        dwconvs.append({
            "kernel": jax.random.normal(kc, (DWCONV_WIDTH, d))
            * DWCONV_WIDTH ** -0.5,
            "bias": 0.02 * jax.random.normal(kb, (d,))})
    dense = {"patch_embed": pe, "blocks": blocks,
             "final_norm": _norm(keys[-3], d),
             "head": _linear(keys[-2], d, n_classes)}
    return {"dense": dense, "router": routers, "dwconv": dwconvs}


@functools.lru_cache(maxsize=None)
def _maker(patch_dim, d, d_ff, n_layers, n_classes, n_experts):
    return jax.jit(functools.partial(
        _make, patch_dim=patch_dim, d=d, d_ff=d_ff, n_layers=n_layers,
        n_classes=n_classes, n_experts=n_experts))


def make(cfg: dict, seed: int) -> dict:
    """{"dense": tree, "router": [kernel per block], "dwconv": [params per
    block]} for configuration `cfg`, from `seed`."""
    maker = _maker(cfg["patch_size"] ** 2 * cfg["in_channels"],
                   cfg["d_model"], cfg["d_ff"], cfg["n_layers"],
                   cfg["n_classes"], len(cfg.get("moe_experts", ("mult",
                                                                   "shift"))))
    return maker(key_from_seed(seed))
