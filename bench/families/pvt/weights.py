"""Seeded random weights of a PVTv2 configuration, made on the device in one
jitted call from two random draws, in float32 as the program serves them.

The tree has the layout of the program's dense PVTv2 parameters, so the
program can convert it and the reference reads the same tree:

- per stage: `patch_embed` (HWIO kernel, bias), `embed_norm`, `blocks`,
  `norm`;
- per block: `norm1`, `mixer` (q/k/v/o linears with biases), where the
  stage's sr > 1 `sr` (an sr x sr HWIO kernel, bias) and `sr_norm`, `norm2`,
  `feed` (up/down linears with biases, the Mix-FFN's fc1/fc2) and
  `ffn_dwconv` (a 3 x 3 x rC depthwise kernel, bias);
- `head`.

Two further groups, one per block, are for the shiftadd arm: the MoE
router's kernel and the V branch's width-3 depthwise convolution. The
program's conversion leaves both at zero (the state at the start of
finetuning); a served model has them trained, so the benchmark sets them
from the seed as well, and the output check covers them.

Pixels are normalised x/127.5 - 1 inside the model, before the first
convolution, not folded into its weights: the convolution pads with zeros,
and a fold would pad with the zero of the raw pixels.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.lib.weights import key_from_seed

DWCONV_WIDTH = 3


class _Draws:
    """Hands out consecutive slices of two seeded vectors: standard normals
    and normals truncated to [-2, 2]. Two draws for the whole tree keep the
    jitted maker small; one draw per leaf (some 400) took minutes to compile
    for the chip. Without a key it only counts what is asked of it (run
    under `jax.eval_shape`), which sizes the vectors."""

    def __init__(self, key=None, sizes=(0, 0)):
        self.used = [0, 0]
        self.vectors = None
        if key is not None:
            kn, kt = jax.random.split(key)
            self.vectors = (jax.random.normal(kn, (sizes[0],)),
                            jax.random.truncated_normal(kt, -2.0, 2.0,
                                                        (sizes[1],)))

    def _take(self, i, shape):
        n, at = math.prod(shape), self.used[i]
        self.used[i] += n
        if self.vectors is None:
            return jnp.zeros(shape)
        return self.vectors[i][at:at + n].reshape(shape)

    def normal(self, *shape):
        return self._take(0, shape)

    def truncated(self, *shape):
        return self._take(1, shape)


def _linear(r, d_in, d_out):
    return {"kernel": r.truncated(d_in, d_out) * d_in ** -0.5,
            "bias": 0.02 * r.normal(d_out)}


def _conv(r, k, c_in, c_out):
    p = _linear(r, k * k * c_in, c_out)
    return {"kernel": p["kernel"].reshape(k, k, c_in, c_out), "bias": p["bias"]}


def _norm(r, d):
    return {"scale": 1.0 + 0.1 * r.normal(d), "bias": 0.05 * r.normal(d)}


def _block(r, c, f, sr, n_experts):
    blk = {
        "norm1": _norm(r, c),
        "mixer": {name: _linear(r, c, c) for name in ("q", "k", "v", "o")},
        "norm2": _norm(r, c),
        "feed": {"up": _linear(r, c, f), "down": _linear(r, f, c)},
        "ffn_dwconv": {"kernel": r.normal(3, 3, f) / 3.0,
                       "bias": 0.02 * r.normal(f)},
    }
    if sr > 1:
        blk["sr"] = _conv(r, sr, c, c)
        blk["sr_norm"] = _norm(r, c)
    router = r.normal(c, n_experts) * c ** -0.5
    dwconv = {"kernel": r.normal(DWCONV_WIDTH, c) * DWCONV_WIDTH ** -0.5,
              "bias": 0.02 * r.normal(c)}
    return blk, router, dwconv


def _make(r, *, in_channels, widths, depths, mlp_ratios, sr_ratios,
          patch_kernels, n_classes, n_experts):
    stages, routers, dwconvs = [], [], []
    c_in = in_channels
    for i, c in enumerate(widths):
        embed = _conv(r, patch_kernels[i], c_in, c)
        embed_norm = _norm(r, c)
        made = [_block(r, c, mlp_ratios[i] * c, sr_ratios[i], n_experts)
                for _ in range(depths[i])]
        stages.append({"patch_embed": embed, "embed_norm": embed_norm,
                       "blocks": [m[0] for m in made], "norm": _norm(r, c)})
        routers.append([m[1] for m in made])
        dwconvs.append([m[2] for m in made])
        c_in = c
    dense = {"stages": stages, "head": _linear(r, widths[-1], n_classes)}
    return {"dense": dense, "router": routers, "dwconv": dwconvs}


@functools.lru_cache(maxsize=None)
def _maker(**geometry):
    counter = _Draws()
    jax.eval_shape(lambda: _make(counter, **geometry))
    sizes = tuple(counter.used)
    return jax.jit(lambda key: _make(_Draws(key, sizes), **geometry))


def make(cfg: dict, seed: int) -> dict:
    """{"dense": tree, "router": [[kernel per block] per stage], "dwconv":
    [[params per block] per stage]} for configuration `cfg`, from `seed`."""
    maker = _maker(in_channels=cfg["in_channels"],
                   widths=tuple(cfg["widths"]), depths=tuple(cfg["depths"]),
                   mlp_ratios=tuple(cfg["mlp_ratios"]),
                   sr_ratios=tuple(cfg["sr_ratios"]),
                   patch_kernels=tuple(cfg["patch_kernels"]),
                   n_classes=cfg["n_classes"],
                   n_experts=len(cfg.get("moe_experts", ("mult", "shift"))))
    return maker(key_from_seed(seed))
