"""Plain reference of the served ViT, in jax.numpy, from the equations.

It imports nothing of the program. It reads the family's own weights
(`weights.py` beside it) and the configuration file, and computes:

- patches of the image (row-major 16x16x3 blocks) -> linear embedding;
- per block, pre-norm residual attention then pre-norm residual MLP;
- dense arm: softmax attention, softmax(q k^T / sqrt(d_head)) v, and a GELU
  (tanh form) MLP;
- shiftadd arm (arXiv:2306.06446): q/k/v/o through power-of-two weights
  sign(w) 2^round(log2|w|); a depthwise width-3 convolution added to V;
  binary (Hamming) linear attention, out_i = sum_j (b(q_i).b(k_j) + d) v_j /
  sum_j (b(q_i).b(k_j) + d) with b(x) = +1 where x >= 0 else -1; and an MoE
  of a multiplication expert (the dense MLP) and a shift expert (the same
  MLP with power-of-two weights), routed top-1 by argmax of the router's
  logits, gated by the softmax probability of the chosen expert, with a
  fixed capacity per expert per image: a token past its expert's capacity,
  in token order, contributes nothing;
- final norm, mean over patches, linear head.

`bench.lib.reference.logits` runs `forward` in blocks of images at the
matmul precision and in the dtype it is given.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6
# The configuration keys that `forward` reads as Python values: the jitted
# reference is compiled once for each set of their values.
cache_keys = ("policy", "patch_size", "n_heads", "moe_capacity_per_image",
              "moe_experts")


def po2(w):
    """sign(w) * 2^round(log2|w|), exponent clipped to [-64, 63]."""
    sign = jnp.where(w < 0, -1.0, 1.0)
    p = jnp.clip(jnp.round(jnp.log2(jnp.maximum(jnp.abs(w), 2.0 ** -65))),
                 -64, 63)
    return sign * jnp.exp2(p)


def _ln(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * p["scale"] + p["bias"]


def _gelu(x):
    c = math.sqrt(2.0 / math.pi)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x ** 3)))


def _lin(x, p, w=None):
    return x @ (p["kernel"] if w is None else w) + p["bias"]


def _heads(t, h):
    b, n, d = t.shape
    return t.reshape(b, n, h, d // h).transpose(0, 2, 1, 3)


def _merge(t):
    b, h, n, dh = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, n, h * dh)


def _dense_block(x, p, n_heads):
    h = _ln(x, p["norm1"])
    q, k, v = (_heads(_lin(h, p["mixer"][n]), n_heads) for n in "qkv")
    s = q @ jnp.swapaxes(k, -1, -2) / math.sqrt(q.shape[-1])
    attn = jax.nn.softmax(s, axis=-1) @ v
    x = x + _lin(_merge(attn), p["mixer"]["o"])
    h = _ln(x, p["norm2"])
    y = _lin(_gelu(_lin(h, p["feed"]["up"])), p["feed"]["down"])
    return x + y


def _dwconv(v, p):
    """'same' depthwise convolution over the token axis, width 3."""
    n = v.shape[1]
    vp = jnp.pad(v, ((0, 0), (1, 1), (0, 0)))
    y = sum(vp[:, t:t + n, :] * p["kernel"][t] for t in range(3))
    return y + p["bias"]


def _shiftadd_block(x, p, router, dwconv, n_heads, caps, kinds):
    h = _ln(x, p["norm1"])
    mixer = p["mixer"]
    q, k = (_heads(_lin(h, mixer[n], po2(mixer[n]["kernel"])), n_heads)
            for n in "qk")
    vraw = _lin(h, mixer["v"], po2(mixer["v"]["kernel"]))
    v = _heads(vraw + _dwconv(vraw, dwconv), n_heads)
    one = jnp.ones((), q.dtype)
    bq, bk = (jnp.where(t >= 0, one, -one) for t in (q, k))
    scores = bq @ jnp.swapaxes(bk, -1, -2) + q.shape[-1]
    attn = (scores @ v) / (jnp.sum(scores, axis=-1, keepdims=True) + 1e-6)
    o = mixer["o"]
    x = x + _lin(_merge(attn), o, po2(o["kernel"]))

    h = _ln(x, p["norm2"])
    logits = h @ router                                  # (B, N, E)
    top1 = jnp.argmax(logits, axis=-1)
    gate = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                               top1[..., None], axis=-1)[..., 0]
    onehot = jax.nn.one_hot(top1, len(kinds), dtype=jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=1) - onehot) * onehot, axis=-1)
    keep = rank < jnp.asarray(caps, jnp.int32)[top1]
    up, down = p["feed"]["up"], p["feed"]["down"]
    y = jnp.zeros_like(h)
    for e, kind in enumerate(kinds):
        wu, wd = ((up["kernel"], down["kernel"]) if kind == "mult"
                  else (po2(up["kernel"]), po2(down["kernel"])))
        ye = _lin(_gelu(_lin(h, up, wu)), down, wd)
        y = jnp.where((top1 == e)[..., None], ye, y)
    return x + y * (gate * keep.astype(gate.dtype))[..., None]


def patchify(images, patch):
    b, hh, ww, c = images.shape
    x = images.reshape(b, hh // patch, patch, ww // patch, patch, c)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(
        b, (hh // patch) * (ww // patch), patch * patch * c)


def forward(weights, images, cfg: dict, dtype=jnp.float32):
    """weights: `weights.make` output; images (B, H, W, C) uint8 -> logits
    (B, n_classes) float32."""
    cast = functools.partial(jax.tree_util.tree_map, lambda t: t.astype(dtype))
    w = cast(weights)
    dense = w["dense"]
    blocks = jax.tree_util.tree_map(lambda *t: jnp.stack(t), *dense["blocks"])
    x = _lin(patchify(images.astype(dtype), cfg["patch_size"]),
             dense["patch_embed"])
    n_heads = cfg["n_heads"]
    if cfg["policy"] == "dense":
        def body(x, p):
            return _dense_block(x, p, n_heads), None
        x, _ = jax.lax.scan(body, x, blocks)
    else:
        caps = tuple(cfg["moe_capacity_per_image"])
        kinds = tuple(cfg["moe_experts"])
        extra = (jnp.stack(w["router"]),
                 jax.tree_util.tree_map(lambda *t: jnp.stack(t), *w["dwconv"]))

        def body(x, layer):
            p, router, dwconv = layer
            return _shiftadd_block(x, p, router, dwconv, n_heads, caps,
                                   kinds), None
        x, _ = jax.lax.scan(body, x, (blocks,) + extra)
    pooled = jnp.mean(_ln(x, dense["final_norm"]), axis=1)
    return _lin(pooled, dense["head"]).astype(jnp.float32)

