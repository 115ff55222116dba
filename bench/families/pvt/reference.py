"""Plain reference of the served PVTv2, in jax.numpy, from the equations of
arXiv:2106.13797 and the ShiftAddViT recipe (arXiv:2306.06446).

It imports nothing of the program. It reads the family's own weights
(`weights.py` beside it) and the configuration file, and computes:

- pixels x -> (x - 127.5) / 127.5;
- per stage: a k x k convolution of stride s and zero padding k // 2
  (computed as a matmul of the k x k windows), LayerNorm (eps
  1e-5), the blocks, LayerNorm (eps 1e-6); the tokens go back to their grid
  for the next stage;
- per block, pre-norm (eps 1e-6) residual attention, then pre-norm residual
  Mix-FFN;
- attention over k/v tokens reduced by an sr x sr convolution of stride sr
  and LayerNorm (eps 1e-5) where sr > 1, else over the tokens themselves;
- dense arm: softmax(q k^T / sqrt(d_head)) v; Mix-FFN fc1, depthwise 3 x 3
  convolution (padding 1, bias), GELU (erf form), fc2;
- shiftadd arm: q/k/v/o through power-of-two weights sign(w)
  2^round(log2|w|); a depthwise width-3 'same' convolution over the k/v
  tokens added to V; binary (Hamming) linear attention, out_i = sum_j
  (b(q_i).b(k_j) + d) v_j / sum_j (b(q_i).b(k_j) + d), with b(x) = +1 where
  x >= 0 else -1, i over the N tokens and j over the reduced ones; and the
  Mix-FFN as an MoE of a multiplication expert (the dense fc1/fc2) and a
  shift expert (the same linears with power-of-two weights), routed top-1
  by argmax of the router's logits, gated by the softmax probability of the
  chosen expert, with a fixed capacity per expert per image for each stage:
  every token's fc1 through both experts, the one it is routed to kept and
  zero where it is past its expert's capacity in token order; convolution
  and GELU on the whole grid; fc2 likewise through its expert; times the
  gate, and zero past capacity;
- the last stage's mean over tokens, linear head.

`bench.lib.reference.logits` runs `forward` in blocks of images at the
matmul precision and in the dtype it is given.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

BLOCK_EPS = 1e-6
EMBED_EPS = 1e-5
# The configuration keys that `forward` reads as Python values: the jitted
# reference is compiled once for each set of their values.
cache_keys = ("policy", "widths", "heads", "sr_ratios", "patch_kernels",
              "patch_strides", "moe_capacity_per_image", "moe_experts")


def po2(w):
    """sign(w) * 2^round(log2|w|), exponent clipped to [-64, 63]."""
    sign = jnp.where(w < 0, -1.0, 1.0)
    p = jnp.clip(jnp.round(jnp.log2(jnp.maximum(jnp.abs(w), 2.0 ** -65))),
                 -64, 63)
    return sign * jnp.exp2(p)


def _ln(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * p["scale"] + p["bias"]


def _gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def _lin(x, p, w=None):
    return x @ (p["kernel"] if w is None else w) + p["bias"]


def _conv(x, p, k, s, pad):
    """x (B, H, W, C) -> (B, H', W', C'): the k x k windows of stride s over
    the zero-padded grid, each flattened (row, column, channel) and
    multiplied by the HWIO kernel flattened the same way. The strided
    windows are `lax.slice`s: NumPy-style strided indexing lowers to
    gathers, which are slow on a TPU."""
    b, h, w, c = x.shape
    xp = jnp.pad(x, ((0, 0), (pad, pad), (pad, pad), (0, 0)))
    ho, wo = (h + 2 * pad - k) // s + 1, (w + 2 * pad - k) // s + 1
    windows = jnp.concatenate(
        [jax.lax.slice(xp, (0, i, j, 0),
                       (b, i + s * (ho - 1) + 1, j + s * (wo - 1) + 1, c),
                       (1, s, s, 1))
         for i in range(k) for j in range(k)], axis=-1)
    return windows @ p["kernel"].reshape(k * k * c, -1) + p["bias"]


def _dwconv3x3(x, p):
    """Depthwise 3 x 3 convolution, zero padding 1, with bias."""
    _, h, w, _ = x.shape
    xp = jnp.pad(x, ((0, 0), (1, 1), (1, 1), (0, 0)))
    y = sum(xp[:, i:i + h, j:j + w, :] * p["kernel"][i, j]
            for i in range(3) for j in range(3))
    return y + p["bias"]


def _dwconv1d(v, p):
    """'same' depthwise convolution over the token axis, width 3."""
    n = v.shape[1]
    vp = jnp.pad(v, ((0, 0), (1, 1), (0, 0)))
    y = sum(vp[:, t:t + n, :] * p["kernel"][t] for t in range(3))
    return y + p["bias"]


def _heads(t, h):
    b, n, d = t.shape
    return t.reshape(b, n, h, d // h).transpose(0, 2, 1, 3)


def _merge(t):
    b, h, n, dh = t.shape
    return t.transpose(0, 2, 1, 3).reshape(b, n, h * dh)


def _kv_tokens(h, p, side, sr):
    if sr == 1:
        return h
    b, n, c = h.shape
    kv = _conv(h.reshape(b, side, side, c), p["sr"], sr, sr, 0)
    return _ln(kv.reshape(b, -1, c), p["sr_norm"], EMBED_EPS)


def _mid(hid, p, side):
    b, n, f = hid.shape
    y = _dwconv3x3(hid.reshape(b, side, side, f), p["ffn_dwconv"])
    return _gelu(y).reshape(b, n, f)


def _dense_block(x, p, side, n_heads, sr):
    h = _ln(x, p["norm1"], BLOCK_EPS)
    kv = _kv_tokens(h, p, side, sr)
    mixer = p["mixer"]
    q = _heads(_lin(h, mixer["q"]), n_heads)
    k, v = (_heads(_lin(kv, mixer[n]), n_heads) for n in "kv")
    s = q @ jnp.swapaxes(k, -1, -2) / math.sqrt(q.shape[-1])
    x = x + _lin(_merge(jax.nn.softmax(s, axis=-1) @ v), mixer["o"])
    h = _ln(x, p["norm2"], BLOCK_EPS)
    feed = p["feed"]
    return x + _lin(_mid(_lin(h, feed["up"]), p, side), feed["down"])


def _shiftadd_block(x, p, router, dwconv, side, n_heads, sr, caps, kinds):
    h = _ln(x, p["norm1"], BLOCK_EPS)
    kv = _kv_tokens(h, p, side, sr)
    mixer = p["mixer"]
    q = _heads(_lin(h, mixer["q"], po2(mixer["q"]["kernel"])), n_heads)
    k = _heads(_lin(kv, mixer["k"], po2(mixer["k"]["kernel"])), n_heads)
    vraw = _lin(kv, mixer["v"], po2(mixer["v"]["kernel"]))
    v = _heads(vraw + _dwconv1d(vraw, dwconv), n_heads)
    one = jnp.ones((), q.dtype)
    bq, bk = (jnp.where(t >= 0, one, -one) for t in (q, k))
    scores = bq @ jnp.swapaxes(bk, -1, -2) + q.shape[-1]
    attn = (scores @ v) / (jnp.sum(scores, axis=-1, keepdims=True) + 1e-6)
    o = mixer["o"]
    x = x + _lin(_merge(attn), o, po2(o["kernel"]))

    h = _ln(x, p["norm2"], BLOCK_EPS)
    logits = h @ router                                  # (B, N, E)
    top1 = jnp.argmax(logits, axis=-1)
    # The chosen expert's probability and capacity as selects over the
    # experts, not per-token gathers (slow on a TPU); the same values.
    probs = jax.nn.softmax(logits, axis=-1)
    onehot = jax.nn.one_hot(top1, len(kinds), dtype=jnp.int32)
    gate = jnp.sum(jnp.where(onehot == 1, probs, 0.0), axis=-1)
    rank = jnp.sum((jnp.cumsum(onehot, axis=1) - onehot) * onehot, axis=-1)
    keep = (rank < jnp.sum(onehot * jnp.asarray(caps, jnp.int32), axis=-1)
            ).astype(h.dtype)
    up, down = p["feed"]["up"], p["feed"]["down"]

    def weights(kind, lin):
        return lin["kernel"] if kind == "mult" else po2(lin["kernel"])

    hid = jnp.zeros(h.shape[:-1] + (up["kernel"].shape[1],), h.dtype)
    for e, kind in enumerate(kinds):
        hid = jnp.where((top1 == e)[..., None], _lin(h, up, weights(kind, up)),
                        hid)
    m = _mid(hid * keep[..., None], p, side)
    y = jnp.zeros_like(h)
    for e, kind in enumerate(kinds):
        y = jnp.where((top1 == e)[..., None],
                      _lin(m, down, weights(kind, down)), y)
    return x + y * (gate * keep)[..., None]


def _stack(blocks):
    return jax.tree_util.tree_map(lambda *t: jnp.stack(t), *blocks)


def forward(weights, images, cfg: dict, dtype=jnp.float32):
    """weights: `weights.make` output; images (B, H, W, C) uint8 -> logits
    (B, n_classes) float32."""
    cast = functools.partial(jax.tree_util.tree_map, lambda t: t.astype(dtype))
    w = cast(weights)
    x = (images.astype(dtype) - 127.5) / 127.5
    shiftadd = cfg["policy"] != "dense"
    last = len(cfg["widths"]) - 1
    for i, sp in enumerate(w["dense"]["stages"]):
        k, s = cfg["patch_kernels"][i], cfg["patch_strides"][i]
        x = _conv(x, sp["patch_embed"], k, s, k // 2)
        b, side, _, c = x.shape
        x = _ln(x.reshape(b, side * side, c), sp["embed_norm"], EMBED_EPS)
        n_heads, sr = cfg["heads"][i], cfg["sr_ratios"][i]
        if shiftadd:
            caps = tuple(cfg["moe_capacity_per_image"][i])
            kinds = tuple(cfg["moe_experts"])

            def body(x, layer):
                p, router, dwconv = layer
                return _shiftadd_block(x, p, router, dwconv, side, n_heads,
                                       sr, caps, kinds), None
            layers = (_stack(sp["blocks"]), jnp.stack(w["router"][i]),
                      _stack(w["dwconv"][i]))
        else:
            def body(x, p):
                return _dense_block(x, p, side, n_heads, sr), None
            layers = _stack(sp["blocks"])
        x, _ = jax.lax.scan(body, x, layers)
        x = _ln(x, sp["norm"], BLOCK_EPS)
        if i != last:
            x = x.reshape(b, side, side, c)
    pooled = jnp.mean(x, axis=1)
    return _lin(pooled, w["dense"]["head"]).astype(jnp.float32)
