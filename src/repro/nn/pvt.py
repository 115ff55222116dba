"""PVTv2 — the pyramid vision transformer ShiftAddViT takes as its main
backbone (Wang et al., "PVT v2: Improved Baselines with Pyramid Vision
Transformer", arXiv:2106.13797), under the same ShiftAddPolicy surface as
`nn/vit.py`'s DeiT.

Four stages, each: an overlapping patch embedding (a k×k convolution of
stride s, padding k//2, then LayerNorm eps 1e-5) → blocks → LayerNorm (eps
1e-6); the tokens go back to their grid for the next stage's embedding. A
block is pre-norm residual (LayerNorm eps 1e-6):

- spatial-reduction attention: q from the tokens; k and v from the tokens
  reduced by an sr×sr convolution of stride sr on the grid, then LayerNorm
  (eps 1e-5), where sr > 1, else from the tokens themselves; C/64 heads;
- Mix-FFN: fc1 (C → rC) → depthwise 3×3 convolution (padding 1, bias) →
  GELU (erf) → fc2.

Then a mean over the last stage's tokens and a linear head. No class token,
no position embedding. Pixels (0-255) are mapped to [-1, 1] before the
first convolution, so its zero padding is of the normalised image.

The policy reparameterizes what `nn/vit.py` does: attention (softmax, or
binary linear with its V-branch DWConv over the flattened k/v tokens, q over
the N tokens against k and v over the reduced ones), the q/k/v/o
projections, and the Mix-FFN's two linears (dense, shift, or the MoE of
mult and shift experts). The convolutions, norms and head stay dense.

The MoE routes each token once per block: a depthwise convolution needs
every neighbour on the grid, so it cannot run inside a routed expert. The
token's fc1 and fc2 run in its expert; the convolution and GELU run once on
the whole grid between them (`MoEPrimitives.infer(mid=)`); a token over its
expert's capacity gives zero; the gate scales the expert's output. Each
stage's MoE plans its capacities for its own grid (`token_counts`, warmed
by `prepare_inference`).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import reparam
from repro.core.dense import Dense
from repro.core.moe_primitives import MoEPrimitives
from repro.core.policy import ShiftAddPolicy
from repro.nn import layers as L
from repro.nn.attention import Attention
from repro.nn.blocks import ZERO_AUX, _make_feed

BLOCK_EPS = 1e-6       # block and stage-end LayerNorms
EMBED_EPS = 1e-5       # patch-embedding and spatial-reduction LayerNorms
PIXEL_HALF = 127.5     # pixels x -> (x - 127.5) / 127.5


@dataclasses.dataclass(frozen=True)
class PVTv2Config:
    """Defaults are PVTv2-B2 at 224 px (arXiv:2106.13797, Table 1)."""
    image_size: int = 224
    in_channels: int = 3
    n_classes: int = 1000
    widths: tuple = (64, 128, 320, 512)
    depths: tuple = (3, 4, 6, 3)
    heads: tuple = (1, 2, 5, 8)
    mlp_ratios: tuple = (8, 8, 4, 4)
    sr_ratios: tuple = (8, 4, 2, 1)
    patch_kernels: tuple = (7, 3, 3, 3)
    patch_strides: tuple = (4, 2, 2, 2)
    policy: ShiftAddPolicy = ShiftAddPolicy()
    dtype: str = "float32"
    moe_capacity: float = 1.25

    @property
    def grids(self) -> tuple:
        """Side of each stage's square token grid."""
        side, out = self.image_size, []
        for k, s in zip(self.patch_kernels, self.patch_strides):
            side = (side + 2 * (k // 2) - k) // s + 1
            out.append(side)
        return tuple(out)

    @property
    def token_counts(self) -> tuple:
        return tuple(g * g for g in self.grids)

    def stage_config(self, i: int) -> ModelConfig:
        """The block-level config of stage i (what Attention and the feeds
        are built from)."""
        c = self.widths[i]
        return ModelConfig(
            name="pvtv2", family="vit", n_layers=self.depths[i], d_model=c,
            n_heads=self.heads[i], n_kv_heads=self.heads[i],
            d_ff=self.mlp_ratios[i] * c, vocab_size=self.n_classes,
            mlp_kind="mlp", causal=False, rope="none", norm="layernorm",
            norm_eps=BLOCK_EPS, use_bias=True, input_mode="embeddings",
            policy=self.policy, scan_layers=False, remat="none",
            dtype=self.dtype, param_dtype="float32",
            moe_primitives_capacity=self.moe_capacity,
            moe_capacity_ref_tokens=self.token_counts[i])


def _conv_init(key, k, c_in, c_out):
    """HWIO kernel, fan-in scaled, and a zero bias."""
    fan_in = k * k * c_in
    w = jax.random.truncated_normal(key, -2.0, 2.0, (k, k, c_in, c_out))
    return {"kernel": w * fan_in ** -0.5, "bias": jnp.zeros((c_out,))}


def _conv(x, p, stride, pad, dtype):
    """x (B, H, W, Cin) → (B, H', W', Cout); HWIO kernel, zero padding."""
    y = jax.lax.conv_general_dilated(
        x.astype(dtype), p["kernel"].astype(dtype), (stride, stride),
        [(pad, pad), (pad, pad)], dimension_numbers=("NHWC", "HWIO", "NHWC"))
    return y + p["bias"].astype(dtype)


def depthwise3x3(x, p, dtype):
    """Depthwise 3×3 convolution, padding 1, with bias: x (B, H, W, F),
    kernel (3, 3, F). Nine shifted multiply-adds (a grouped conv with F
    groups lowers to a per-channel loop on CPU XLA; see L.DWConv1D)."""
    _, h, w, _ = x.shape
    k = p["kernel"].astype(dtype)
    xp = jnp.pad(x.astype(dtype), ((0, 0), (1, 1), (1, 1), (0, 0)))
    y = None
    for i in range(3):
        for j in range(3):
            t = xp[:, i:i + h, j:j + w, :] * k[i, j]
            y = t if y is None else y + t
    return y + p["bias"].astype(dtype)


class PVTBlock:
    """One PVTv2 block on a side×side grid: spatial-reduction attention and
    Mix-FFN, each pre-norm residual."""

    def __init__(self, mc: ModelConfig, side: int, sr: int):
        self.mc = mc
        self.side, self.sr = side, sr
        dt = mc.activation_dtype
        self.dtype = dt
        self.mixer = Attention(mc)
        self.feed = _make_feed(mc, "attn")
        self.is_moe = isinstance(self.feed, MoEPrimitives)
        self.norm1 = L.LayerNorm(mc.d_model, BLOCK_EPS, dt, jnp.float32)
        self.norm2 = L.LayerNorm(mc.d_model, BLOCK_EPS, dt, jnp.float32)
        self.sr_norm = (L.LayerNorm(mc.d_model, EMBED_EPS, dt, jnp.float32)
                        if sr > 1 else None)

    def init(self, key):
        ks = jax.random.split(key, 6)
        c, f = self.mc.d_model, self.mc.d_ff
        p = {"norm1": self.norm1.init(ks[0]), "mixer": self.mixer.init(ks[1]),
             "norm2": self.norm2.init(ks[2]), "feed": self.feed.init(ks[3]),
             "ffn_dwconv": {
                 "kernel": jax.random.normal(ks[4], (3, 3, f)) / 3.0,
                 "bias": jnp.zeros((f,))}}
        if self.sr > 1:
            p["sr"] = _conv_init(ks[5], self.sr, c, c)
            p["sr_norm"] = self.sr_norm.init(None)
        return p

    def reduce(self, params, h):
        """k/v tokens of the attention: the sr×sr, stride-sr convolution of
        the token grid, then LayerNorm (eps 1e-5); None where sr = 1 (the
        attention then takes k and v from h itself)."""
        if self.sr == 1:
            return None
        b, n, c = h.shape
        grid = h.reshape(b, self.side, self.side, c)
        kv = _conv(grid, params["sr"], self.sr, 0, self.dtype)
        return self.sr_norm(params["sr_norm"], kv.reshape(b, -1, c))

    def mid(self, params, h):
        """Mix-FFN between fc1 and fc2: (B, N, rC) → depthwise 3×3 on the
        grid → GELU (erf) → (B, N, rC)."""
        with jax.named_scope("ffn_dwconv"):
            b, n, f = h.shape
            y = depthwise3x3(h.reshape(b, self.side, self.side, f),
                             params["ffn_dwconv"], self.dtype)
            return jax.nn.gelu(y, approximate=False).reshape(b, n, f)

    def _feed(self, params, h, impl, tune):
        mid = functools.partial(self.mid, params)
        if self.is_moe:
            return self.feed.infer(params["feed"], h, impl=impl, tune=tune,
                                   mid=mid)
        return self.feed(params["feed"], h, impl=impl, tune=tune, mid=mid)

    def __call__(self, params, x, train=False):
        h = self.norm1(params["norm1"], x)
        x = x + self.mixer(params["mixer"], h, train=train,
                           kv=self.reduce(params, h))
        return x + self._feed(params, self.norm2(params["norm2"], x), None,
                              None)

    def infer(self, params, x, impl=None, tune=None):
        """Serving forward. Named scopes: `mixer` (norm1, `sr_reduce`,
        attention, residual) and `feed` (norm2, Mix-FFN or its MoE with
        `ffn_dwconv` inside, residual)."""
        with jax.named_scope("mixer"):
            h = self.norm1(params["norm1"], x)
            with jax.named_scope("sr_reduce"):
                kv = self.reduce(params, h)
            x = x + self.mixer.infer(params["mixer"], h, impl=impl, tune=tune,
                                     kv=kv)
        with jax.named_scope("feed"):
            return x + self._feed(params, self.norm2(params["norm2"], x),
                                  impl, tune)


class PVTv2:
    """PVTv2 image classifier under a ShiftAddPolicy; served by
    `serve.vision.BucketedViTEngine` as `ShiftAddViT` is."""

    def __init__(self, cfg: PVTv2Config):
        self.cfg = cfg
        dt = jnp.dtype(cfg.dtype)
        self.dtype = dt
        self.stages = []
        for i, side in enumerate(cfg.grids):
            mc = cfg.stage_config(i)
            self.stages.append({
                "embed_norm": L.LayerNorm(cfg.widths[i], EMBED_EPS, dt,
                                          jnp.float32),
                "blocks": [PVTBlock(mc, side, cfg.sr_ratios[i])
                           for _ in range(cfg.depths[i])],
                "norm": L.LayerNorm(cfg.widths[i], BLOCK_EPS, dt,
                                    jnp.float32),
            })
        self.head = Dense(cfg.widths[-1], cfg.n_classes, dtype=dt)

    @property
    def blocks(self) -> list:
        """Every block, stage after stage (what core.deploy walks)."""
        return [blk for st in self.stages for blk in st["blocks"]]

    @property
    def token_counts(self) -> tuple:
        """Per-image token count of each stage: the grids its MoE feeds
        route (the serving engine warms their capacity plans)."""
        return self.cfg.token_counts

    def init(self, key):
        cfg = self.cfg
        keys = jax.random.split(key, len(self.stages) + 1)
        stages, c_in = [], cfg.in_channels
        for i, (st, k) in enumerate(zip(self.stages, keys)):
            ks = jax.random.split(k, len(st["blocks"]) + 1)
            stages.append({
                "patch_embed": _conv_init(ks[0], cfg.patch_kernels[i], c_in,
                                          cfg.widths[i]),
                "embed_norm": st["embed_norm"].init(None),
                "blocks": [b.init(kb) for b, kb in zip(st["blocks"], ks[1:])],
                "norm": st["norm"].init(None),
            })
            c_in = cfg.widths[i]
        return {"stages": stages, "head": self.head.init(keys[-1])}

    def _forward(self, params, images, block_fn):
        cfg = self.cfg
        x = (images.astype(self.dtype) - PIXEL_HALF) / PIXEL_HALF
        last = len(self.stages) - 1
        for i, (st, sp) in enumerate(zip(self.stages, params["stages"])):
            with jax.named_scope("patch_embed"):
                k = cfg.patch_kernels[i]
                x = _conv(x, sp["patch_embed"], cfg.patch_strides[i], k // 2,
                          self.dtype)
                b, h, w, c = x.shape
                x = st["embed_norm"](sp["embed_norm"], x.reshape(b, h * w, c))
            for blk, bp in zip(st["blocks"], sp["blocks"]):
                x = block_fn(blk, bp, x)
            with jax.named_scope("head" if i == last else "patch_embed"):
                x = st["norm"](sp["norm"], x)
                if i != last:
                    x = x.reshape(b, h, w, c)
        with jax.named_scope("head"):
            pooled = jnp.mean(x, axis=1)                        # (B, C)
            # Broadcast-multiply and reduce rather than a (B, C)·(C, k) dot,
            # as ShiftAddViT.infer's head: a row's logits never depend on
            # the batch size.
            w = params["head"]["kernel"].astype(pooled.dtype)
            logits = jnp.sum(pooled[:, :, None] * w[None], axis=1)
            return logits + params["head"]["bias"].astype(pooled.dtype)

    def __call__(self, params, images, train=False):
        """images (B, H, W, C) → (logits, aux), aux as ShiftAddViT's. The
        attention takes its training-side path (core.add_attention's STE
        for the binary arm). The Mix-FFN MoE routes as in serving: its
        shared `mid` has no training dispatch, so an MoE arm refuses
        train=True."""
        if train and any(blk.is_moe for blk in self.blocks):
            raise NotImplementedError(
                "the Mix-FFN MoE has no training dispatch; serve it with "
                "infer or call with train=False")
        logits = self._forward(params, images,
                               lambda blk, p, x: blk(p, x, train=train))
        return logits, dict(ZERO_AUX)

    def infer(self, params, images, impl=None, tune=None):
        """Serving forward: images (B, H, W, C) → logits (B, n_classes).

        Named scopes: `patch_embed` (each stage's embedding and its norm,
        and the stage-end norm of stages 1-3), each block's `mixer` and
        `feed` (with `sr_reduce` and `ffn_dwconv` inside), and `head` (the
        last stage's norm, the mean and the classifier)."""
        return self._forward(
            params, images,
            lambda blk, p, x: blk.infer(p, x, impl=impl, tune=tune))

    def prepare_inference(self, params, impl=None, token_counts=(),
                          tune=None):
        """Deployment freeze (core.deploy): decode/pack every shift weight
        once and warm the MoE capacity plans at `token_counts` (each
        stage's grid among them)."""
        from repro.core.deploy import prepare_inference
        return prepare_inference(self, params, impl=impl,
                                 token_counts=token_counts, tune=tune)

    def convert_from(self, dense_model: "PVTv2", dense_params, stage=2):
        """The paper's two-stage reparameterization of a dense PVTv2's
        params, as ShiftAddViT.convert_from: stage 0 copies; stage 1 makes
        q/k/v/o shift layers where the policy says so and adds the V-branch
        DWConv (zero, the state at the start of finetuning); stage 2 also
        turns each Mix-FFN's fc1/fc2 into shift layers or the MoE of
        primitives (mult expert = the dense linears, shift expert = their
        power-of-two projection). Convolutions, norms and head stay."""
        assert dense_model.cfg.depths == self.cfg.depths
        p = self.cfg.policy
        out = jax.tree_util.tree_map(lambda x: x, dense_params)
        if stage < 1:
            return out
        for st, sp in zip(self.stages, out["stages"]):
            for i, blk in enumerate(st["blocks"]):
                src = sp["blocks"][i]
                dst = dict(src)
                mixer = dict(src["mixer"])
                if p.projections == "shift":
                    for name in ("q", "k", "v", "o"):
                        mixer[name] = reparam.dense_to_shift(mixer[name])
                if blk.mixer.dwconv is not None:
                    fresh = blk.mixer.dwconv.init(jax.random.PRNGKey(0))
                    mixer["dwconv"] = jax.tree_util.tree_map(jnp.zeros_like,
                                                             fresh)
                dst["mixer"] = mixer
                if stage >= 2:
                    if p.mlp == "shift":
                        dst["feed"] = {
                            name: reparam.dense_to_shift(src["feed"][name])
                            for name in ("up", "down")}
                    elif p.mlp == "moe_primitives":
                        dst["feed"] = reparam.dense_mlp_to_moe(
                            src["feed"], p.moe_experts)
                sp["blocks"][i] = dst
        return out
