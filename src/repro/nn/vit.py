"""ShiftAddViT — the paper's own model family, used for the faithful
reproduction experiments (sensitivity Tab. 2, MoE routing Fig. 6, LL-loss
Tab. 7) on synthetic image-classification tasks.

A compact PVT/DeiT-style encoder: patchify (linear on flattened patches) →
bidirectional transformer blocks whose attention / projections / MLPs follow
the ShiftAddPolicy (exactly the paper's reparameterization surface) → mean
pool → classifier head. `convert_from` implements the paper's two-stage
reparameterization from a pretrained dense ViT's params.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from repro.core import reparam
from repro.core.dense import Dense
from repro.core.policy import ShiftAddPolicy
from repro.configs.base import ModelConfig
from repro.nn.blocks import TransformerBlock


@dataclasses.dataclass(frozen=True)
class ViTConfig:
    image_size: int = 32
    patch_size: int = 4
    in_channels: int = 3
    n_classes: int = 10
    n_layers: int = 4
    d_model: int = 128
    n_heads: int = 4
    d_ff: int = 256
    policy: ShiftAddPolicy = ShiftAddPolicy()
    dtype: str = "float32"
    moe_capacity: float = 1.25

    @property
    def n_patches(self) -> int:
        return (self.image_size // self.patch_size) ** 2

    def model_config(self) -> ModelConfig:
        return ModelConfig(
            name="shiftadd_vit", family="vit", n_layers=self.n_layers,
            d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_heads,
            d_ff=self.d_ff, vocab_size=self.n_classes, mlp_kind="mlp",
            causal=False, rope="none", norm="layernorm", use_bias=True,
            input_mode="embeddings", policy=self.policy, scan_layers=False,
            remat="none", dtype=self.dtype, param_dtype="float32",
            moe_primitives_capacity=self.moe_capacity,
            moe_capacity_ref_tokens=self.n_patches)


class ShiftAddViT:
    def __init__(self, cfg: ViTConfig):
        self.cfg = cfg
        mc = cfg.model_config()
        self.mc = mc
        dt = mc.activation_dtype
        patch_dim = cfg.patch_size ** 2 * cfg.in_channels
        self.patch_embed = Dense(patch_dim, cfg.d_model, dtype=dt)
        self.blocks = [TransformerBlock(mc, "attn") for _ in range(cfg.n_layers)]
        from repro.nn.layers import make_norm
        self.final_norm = make_norm("layernorm", cfg.d_model, 1e-6, dt, jnp.float32)
        self.head = Dense(cfg.d_model, cfg.n_classes, dtype=dt)

    def init(self, key):
        ks = jax.random.split(key, len(self.blocks) + 3)
        return {
            "patch_embed": self.patch_embed.init(ks[0]),
            "blocks": [b.init(ks[1 + i]) for i, b in enumerate(self.blocks)],
            "final_norm": self.final_norm.init(ks[-2]),
            "head": self.head.init(ks[-1]),
        }

    def patchify(self, images):
        """(B, H, W, C) → (B, n_patches, patch_dim)."""
        c = self.cfg
        b, h, w, ch = images.shape
        p = c.patch_size
        x = images.reshape(b, h // p, p, w // p, p, ch)
        x = x.transpose(0, 1, 3, 2, 4, 5).reshape(b, (h // p) * (w // p), p * p * ch)
        return x

    def __call__(self, params, images, train=True):
        """images: (B, H, W, C) → (logits (B, n_classes), aux)."""
        x = self.patch_embed(params["patch_embed"],
                             self.patchify(images).astype(self.mc.activation_dtype))
        bal = jnp.float32(0.0)
        drop = jnp.float32(0.0)
        aux_all = []
        for blk, p in zip(self.blocks, params["blocks"]):
            x, aux = blk(p, x, positions=None, train=train)
            bal += aux["balance_loss"]
            drop += aux["drop_fraction"]
            aux_all.append(aux)
        x = self.final_norm(params["final_norm"], x)
        logits = self.head(params["head"], jnp.mean(x, axis=1))
        n = max(len(self.blocks), 1)
        return logits, {"balance_loss": bal / n, "drop_fraction": drop / n}

    def prepare_inference(self, params, impl=None, token_counts=(),
                          tune=None):
        """Deployment freeze (core.deploy): decode/pack every shift weight
        once and warm MoE capacity plans. Returns a DeployPlan whose `params`
        feed `infer` with exact logit parity — the serving engine closes its
        jitted forward over them. `tune` (a kernels.autotune.TuneTable) is
        recorded on the plan and must be threaded to `infer` alongside the
        frozen params."""
        from repro.core.deploy import prepare_inference
        return prepare_inference(self, params, impl=impl,
                                 token_counts=token_counts, tune=tune)

    def infer(self, params, images, impl=None, tune=None):
        """Inference fast path: images (B, H, W, C) → logits (B, n_classes).

        The serving forward (repro.serve.vision jits this): no aux-loss
        computation, binary-linear attention through the fused bidirectional
        op, MoE feeds through the deterministic gather dispatch on
        clean-logit argmax with capacity planned per image row — no rng
        anywhere, so two calls on the same batch return identical logits.
        Pass a DeployPlan's frozen params (see `prepare_inference`) to also
        hoist every shift-weight decode out of the compiled program; logits
        are bit-identical either way.

        Batch-invariance contract (ISSUE 5): a given image's logits are
        bit-identical no matter what it is batched with, in which row, at
        which bucket padding, on how many replicas. Every reduction in the
        forward is within-row (attention/MLP/norms reduce over tokens or
        channels of one image; the MoE capacity domain is one row), and the
        classifier head below is written as an explicit broadcast-multiply
        + within-row reduce rather than a (B, d)·(d, k) dot: XLA CPU picks
        a different gemm/gemv strategy for tiny-M matmuls as M crosses ~1,
        which was the one op whose row values depended on the batch size.

        Every op lies under a named scope (HLO `op_name` metadata only, no
        effect on the program): `patch_embed`, each block's `mixer` and
        `feed` (TransformerBlock.infer), and `head`.
        """
        with jax.named_scope("patch_embed"):
            x = self.patch_embed(
                params["patch_embed"],
                self.patchify(images).astype(self.mc.activation_dtype))
        for blk, p in zip(self.blocks, params["blocks"]):
            x = blk.infer(p, x, positions=None, impl=impl, tune=tune)
        with jax.named_scope("head"):
            x = self.final_norm(params["final_norm"], x)
            pooled = jnp.mean(x, axis=1)                   # (B, d)
            w = params["head"]["kernel"].astype(pooled.dtype)
            logits = jnp.sum(pooled[:, :, None] * w[None], axis=1)
            if "bias" in params["head"]:
                logits = logits + params["head"]["bias"].astype(pooled.dtype)
        return logits

    def loss(self, params, batch, train=True):
        logits, aux = self(params, batch["images"], train=train)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        ce = -jnp.mean(jnp.take_along_axis(logp, batch["labels"][:, None], 1))
        lam = self.mc.policy.balance_loss_weight
        total = ce + lam * aux["balance_loss"]
        acc = jnp.mean((jnp.argmax(logits, -1) == batch["labels"]).astype(jnp.float32))
        return total, {"ce": ce, "acc": acc, "balance_loss": aux["balance_loss"],
                       "loss": total}

    # -- the paper's two-stage conversion ------------------------------------
    def convert_from(self, dense_model: "ShiftAddViT", dense_params, stage=2):
        """Reparameterize a pretrained dense ViT into this policy's structure.

        stage 0: structural copy only (the dense arm of a policy sweep).
        stage 1: attention → (binary-)linear (+ shift projections if policy
                 says so); MLPs untouched.
        stage 2: + MLPs → shift or MoE-of-primitives (Mult expert = pretrained
                 MLP, Shift expert = its po2 projection).
        """
        assert dense_model.cfg.n_layers == self.cfg.n_layers
        p = self.cfg.policy
        out = jax.tree_util.tree_map(lambda x: x, dense_params)  # copy
        if stage < 1:
            return out
        for i, blk in enumerate(self.blocks):
            src = dense_params["blocks"][i]
            dst = dict(src)
            mixer = dict(src["mixer"])
            if p.projections == "shift":
                for name in ("q", "k", "v", "o"):
                    mixer[name] = reparam.dense_to_shift(mixer[name])
            if p.attention in ("linear", "binary_linear") and p.dwconv_v:
                # New parameter introduced by the reparam: zero-init so the
                # converted model starts as the pure linear-attention of the
                # pretrained weights (the DWConv grows in during finetuning).
                key = jax.random.PRNGKey(1000 + i)
                fresh = blk.mixer.dwconv.init(key)
                mixer["dwconv"] = jax.tree_util.tree_map(jnp.zeros_like, fresh)
            dst["mixer"] = mixer
            if stage >= 2:
                if p.mlp == "shift":
                    dst["feed"] = {
                        "up": reparam.dense_to_shift(src["feed"]["up"]),
                        "down": reparam.dense_to_shift(src["feed"]["down"]),
                    }
                elif p.mlp == "moe_primitives":
                    dst["feed"] = reparam.dense_mlp_to_moe(
                        src["feed"], p.moe_experts)
            out["blocks"][i] = dst
        return out
