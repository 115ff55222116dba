"""Paper Tab. 2 (sensitivity analysis): which components tolerate
reparameterization. Reduced-scale faithful reproduction: pretrain a dense ViT
on the synthetic object-classification task, apply each component
conversion, finetune briefly, report accuracy.

Expected ordering (the paper's finding, validated in EXPERIMENTS.md):
  attention reparam (LA+Add / Shift-proj) ≈ baseline;
  Shift on MLPs drops accuracy;
  MoE-of-primitives recovers it.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.policy import ShiftAddPolicy, DENSE
from repro.data.pipeline import SyntheticImageData
from repro.nn.vit import ShiftAddViT, ViTConfig
from repro.optim.optimizer import adamw

VARIANTS = {
    "msa_dense": DENSE,
    "attn_la_add": ShiftAddPolicy(attention="binary_linear"),
    "attn_shift": ShiftAddPolicy(projections="shift"),
    "mlp_shift": ShiftAddPolicy(mlp="shift"),
    "mlp_moe": ShiftAddPolicy(mlp="moe_primitives"),
}

CFG = dict(image_size=16, patch_size=4, n_classes=4, n_layers=2, d_model=48,
           n_heads=2, d_ff=96)


def _train(model, params, data, steps, lr, seed_offset=0):
    opt = adamw(lr, weight_decay=0.0)
    state = opt.init(params)

    @jax.jit
    def step(params, state, batch):
        (loss, m), grads = jax.value_and_grad(model.loss, has_aux=True)(
            params, batch)
        params, state = opt.update(grads, state, params)
        return params, state, m

    for i in range(steps):
        batch = {k: jnp.asarray(v)
                 for k, v in data.batch_at(seed_offset + i).items()
                 if k != "object_yx"}
        params, state, m = step(params, state, batch)
    return params


def _acc(model, params, data, n=8):
    accs = []
    for i in range(n):
        batch = {k: jnp.asarray(v) for k, v in data.batch_at(5000 + i).items()
                 if k != "object_yx"}
        _, m = model.loss(params, batch, train=False)
        accs.append(float(m["acc"]))
    return float(np.mean(accs))


def main(rows=None, pretrain_steps=150, finetune_steps=60):
    own = rows is None
    rows = [] if own else rows
    data = SyntheticImageData(image_size=16, n_classes=4, global_batch=32,
                              seed=7)
    dense_cfg = ViTConfig(**CFG, policy=DENSE)
    dense = ShiftAddViT(dense_cfg)
    params = dense.init(jax.random.PRNGKey(0))
    params = _train(dense, params, data, pretrain_steps, 3e-3)
    base_acc = _acc(dense, params, data)
    rows.append(("sensitivity_msa_dense", 0.0, f"acc={base_acc:.3f}"))

    for name, policy in VARIANTS.items():
        if name == "msa_dense":
            continue
        cfg = ViTConfig(**CFG, policy=policy)
        model = ShiftAddViT(cfg)
        p = model.convert_from(dense, params, stage=2)
        p = _train(model, p, data, finetune_steps, 3e-4, seed_offset=300)
        acc = _acc(model, p, data)
        rows.append((f"sensitivity_{name}", 0.0,
                     f"acc={acc:.3f};delta={acc - base_acc:+.3f}"))
    if own:
        for r in rows:
            print(",".join(str(c) for c in r))
    return rows


if __name__ == "__main__":
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
