"""Deployment freeze — one-time param-tree pass that hoists every per-forward
decode out of the serving hot loop (ISSUE 3 tentpole).

The stage-2 shiftadd model pays three per-call taxes the dense model doesn't:

1. every ShiftLinear forward fake-quantizes its fp32 latent (or decodes its
   packed int8) back to s·2^P — log2/round/clip/ldexp over every weight,
   every call;
2. the binary attention runs through STE machinery built for training;
3. the MoE recomputes its capacity split bookkeeping at every trace.

`prepare_inference` walks the param tree ONCE at engine-build time and
materializes a `DeployPlan`:

- **shift weights** are decoded to their exact s·2^P value (impl="xla": a
  plain `w_deploy` operand for the dense dot — the hoisted twin of
  `ref.shift_matmul_ref`'s per-call `po2_weight_from_packed`) or packed to
  the int8 kernel format (impl="pallas"/"interpret": the Pallas kernel
  decodes in VMEM, which is already free). Both decodes are bit-exact, so
  frozen inference has EXACT logit parity with unfrozen inference.
- **MoE capacities/offsets** are precomputed into each
  `MoEPrimitives.capacity_plan` memo for the PER-IMAGE token counts the
  serving dispatch routes over (one routing group per batch row — ISSUE 5).
  Tokens-per-image is a property of the model geometry, not of the bucket
  (one count for a ViT, one per stage for PVTv2), so the warmed counts
  cover every bucket and the plan is identical for an image no matter
  which co-batch it arrives in.

The plan's `params` tree is what the serving engine's jitted forward closes
over; `ShiftLinear.__call__` recognizes the frozen leaves, so `infer` paths
consume the plan with no signature changes anywhere in the stack.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax.numpy as jnp

from repro.core import quant


def _is_shift_leaf(tree) -> bool:
    return isinstance(tree, dict) and ("w_latent" in tree or "w_packed" in tree)


def _freeze_shift_leaf(leaf, impl: str):
    """One ShiftLinear param dict → its deployment form for `impl`.

    xla: decode once to the exact s·2^P fp32 weight (what the unfrozen
      forward recomputes per call — `po2_quantize_ste` forward value /
      `po2_weight_from_packed`, both bit-exact powers of two).
    pallas/interpret: pack once to the int8 kernel format (1 B/weight HBM
      traffic; the kernel reassembles bf16 exponents in VMEM).
    """
    if impl == "xla":
        if "w_latent" in leaf:
            sign, p = quant.po2_quantize(leaf["w_latent"])
            w = quant.po2_value(sign, p, jnp.float32)
        else:
            w = quant.po2_weight_from_packed(leaf["w_packed"], jnp.float32)
        out = {"w_deploy": w}
    else:
        out = {"w_packed": (leaf["w_packed"] if "w_packed" in leaf
                            else quant.pack_from_dense(leaf["w_latent"]))}
    if "bias" in leaf:
        out["bias"] = leaf["bias"]
    return out


@dataclasses.dataclass(frozen=True)
class DeployPlan:
    """Frozen inference artifacts for one (model, params) pair.

    params: the frozen param tree — same structure as the live tree, with
      every ShiftLinear subtree replaced by its deployment form. The serving
      engine's jitted forward closes over this tree as constants.
    impl: kernel implementation the decode targeted ("xla"|"pallas"|"interpret").
    frozen_linears: how many shift subtrees were decoded/packed.
    moe_layers: how many MoE feeds had capacity plans warmed.
    token_counts: PER-IMAGE token counts the capacity plans were warmed for
      (the serving dispatch routes one group per batch row, so these are
      tokens-per-image — the model's `token_counts`, e.g. `(n_patches,)`
      for the ViT — not flattened co-batch group sizes).
    tune: optional kernels.autotune.TuneTable the frozen program's kernel
      calls consume (threaded by the engine to every infer; hashable, so the
      jit cache keys on it). None → module-default block caps.
    """

    params: Any
    impl: str
    frozen_linears: int = 0
    moe_layers: int = 0
    token_counts: Tuple[int, ...] = ()
    tune: Any = None


def freeze_params(params, impl: str):
    """Walk a param tree, freezing every shift subtree. Returns (tree, count)."""
    count = 0

    def walk(tree):
        nonlocal count
        if _is_shift_leaf(tree):
            count += 1
            return _freeze_shift_leaf(tree, impl)
        if isinstance(tree, dict):
            return {k: walk(v) for k, v in tree.items()}
        if isinstance(tree, (list, tuple)):
            seq = [walk(v) for v in tree]
            return tuple(seq) if isinstance(tree, tuple) else seq
        return tree

    return walk(params), count


def prepare_inference(model, params, impl=None, token_counts=(),
                      tune=None) -> DeployPlan:
    """Build the DeployPlan for `model` + `params` (ISSUE 3 tentpole entry).

    model: anything with an optional `blocks` list whose block feeds may be
      `MoEPrimitives` (ShiftAddViT, PVTv2, TransformerBlock stacks, ...).
      Only the param tree is required; the model is consulted to warm MoE
      capacity plans for `token_counts` (PER-IMAGE token counts — the
      serving dispatch plans capacity per batch row) so dispatch trace time
      pays no capacity math either. Every MoE feed is warmed at every
      count: a pyramid's stages each route one of them.
    """
    from repro.core.moe_primitives import MoEPrimitives
    from repro.kernels import ops

    impl = impl or ops.default_impl()
    assert impl in ("xla", "pallas", "interpret"), impl
    frozen, n_frozen = freeze_params(params, impl)

    moe_layers = 0
    token_counts = tuple(sorted(set(int(t) for t in token_counts)))
    for blk in getattr(model, "blocks", []):
        feed = getattr(blk, "feed", None)
        if isinstance(feed, MoEPrimitives):
            moe_layers += 1
            for t in token_counts:
                feed.capacity_plan(t)
    return DeployPlan(params=frozen, impl=impl, frozen_linears=n_frozen,
                      moe_layers=moe_layers, token_counts=token_counts,
                      tune=tune)
