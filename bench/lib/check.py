"""The comparison that decides `correct`.

Served logits of the checked requests are compared image by image with the
reference's logits of the same images, computed twice: at full float32
matmul precision ("highest") and at the chip's default matmul precision,
which the configuration states ("default"). Numbers, per reference r:

- `logit_err_<r>`: over the checked images, the largest
  max_j |served_j - ref_j| / max_j |ref_j|;
- `logit_rms_<r>`: root mean square of served - ref over every checked
  logit, over the root mean square of ref;

and `missing`: checked images with no answer, or an answer of the wrong
shape or not finite (limit 0). The configuration file's `limits` name the
numbers compared and their limits; a number is within its limit when it is
not above it.
"""
from __future__ import annotations

import numpy as np


def compare(served: list, refs: dict, n_classes: int) -> dict:
    """served: per checked image its logits row, or None where no answer
    came; refs: name -> (n, n_classes) reference logits of the same images."""
    ok = [row is not None and np.shape(row) == (n_classes,)
          and bool(np.all(np.isfinite(row))) for row in served]
    out = {"missing": float(len(ok) - sum(ok))}
    got = np.asarray([row for row, k in zip(served, ok) if k], np.float64)
    for name, ref in refs.items():
        want = np.asarray([r for r, k in zip(ref, ok) if k], np.float64)
        if len(got) == 0:
            out[f"logit_err_{name}"] = out[f"logit_rms_{name}"] = float("inf")
            continue
        diff = np.abs(got - want)
        out[f"logit_err_{name}"] = float(np.max(
            diff.max(axis=1) / np.abs(want).max(axis=1)))
        out[f"logit_rms_{name}"] = float(np.sqrt(np.mean(diff ** 2))
                                         / np.sqrt(np.mean(want ** 2)))
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """(correct, {name: {"value", "limit"}}) over `missing` and the numbers
    that `limits` names."""
    checks, ok = {}, True
    for name in ["missing"] + sorted(limits):
        value = numbers[name]
        limit = 0.0 if name == "missing" else float(limits[name])
        ok &= value <= limit
        checks[name] = {"value": value, "limit": limit}
    return bool(ok), checks
