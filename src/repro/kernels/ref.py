"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are tested against (shape/dtype sweeps
in tests/test_kernels_*.py). Deliberately naive; no fusion, no chunking.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.quant import po2_weight_from_packed


def shift_matmul_ref(x, w_packed, out_dtype=None):
    """x: (M, K) float; w_packed: (K, N) int8 (sign|P+64). y = x @ (s * 2^P)."""
    w = po2_weight_from_packed(w_packed, jnp.float32)
    y = jnp.dot(x.astype(jnp.float32), w)
    return y.astype(out_dtype or x.dtype)


def add_matmul_ref(x, b, out_dtype=None):
    """x: (G, M, K) float; b: (G, K, N) int8 in {-1, 0, +1}. y = x @ b.

    A MatMul against a ±1 operand — semantically pure accumulation (the
    paper's MatAdd). Zeros are allowed (they encode padding / skipped weights).
    """
    y = jnp.einsum("gmk,gkn->gmn", x.astype(jnp.float32), b.astype(jnp.float32))
    return y.astype(out_dtype or x.dtype)


def binary_linear_attention_ref(q, k, v, causal=True):
    """Naive quadratic oracle of the Hamming-kernel linear attention.

    q: (B, H, Nq, Dk) float; k: (B, H, Nkv, Dk); v: (B, H, Nkv, Dv). Causal
    needs Nq = Nkv; non-causal takes any Nkv (keys and values over a reduced
    token set, as PVT's spatial-reduction attention has them).
    sim(i,j) = (b_qi . b_kj + d) / (2d); out_i = sum_j sim v_j / sum_j sim.
    The (2d) cancels; this oracle keeps the raw (b.b + d) weights.
    """
    d = q.shape[-1]
    n = q.shape[-2]
    bq = jnp.where(q >= 0, 1.0, -1.0)
    bk = jnp.where(k >= 0, 1.0, -1.0)
    scores = jnp.einsum("bhnd,bhmd->bhnm", bq, bk) + d
    if causal:
        assert k.shape[-2] == n, (n, k.shape[-2])
        mask = jnp.tril(jnp.ones((n, n)))
        scores = scores * mask
    out = jnp.einsum("bhnm,bhme->bhne", scores, v.astype(jnp.float32))
    den = jnp.sum(scores, axis=-1, keepdims=True)
    return (out / (den + 1e-6)).astype(v.dtype)


def binary_linear_attention_state_ref(q, k, v):
    """Final recurrent carry after consuming the whole sequence.

    Matches core.add_attention.init_decode_state layout: the state a chunked
    prefill must hand to binary_linear_attention_step for token N+1.
    """
    n = k.shape[-2]
    bk = jnp.where(k >= 0, 1.0, -1.0).astype(jnp.float32)
    v32 = v.astype(jnp.float32)
    return {
        "kv": jnp.einsum("bhnd,bhne->bhde", bk, v32),
        "ksum": jnp.sum(bk, axis=-2),
        "vsum": jnp.sum(v32, axis=-2),
        "count": jnp.asarray(float(n), jnp.float32),
    }
