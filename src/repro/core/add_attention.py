"""Binary-code linear attention — the paper's `Add`-reparameterized attention.

Order is exchanged to Q(KᵀV) for linear complexity (paper §4.1), then Q and K
are mapped to binary codes in Hamming space. The similarity kernel is

    sim(q, k) = (b_q · b_k + d) / (2d)  ∈ [0, 1]            (b ∈ {-1,+1}^d)

i.e. the fraction of matching bits (1 − normalized Hamming distance). It is
non-negative, so the linear-attention normalizer is strictly positive — this
is the stability property Ecoformer's kernelized hashing buys, obtained here
with *vanilla* binarization (which the paper shows works better, Tab. 4 obs. 2).

Every MatMul against b_q / b_k is a ±1 contraction ⇒ pure additions — the
paper's MatAdd. The (2d) factor cancels between numerator and denominator.

Forms provided:
- bidirectional (encoder / ViT): two einsums over global sums.
- causal chunked (decoder training / prefill): scan over chunks with a running
  (d_k × d_v) state — the same dataflow the Pallas kernel implements in VMEM.
- decode step: O(1)-state recurrent update — what makes `long_500k` feasible.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.quant import binarize_ste


def _featurize(q, k, feature="binary"):
    """Map q/k to the kernel feature space.

    "binary": hard ±1 Hamming codes (STE-differentiable) with offset d — the
      paper's Add-reparameterized attention.
    "elu1": φ(x) = elu(x)+1 (Katharopoulos linear attention) with offset 0 —
      the paper's plain linear-attention stage (Tab. 4 "Linear Attn" rows).

    Returns (fq, fk, offset); the attention weight is fq·fk + offset ≥ 0.
    """
    if feature == "binary":
        return (binarize_ste(q, with_scale=False),
                binarize_ste(k, with_scale=False),
                float(q.shape[-1]))
    if feature == "elu1":
        return jax.nn.elu(q) + 1.0, jax.nn.elu(k) + 1.0, 0.0
    raise ValueError(feature)


def binary_linear_attention(q, k, v, *, causal=False, chunk=128, train=True,
                            feature="binary", return_state=False,
                            lengths=None):
    """q, k: (B, H, N, Dk); v: (B, H, N, Dv) → (B, H, N, Dv). Non-causal, k
    and v may hold fewer tokens than q.

    With return_state=True (causal only) also returns the final recurrent
    carry {"kv", "ksum", "vsum", "count"} in the init_decode_state layout —
    the chunked-prefill handoff into the O(1) decode path.

    lengths (B,) int32, causal only: per-row valid prompt length for
    end-padded batches. Keys/values at positions >= lengths[b] are masked out
    of the carry and the counts, so the returned state is exactly the state
    of the unpadded row — outputs at padded positions are garbage (they are
    never read: padding sits strictly in every real position's causal
    future).
    """
    if causal:
        return _causal_chunked(q, k, v, chunk=chunk, train=train,
                               feature=feature, return_state=return_state,
                               lengths=lengths)
    if return_state or lengths is not None:
        raise ValueError("return_state/lengths require causal=True (there is "
                         "no recurrent carry in the bidirectional form)")
    return _bidirectional(q, k, v, train=train, feature=feature)


def _bidirectional(q, k, v, train=True, feature="binary"):
    n = k.shape[-2]            # k/v may be a reduced token set (PVT's SRA)
    bq, bk, d = _featurize(q, k, feature)
    kv = jnp.einsum("bhnd,bhne->bhde", bk, v)           # MatAdd (±1 operand)
    ksum = jnp.sum(bk, axis=-2)                          # (B,H,Dk)
    vsum = jnp.sum(v, axis=-2)                           # (B,H,Dv)
    num = jnp.einsum("bhnd,bhde->bhne", bq, kv) + d * vsum[:, :, None, :]
    den = jnp.einsum("bhnd,bhd->bhn", bq, ksum) + jnp.asarray(d * n, q.dtype)
    return num / (den[..., None] + 1e-6)


def _causal_chunked(q, k, v, *, chunk=128, train=True, feature="binary",
                    return_state=False, lengths=None):
    b, h, n, dk_dim = q.shape
    dv = v.shape[-1]
    if n % chunk != 0:
        pad = chunk - n % chunk
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    nc = q.shape[-2] // chunk
    bq, bk, dk = _featurize(q, k, feature)
    if lengths is not None:
        # Per-row valid prompt lengths (bucketed end-padded prefill): masked
        # key positions would featurize to nonzero codes and poison the carry.
        valid = (jnp.arange(q.shape[-2])[None, :]
                 < lengths[:, None]).astype(q.dtype)[:, None, :, None]
        bk = bk * valid
        v = v * valid
    elif q.shape[-2] != n:
        # Padded key positions would featurize to nonzero codes (sign(0)=+1,
        # elu(0)+1=1) and poison the carry; zero them out. Valid outputs are
        # untouched (padding is strictly in the causal future of every real
        # position), so this is safe unconditionally.
        valid = (jnp.arange(q.shape[-2]) < n).astype(q.dtype)[None, None, :, None]
        bk = bk * valid
        v = v * valid

    # (nc, B, H, chunk, D) chunked views for scan.
    def to_chunks(x):
        return x.reshape(b, h, nc, chunk, x.shape[-1]).transpose(2, 0, 1, 3, 4)

    bq_c, bk_c, v_c = to_chunks(bq), to_chunks(bk), to_chunks(v)
    mask = jnp.tril(jnp.ones((chunk, chunk), q.dtype))          # includes self
    pos_in_chunk = jnp.arange(1, chunk + 1, dtype=q.dtype)      # causal count

    def step(carry, xs):
        kv_s, ksum_s, vsum_s, cnt = carry
        bq_i, bk_i, v_i = xs
        # Inter-chunk (history) terms: running-state contractions.
        num = jnp.einsum("bhcd,bhde->bhce", bq_i, kv_s) + dk * vsum_s[:, :, None, :]
        den = jnp.einsum("bhcd,bhd->bhc", bq_i, ksum_s) + dk * cnt
        # Intra-chunk causal term.
        scores = jnp.einsum("bhcd,bhkd->bhck", bq_i, bk_i) + jnp.asarray(dk, q.dtype)
        scores = scores * mask
        num = num + jnp.einsum("bhck,bhke->bhce", scores, v_i)
        den = den + dk * pos_in_chunk  # Σ_{j≤i} d term for in-chunk positions
        den = den + jnp.einsum("bhcd,bhkd,ck->bhc", bq_i, bk_i, mask)
        out_i = num / (den[..., None] + 1e-6)
        # State update.
        kv_s = kv_s + jnp.einsum("bhcd,bhce->bhde", bk_i, v_i)
        ksum_s = ksum_s + jnp.sum(bk_i, axis=-2)
        vsum_s = vsum_s + jnp.sum(v_i, axis=-2)
        cnt = cnt + jnp.asarray(chunk, q.dtype)
        return (kv_s, ksum_s, vsum_s, cnt), out_i

    carry = (
        jnp.zeros((b, h, dk_dim, dv), q.dtype),
        jnp.zeros((b, h, dk_dim), q.dtype),
        jnp.zeros((b, h, dv), q.dtype),
        jnp.asarray(0.0, q.dtype),
    )
    (kv_f, ksum_f, vsum_f, _), out = jax.lax.scan(step, carry, (bq_c, bk_c, v_c))
    out = out.transpose(1, 2, 0, 3, 4).reshape(b, h, nc * chunk, dv)
    out = out[:, :, :n]
    if not return_state:
        return out
    # count is the number of *real* tokens per row (the scan's cnt includes
    # padding). Per-row so a packed decode batch can hold slots at different
    # positions (continuous batching).
    if lengths is not None:
        count = lengths.astype(q.dtype)
    else:
        count = jnp.full((b,), float(n), q.dtype)
    state = {"kv": kv_f, "ksum": ksum_f, "vsum": vsum_f, "count": count}
    return out, state


def init_decode_state(batch, heads, dk, dv, dtype=jnp.float32):
    """O(1) recurrent state for autoregressive decode (replaces the KV cache).

    Every leaf — including "count" — carries the batch axis, so admitting or
    evicting one request from a packed decode batch is a single-axis
    gather/scatter over the whole pytree (serve.lm.BucketedLMEngine).
    """
    return {
        "kv": jnp.zeros((batch, heads, dk, dv), dtype),
        "ksum": jnp.zeros((batch, heads, dk), dtype),
        "vsum": jnp.zeros((batch, heads, dv), dtype),
        "count": jnp.zeros((batch,), dtype),
    }


def binary_linear_attention_step(q_t, k_t, v_t, state, feature="binary"):
    """One decode step. q_t/k_t: (B,H,Dk), v_t: (B,H,Dv). Causal incl. self.

    Featurization goes through the same `_featurize` as the chunked path, so
    the decode step and prefill can never diverge on the code definition.
    """
    bq, bk, d = _featurize(q_t, k_t, feature)
    kv = state["kv"] + bk[..., :, None] * v_t[..., None, :]
    ksum = state["ksum"] + bk
    vsum = state["vsum"] + v_t
    count = state["count"] + 1.0
    num = jnp.einsum("bhd,bhde->bhe", bq, kv) + d * vsum
    den = jnp.einsum("bhd,bhd->bh", bq, ksum) + d * count[:, None]
    out = num / (den[..., None] + 1e-6)
    new_state = {"kv": kv, "ksum": ksum, "vsum": vsum, "count": count}
    return out, new_state


class BinaryLinearAttention:
    """Config wrapper so model code can treat attention math uniformly."""

    def __init__(self, causal=False, chunk=128, feature="binary"):
        self.causal = causal
        self.chunk = chunk
        self.feature = feature

    def __call__(self, q, k, v, train=True):
        return binary_linear_attention(
            q, k, v, causal=self.causal, chunk=self.chunk, train=train,
            feature=self.feature)
