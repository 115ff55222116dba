"""Substrate layers: norms, embeddings, rotary (incl. M-RoPE), MLPs, DWConv.

Every weight-bearing layer honors the ShiftAddPolicy through `make_linear`:
dense (Mult.) or ShiftLinear (s·2^P). Each module exposes `.spec()` — a
pytree of logical-axis name tuples mirroring its params — consumed by
repro.distributed.sharding to produce mesh PartitionSpecs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.dense import Dense
from repro.core.shift_linear import ShiftLinear


def make_linear(kind, d_in, d_out, use_bias=False, dtype=jnp.bfloat16,
                param_dtype=jnp.float32):
    """kind: "dense" | "shift" | "shift_packed" — the policy switch for one
    projection (packed = int8 deployment format, frozen)."""
    if kind == "dense":
        return Dense(d_in, d_out, use_bias=use_bias, dtype=dtype,
                     param_dtype=param_dtype)
    mode = "packed" if kind == "shift_packed" else "latent"
    return ShiftLinear(d_in, d_out, use_bias=use_bias, dtype=dtype,
                       param_dtype=param_dtype, mode=mode)


def call_linear(layer, params, x, impl=None, tune=None):
    """Apply a `make_linear` product, threading kernel impl/tune selection to
    layers that have one (ShiftLinear → kernels.ops). Dense has no kernel
    selection; the kwargs stop here instead of leaking a process global into
    the call (the old `ops.default_impl()` memoization bug)."""
    if getattr(layer, "accepts_impl", False):
        return layer(params, x, impl=impl, tune=tune)
    return layer(params, x)


def linear_spec(in_axis, out_axis, use_bias=False):
    """Logical spec for Dense/ShiftLinear params (same tree keys either way:
    kernel/w_latent/w_packed are all (in, out))."""
    spec = {"kernel": (in_axis, out_axis)}
    if use_bias:
        spec["bias"] = (out_axis,)
    return spec


def match_linear_spec(params, spec):
    """Rename the kernel key of a linear spec to match actual param keys."""
    out = {}
    for key in params:
        if key == "bias":
            out["bias"] = spec.get("bias", (spec["kernel"][-1],))
        else:
            out[key] = spec["kernel"]
    return out


class RMSNorm:
    def __init__(self, dim, eps=1e-6, dtype=jnp.bfloat16, param_dtype=jnp.float32):
        self.dim, self.eps, self.dtype, self.param_dtype = dim, eps, dtype, param_dtype

    def init(self, key):
        return {"scale": jnp.ones((self.dim,), self.param_dtype)}

    def spec(self):
        return {"scale": (None,)}

    def __call__(self, params, x):
        x32 = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
        y = x32 * jax.lax.rsqrt(var + self.eps)
        return (y * params["scale"].astype(jnp.float32)).astype(self.dtype)


class LayerNorm:
    def __init__(self, dim, eps=1e-6, dtype=jnp.bfloat16, param_dtype=jnp.float32):
        self.dim, self.eps, self.dtype, self.param_dtype = dim, eps, dtype, param_dtype

    def init(self, key):
        return {"scale": jnp.ones((self.dim,), self.param_dtype),
                "bias": jnp.zeros((self.dim,), self.param_dtype)}

    def spec(self):
        return {"scale": (None,), "bias": (None,)}

    def __call__(self, params, x):
        x32 = x.astype(jnp.float32)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.var(x32, axis=-1, keepdims=True)
        y = (x32 - mean) * jax.lax.rsqrt(var + self.eps)
        y = y * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
        return y.astype(self.dtype)


def make_norm(kind, dim, eps, dtype, param_dtype):
    cls = RMSNorm if kind == "rmsnorm" else LayerNorm
    return cls(dim, eps, dtype, param_dtype)


class Embedding:
    def __init__(self, vocab, dim, dtype=jnp.bfloat16, param_dtype=jnp.float32):
        self.vocab, self.dim, self.dtype, self.param_dtype = vocab, dim, dtype, param_dtype

    def init(self, key):
        table = jax.random.normal(key, (self.vocab, self.dim), jnp.float32) * 0.02
        return {"table": table.astype(self.param_dtype)}

    def spec(self):
        return {"table": ("vocab", "embed")}

    def __call__(self, params, ids):
        return params["table"].astype(self.dtype)[ids]

    def attend(self, params, x):
        """Tied output head: logits = x @ tableᵀ."""
        return jnp.einsum("...d,vd->...v", x.astype(self.dtype),
                          params["table"].astype(self.dtype))


# ---------------------------------------------------------------------------
# Rotary embeddings (standard + M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim, theta=10000.0):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def _rotate(x, sin, cos):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def apply_rope(x, positions, theta=10000.0):
    """x: (B, H, N, D); positions: (B, N) int32."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                   # (D/2,)
    ang = positions.astype(jnp.float32)[:, None, :, None] * freqs  # (B,1,N,D/2)
    return _rotate(x.astype(jnp.float32), jnp.sin(ang), jnp.cos(ang)).astype(x.dtype)


def apply_mrope(x, positions, sections, theta=10000.0):
    """Multimodal RoPE (Qwen2-VL): positions (B, 3, N) = (t, h, w) ids;
    the head-dim frequency bands are split across the three position streams.
    sections: per-stream *pair* counts summing to D/2 (e.g. 16/24/24 for D=128).
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                                   # (D/2,)
    assert sum(sections) == d // 2, (sections, d)
    # Build a (B, N, D/2) angle tensor: each frequency band uses the position
    # stream its section assigns.
    parts = []
    start = 0
    for s_idx, width in enumerate(sections):
        f = freqs[start:start + width]
        pos = positions[:, s_idx].astype(jnp.float32)              # (B, N)
        parts.append(pos[:, :, None] * f)                          # (B,N,width)
        start += width
    ang = jnp.concatenate(parts, axis=-1)[:, None]                 # (B,1,N,D/2)
    return _rotate(x.astype(jnp.float32), jnp.sin(ang), jnp.cos(ang)).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs (policy-aware)
# ---------------------------------------------------------------------------

class MLP:
    """mlp: up→act→down.  swiglu/geglu: (gate, up)→act(gate)*up→down."""

    def __init__(self, d_model, d_ff, kind="swiglu", linear="dense",
                 use_bias=False, dtype=jnp.bfloat16, param_dtype=jnp.float32):
        self.kind = kind
        self.gated = kind in ("swiglu", "geglu")
        self.act = {"swiglu": jax.nn.silu, "geglu": jax.nn.gelu, "mlp": jax.nn.gelu}[kind]
        mk = lambda i, o: make_linear(linear, i, o, use_bias, dtype, param_dtype)
        if self.gated:
            self.gate = mk(d_model, d_ff)
        self.up = mk(d_model, d_ff)
        self.down = mk(d_ff, d_model)

    def init(self, key):
        ks = jax.random.split(key, 3)
        p = {"up": self.up.init(ks[0]), "down": self.down.init(ks[1])}
        if self.gated:
            p["gate"] = self.gate.init(ks[2])
        return p

    def spec(self, params):
        s = {"up": match_linear_spec(params["up"], linear_spec("embed", "mlp")),
             "down": match_linear_spec(params["down"], linear_spec("mlp", "embed"))}
        if self.gated:
            s["gate"] = match_linear_spec(params["gate"], linear_spec("embed", "mlp"))
        return s

    # Shift-MLPs route through ShiftLinear; serving threads impl/tune here.
    accepts_impl = True

    def __call__(self, params, x, impl=None, tune=None, mid=None):
        """mid: a function of the hidden activations (..., N, d_ff) that
        takes the activation's place (PVT's Mix-FFN: a depthwise conv over
        the token grid, then GELU); ungated MLPs only."""
        h = call_linear(self.up, params["up"], x, impl, tune)
        if mid is not None:
            assert not self.gated, "mid replaces the activation of an ungated MLP"
            h = mid(h)
        elif self.gated:
            h = self.act(call_linear(self.gate, params["gate"], x,
                                     impl, tune)) * h
        else:
            h = self.act(h)
        return call_linear(self.down, params["down"], h, impl, tune)


class DWConv1D:
    """Depthwise temporal conv. Causal for decoders (RG-LRU conv, V-branch
    DWConv of the paper's linear attention); 'same' for encoders."""

    def __init__(self, dim, width=4, causal=True, dtype=jnp.bfloat16,
                 param_dtype=jnp.float32):
        self.dim, self.width, self.causal = dim, width, causal
        self.dtype, self.param_dtype = dtype, param_dtype

    def init(self, key):
        k = jax.random.normal(key, (self.width, self.dim), jnp.float32)
        return {"kernel": (k * (self.width ** -0.5)).astype(self.param_dtype),
                "bias": jnp.zeros((self.dim,), self.param_dtype)}

    def spec(self):
        return {"kernel": (None, "embed"), "bias": (None,)}

    def __call__(self, params, x):
        """x: (B, N, D) → (B, N, D).

        Computed as `width` shifted multiply-adds rather than a grouped
        conv_general_dilated: a depthwise conv with D groups lowers to a
        pathologically slow per-channel loop on CPU XLA (~19 ms/layer at
        D=128 — it single-handedly kept the stage-1 serving arm 4× over
        dense), while the shifted-add form is three fused elementwise FMAs.
        """
        w = params["kernel"].astype(self.dtype)
        n = x.shape[1]
        if self.causal:
            left, right = self.width - 1, 0
        else:
            left, right = (self.width - 1) // 2, self.width // 2
        xp = jnp.pad(x.astype(self.dtype), ((0, 0), (left, right), (0, 0)))
        y = xp[:, 0:n, :] * w[0]
        for t in range(1, self.width):
            y = y + xp[:, t:t + n, :] * w[t]
        return y + params["bias"].astype(self.dtype)

    def step(self, params, x_t, conv_state):
        """Decode step. x_t: (B, D); conv_state: (B, width-1, D)."""
        w = params["kernel"].astype(self.dtype)
        window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)
        y = jnp.einsum("bwd,wd->bd", window.astype(self.dtype), w)
        return y + params["bias"].astype(self.dtype), window[:, 1:]


def trailing_window(x, w, dtype=None, lengths=None):
    """Last `w` steps of x (B, N, D), front-zero-padded to exactly `w`.

    Warms a causal-conv decode state from a full-sequence (prefill) pass: the
    zeros for N < w reproduce the conv's implicit causal left-padding.

    lengths (B,) int32: per-row valid length for end-padded batches — row b's
    window ends at position lengths[b]-1, with the same zero left-padding for
    lengths[b] < w.
    """
    b, n, d = x.shape
    if lengths is not None:
        idx = lengths[:, None] - w + jnp.arange(w, dtype=lengths.dtype)[None, :]
        tail = jnp.take_along_axis(x, jnp.clip(idx, 0, n - 1)[:, :, None],
                                   axis=1)
        tail = jnp.where(idx[:, :, None] >= 0, tail, 0)
        return tail.astype(dtype or x.dtype)
    tail = x[:, max(0, n - w):]
    if n < w:
        tail = jnp.pad(tail, ((0, 0), (w - n, 0), (0, 0)))
    return tail.astype(dtype or x.dtype)
