"""Attention layers: GQA softmax (memory-efficient chunked), sliding-window,
MLA (latent KV), and the paper's linear / binary-linear reparameterizations —
all selected by (ModelConfig, ShiftAddPolicy).

Softmax attention uses an online-softmax scan over KV chunks (Flash-style
dataflow in XLA) so peak activation memory is O(N·chunk) instead of O(N²) —
required for the 32k prefill cells to fit the dry-run memory budget.

Decode paths:
- softmax: dense KV cache (B, Hkv, L, Dh), dynamic_update_slice writes.
- local_attn: ring-buffer KV cache of size `window`.
- linear/binary_linear: O(1) recurrent state (core.add_attention) — the
  paper's technique is what makes the 500k-context cells feasible.
- MLA: compressed latent cache (B, L, kv_lora + rope_dim) with the absorbed
  decode form (scores and context computed directly in latent space).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import add_attention as la
from repro.nn import layers as L


# ---------------------------------------------------------------------------
# Chunked online-softmax attention
# ---------------------------------------------------------------------------

def softmax_attention(q, k, v, *, causal=True, window=None, softcap=None,
                      chunk=512, q_offset=0):
    """q: (B, Hq, Nq, D); k, v: (B, Hkv, Nkv, D). GQA-grouped, O(Nq·chunk) mem.

    q_offset: absolute position of q[0] relative to k[0] (prefill continuation
    / decode use). Causal masking compares absolute positions.
    """
    from repro.distributed.sharding import constrain

    b, hq, nq, d = q.shape
    hkv, nkv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = hq // hkv
    qg = q.reshape(b, hkv, g, nq, d)
    # Shard kv-heads over model when divisible; otherwise fall back to
    # sequence parallelism over the query length (indivisible-head archs
    # would otherwise replicate the O(N·chunk) score buffers 16×).
    qg = constrain(qg, ("batch", "kv_heads", None, "seq_model", None))
    chunk = min(chunk, nkv)
    assert nkv % chunk == 0, (nkv, chunk)
    nchunks = nkv // chunk
    kc = k.reshape(b, hkv, nchunks, chunk, d).transpose(2, 0, 1, 3, 4)
    vc = v.reshape(b, hkv, nchunks, chunk, dv).transpose(2, 0, 1, 3, 4)
    scale = d ** -0.5
    q_pos = q_offset + jnp.arange(nq)

    def step(carry, xs):
        m_prev, l_prev, acc = carry
        k_i, v_i, c0 = xs
        s = jnp.einsum("bkgnd,bkcd->bkgnc", qg.astype(jnp.float32),
                       k_i.astype(jnp.float32)) * scale
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        kv_pos = c0 + jnp.arange(chunk)
        valid = jnp.ones((nq, chunk), bool)
        if causal:
            valid &= kv_pos[None, :] <= q_pos[:, None]
        if window is not None:
            valid &= (q_pos[:, None] - kv_pos[None, :]) < window
        s = jnp.where(valid[None, None, None], s, -jnp.inf)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        # Renormalize previous accumulator; guard fully-masked rows.
        m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        p = jnp.where(valid[None, None, None], p, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isneginf(m_prev), -jnp.inf, m_prev) - m_safe)
        alpha = jnp.where(jnp.isneginf(m_prev), 0.0, alpha)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bkgnc,bkcd->bkgnd", p, v_i.astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, hkv, g, nq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, nq), jnp.float32)
    acc0 = jnp.zeros((b, hkv, g, nq, dv), jnp.float32)
    offsets = jnp.arange(nchunks) * chunk
    (m, l, acc), _ = jax.lax.scan(step, (m0, l0, acc0), (kc, vc, offsets))
    out = acc / jnp.maximum(l[..., None], 1e-9)
    return out.reshape(b, hq, nq, dv).astype(v.dtype)


def _repeat_kv(x, g):
    """(B, Hkv, N, D) → (B, Hkv*g, N, D) by group repeat."""
    if g == 1:
        return x
    b, h, n, d = x.shape
    return jnp.broadcast_to(x[:, :, None], (b, h, g, n, d)).reshape(b, h * g, n, d)


# ---------------------------------------------------------------------------
# GQA attention layer (policy-aware)
# ---------------------------------------------------------------------------

class Attention:
    def __init__(self, cfg, layer_kind="attn"):
        self.cfg = cfg
        p = cfg.policy
        self.mode = p.attention          # dense | linear | binary_linear
        self.h = cfg.n_heads
        self.hkv = cfg.n_kv_heads
        self.dh = cfg.head_dim
        self.window = cfg.window if layer_kind == "local_attn" else None
        self.causal = cfg.causal
        dt, pdt = cfg.activation_dtype, cfg.weight_dtype
        lin = p.proj_linear()
        d = cfg.d_model
        qb = cfg.use_bias or cfg.qkv_bias
        self.q_proj = L.make_linear(lin, d, self.h * self.dh, qb, dt, pdt)
        self.k_proj = L.make_linear(lin, d, self.hkv * self.dh, qb, dt, pdt)
        self.v_proj = L.make_linear(lin, d, self.hkv * self.dh, qb, dt, pdt)
        self.o_proj = L.make_linear(lin, self.h * self.dh, d, cfg.use_bias, dt, pdt)
        self.qk_norm = cfg.qk_norm
        if self.qk_norm:
            self.q_norm = L.RMSNorm(self.dh, cfg.norm_eps, dt, pdt)
            self.k_norm = L.RMSNorm(self.dh, cfg.norm_eps, dt, pdt)
        self.dwconv = None
        if self.mode in ("linear", "binary_linear") and p.dwconv_v:
            self.dwconv = L.DWConv1D(self.hkv * self.dh, width=3,
                                     causal=cfg.causal, dtype=dt, param_dtype=pdt)
        self.feature = "binary" if self.mode == "binary_linear" else "elu1"

    def init(self, key):
        ks = jax.random.split(key, 8)
        p = {"q": self.q_proj.init(ks[0]), "k": self.k_proj.init(ks[1]),
             "v": self.v_proj.init(ks[2]), "o": self.o_proj.init(ks[3])}
        if self.qk_norm:
            p["q_norm"] = self.q_norm.init(ks[4])
            p["k_norm"] = self.k_norm.init(ks[5])
        if self.dwconv is not None:
            p["dwconv"] = self.dwconv.init(ks[6])
        return p

    def spec(self, params):
        s = {"q": L.match_linear_spec(params["q"], L.linear_spec("embed", "heads")),
             "k": L.match_linear_spec(params["k"], L.linear_spec("embed", "heads")),
             "v": L.match_linear_spec(params["v"], L.linear_spec("embed", "heads")),
             "o": L.match_linear_spec(params["o"], L.linear_spec("heads", "embed"))}
        if self.qk_norm:
            s["q_norm"] = self.q_norm.spec()
            s["k_norm"] = self.k_norm.spec()
        if self.dwconv is not None:
            s["dwconv"] = {"kernel": (None, "heads"), "bias": ("heads",)}
        return s

    # Serving threads kernel impl/tune explicitly (blocks → here → ops).
    accepts_impl = True

    # -- shared projection helpers ------------------------------------------
    def _qkv(self, params, x, positions, impl=None, tune=None, kv=None):
        """Returns (q, k, v, vraw); vraw is the pre-DWConv V projection —
        the raw stream the decode conv cache is warmed from. q comes from
        x, k and v from `kv` (default x): PVT's spatial-reduction attention
        projects them from a reduced token set."""
        b, n, _ = x.shape
        kv = x if kv is None else kv
        nkv = kv.shape[1]
        q = L.call_linear(self.q_proj, params["q"], x, impl,
                          tune).reshape(b, n, self.h, self.dh)
        k = L.call_linear(self.k_proj, params["k"], kv, impl,
                          tune).reshape(b, nkv, self.hkv, self.dh)
        vraw = L.call_linear(self.v_proj, params["v"], kv, impl, tune)
        vflat = vraw
        if self.dwconv is not None:
            vflat = vflat + self.dwconv(params["dwconv"], vflat)
        v = vflat.reshape(b, nkv, self.hkv, self.dh)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if self.qk_norm:
            q = self.q_norm(params["q_norm"], q)
            k = self.k_norm(params["k_norm"], k)
        q, k = self._rope(q, k, positions)
        return q, k, v, vraw

    def _rope(self, q, k, positions):
        cfg = self.cfg
        if cfg.rope == "none" or positions is None:
            return q, k
        if cfg.rope == "mrope":
            fn = lambda t: L.apply_mrope(t, positions, cfg.mrope_sections, cfg.rope_theta)
        else:
            fn = lambda t: L.apply_rope(t, positions, cfg.rope_theta)
        return fn(q), fn(k)

    # -- full-sequence forward (train / prefill) -----------------------------
    def __call__(self, params, x, positions=None, train=True, kv=None):
        """kv: the token set k and v are projected from (default x)."""
        cfg = self.cfg
        q, k, v, _ = self._qkv(params, x, positions, kv=kv)
        b, _, n, _ = q.shape
        if self.mode == "dense":
            out = softmax_attention(q, k, v, causal=self.causal,
                                    window=self.window,
                                    softcap=cfg.attn_logit_softcap,
                                    chunk=min(512, k.shape[2]))
        else:
            g = self.h // self.hkv
            kf = _repeat_kv(k, g)
            vf = _repeat_kv(v, g)
            out = la.binary_linear_attention(
                q.astype(jnp.float32), kf.astype(jnp.float32),
                vf.astype(jnp.float32), causal=self.causal,
                chunk=min(128, n), train=train,
                feature=self.feature).astype(x.dtype)
        out = out.transpose(0, 2, 1, 3).reshape(b, n, self.h * self.dh)
        return self.o_proj(params["o"], out)

    # -- inference -----------------------------------------------------------
    def infer(self, params, x, positions=None, impl=None, tune=None,
              kv=None):
        """Serving forward. For the encoder binary-linear mode (the ViT path)
        this routes through the fused bidirectional Hamming-attention op
        (kernels.ops.binary_linear_attention_bidir): one pass accumulating
        KV/ksum then emitting outputs, no STE machinery. impl/tune arrive
        threaded from the serving engine (never a process global); every
        other mode falls back to the train=False forward, whose kernels have
        no impl selection. kv: the token set k and v are projected from
        (default x; PVT passes its spatially reduced tokens)."""
        if self.mode != "binary_linear" or self.causal:
            return self(params, x, positions=positions, train=False, kv=kv)
        from repro.kernels import ops

        b, n, _ = x.shape
        q, k, v, _ = self._qkv(params, x, positions, impl=impl, tune=tune,
                               kv=kv)
        g = self.h // self.hkv
        kf = _repeat_kv(k, g)
        vf = _repeat_kv(v, g)
        out = ops.binary_linear_attention_bidir(
            q.astype(jnp.float32), kf.astype(jnp.float32),
            vf.astype(jnp.float32), impl=impl, tune=tune).astype(x.dtype)
        out = out.transpose(0, 2, 1, 3).reshape(b, n, self.h * self.dh)
        return L.call_linear(self.o_proj, params["o"], out, impl, tune)

    # -- decode --------------------------------------------------------------
    # Every cache leaf — positions included — carries the batch axis, so a
    # packed decode batch can hold requests at different positions and
    # admitting/evicting one is a single-axis gather/scatter over the pytree
    # (serve.lm.BucketedLMEngine's continuous batching).
    def init_cache(self, batch, max_len, dtype=jnp.bfloat16):
        if self.mode in ("linear", "binary_linear"):
            state = la.init_decode_state(batch, self.h, self.dh, self.dh, jnp.float32)
            if self.dwconv is not None:
                state["conv"] = jnp.zeros((batch, 2, self.hkv * self.dh), dtype)
            return state
        length = min(max_len, self.window) if self.window else max_len
        if self.cfg.kv_cache_dtype == "int8":
            # Quantized cache (per-token-per-head scales). Scales factor out
            # of both attention contractions, so decode never materializes a
            # dequantized cache copy (see decode_step).
            return {
                "k": jnp.zeros((batch, self.hkv, length, self.dh), jnp.int8),
                "v": jnp.zeros((batch, self.hkv, length, self.dh), jnp.int8),
                "k_scale": jnp.zeros((batch, self.hkv, length), jnp.float32),
                "v_scale": jnp.zeros((batch, self.hkv, length), jnp.float32),
                "slot_pos": jnp.full((batch, length), -1, jnp.int32),
                "pos": jnp.zeros((batch,), jnp.int32),
            }
        return {
            "k": jnp.zeros((batch, self.hkv, length, self.dh), dtype),
            "v": jnp.zeros((batch, self.hkv, length, self.dh), dtype),
            "slot_pos": jnp.full((batch, length), -1, jnp.int32),
            "pos": jnp.zeros((batch,), jnp.int32),
        }

    @staticmethod
    def _quantize_kv(t):
        """(B, Hkv, 1, Dh) → int8 values + (B, Hkv, 1) scales."""
        scale = jnp.max(jnp.abs(t), axis=-1) / 127.0 + 1e-8
        q = jnp.clip(jnp.round(t / scale[..., None]), -127, 127).astype(jnp.int8)
        return q, scale.astype(jnp.float32)

    # -- parallel prefill ----------------------------------------------------
    def prefill(self, params, x, cache, positions=None, lengths=None):
        """Whole-prompt pass against a *fresh* cache. x: (B, N, d_model).

        Returns (y (B, N, d_model), cache) where cache is decode-ready: the
        linear modes hand over the chunked pass's final recurrent carry (one
        O(N) pass instead of N decode steps); dense modes bulk-write K/V.

        lengths (B,) int32: per-row valid prompt length for bucket-padded
        prompts (tokens at positions >= lengths[b] are end-padding). The
        returned cache row is exactly the unpadded row's state; outputs at
        padded positions are garbage (never read — padding is strictly in
        every real position's causal future).
        """
        cfg = self.cfg
        b, n, _ = x.shape
        q, k, v, vraw = self._qkv(params, x, positions)
        if self.mode in ("linear", "binary_linear"):
            g = self.h // self.hkv
            kf = _repeat_kv(k, g)
            vf = _repeat_kv(v, g)
            out, state = la.binary_linear_attention(
                q.astype(jnp.float32), kf.astype(jnp.float32),
                vf.astype(jnp.float32), causal=self.causal, chunk=min(128, n),
                train=False, feature=self.feature, return_state=True,
                lengths=lengths)
            out = out.astype(x.dtype)
            # Accumulate into the caller's carry instead of replacing it: the
            # recurrent state is additive, so this is exact for the fresh
            # (zero) cache and correct for a warm-carry continuation — and it
            # consumes the donated cache buffers (serving audit JX005)
            # instead of allocating a fresh carry next to them.
            new_cache = {name: cache[name] + state[name] for name in state}
            if "conv" in cache:
                new_cache["conv"] = L.trailing_window(
                    vraw, self.dwconv.width - 1, cache["conv"].dtype,
                    lengths=lengths)
        else:
            out = softmax_attention(q, k, v, causal=self.causal,
                                    window=self.window,
                                    softcap=cfg.attn_logit_softcap,
                                    chunk=min(512, n))
            length = cache["k"].shape[2]
            if lengths is not None and n > length:
                raise ValueError("lengths-masked prefill requires the prompt "
                                 f"to fit the cache ({n} > {length})")
            m = min(n, length)          # ring buffer keeps the last `length`
            pos_abs = jnp.arange(n - m, n, dtype=jnp.int32)
            slots = jnp.mod(pos_abs, length)
            k_tail, v_tail = k[:, :, n - m:], v[:, :, n - m:]
            quantized = cfg.kv_cache_dtype == "int8"
            if quantized:
                kq, kscale = self._quantize_kv(k_tail)
                vq, vscale = self._quantize_kv(v_tail)
                ck = cache["k"].at[:, :, slots].set(kq)
                cv = cache["v"].at[:, :, slots].set(vq)
            else:
                ck = cache["k"].at[:, :, slots].set(
                    k_tail.astype(cache["k"].dtype))
                cv = cache["v"].at[:, :, slots].set(
                    v_tail.astype(cache["v"].dtype))
            pos_rows = jnp.broadcast_to(pos_abs[None], (b, m))
            if lengths is not None:
                # Padded rows stay invalid (-1); decode overwrites them
                # write-before-read as pos reaches each slot.
                pos_rows = jnp.where(pos_abs[None] < lengths[:, None],
                                     pos_rows, -1)
            slot_pos = cache["slot_pos"].at[:, slots].set(pos_rows)
            pos_new = (lengths.astype(jnp.int32) if lengths is not None
                       else jnp.full((b,), n, jnp.int32))
            new_cache = {"k": ck, "v": cv, "slot_pos": slot_pos,
                         "pos": pos_new}
            if quantized:
                new_cache["k_scale"] = cache["k_scale"].at[:, :, slots].set(kscale)
                new_cache["v_scale"] = cache["v_scale"].at[:, :, slots].set(vscale)
        out = out.transpose(0, 2, 1, 3).reshape(b, n, self.h * self.dh)
        return self.o_proj(params["o"], out), new_cache

    def decode_step(self, params, x_t, cache):
        """x_t: (B, d_model) one token. Returns (y_t, cache).

        Positions are per-row ((B,) in the cache), so a packed continuous
        decode batch can hold requests at different depths.
        """
        b = x_t.shape[0]
        pos = cache["count"].astype(jnp.int32) if "count" in cache else cache["pos"]
        positions = pos[:, None]
        if self.cfg.rope == "mrope":
            positions = jnp.broadcast_to(pos[:, None, None], (b, 3, 1))
        x = x_t[:, None, :]
        q = self.q_proj(params["q"], x).reshape(b, 1, self.h, self.dh)
        k = self.k_proj(params["k"], x).reshape(b, 1, self.hkv, self.dh)
        vflat = self.v_proj(params["v"], x)
        if self.dwconv is not None and "conv" in cache:
            vconv, conv_state = self.dwconv.step(params["dwconv"], vflat[:, 0], cache["conv"])
            vflat = vflat + vconv[:, None]
        else:
            conv_state = None
        v = vflat.reshape(b, 1, self.hkv, self.dh)
        q, k, v = (t.transpose(0, 2, 1, 3) for t in (q, k, v))
        if self.qk_norm:
            q = self.q_norm(params["q_norm"], q)
            k = self.k_norm(params["k_norm"], k)
        q, k = self._rope(q, k, positions)

        if self.mode in ("linear", "binary_linear"):
            g = self.h // self.hkv
            kf = _repeat_kv(k, g)[:, :, 0].astype(jnp.float32)
            vf = _repeat_kv(v, g)[:, :, 0].astype(jnp.float32)
            state = {n: cache[n] for n in ("kv", "ksum", "vsum", "count")}
            out, state = la.binary_linear_attention_step(
                q[:, :, 0].astype(jnp.float32), kf, vf, state, self.feature)
            if conv_state is not None:
                state["conv"] = conv_state
            out = out[:, :, None].astype(x_t.dtype)
            new_cache = state
        else:
            quantized = self.cfg.kv_cache_dtype == "int8"
            length = cache["k"].shape[2]
            slot = jnp.mod(pos, length)                     # (B,)
            rows = jnp.arange(b)
            # Per-row ring-buffer write: row i lands at its own slot[i]
            # (advanced-index scatter; the advanced axes move to the front,
            # which matches the (B, Hkv, Dh) value layout).
            if quantized:
                kq, kscale = self._quantize_kv(k)
                vq, vscale = self._quantize_kv(v)
                ck = cache["k"].at[rows, :, slot].set(kq[:, :, 0])
                cv = cache["v"].at[rows, :, slot].set(vq[:, :, 0])
                ks = cache["k_scale"].at[rows, :, slot].set(kscale[:, :, 0])
                vs = cache["v_scale"].at[rows, :, slot].set(vscale[:, :, 0])
            else:
                ck = cache["k"].at[rows, :, slot].set(
                    k[:, :, 0].astype(cache["k"].dtype))
                cv = cache["v"].at[rows, :, slot].set(
                    v[:, :, 0].astype(cache["v"].dtype))
            slot_pos = cache["slot_pos"].at[rows, slot].set(pos)
            qg = q.reshape(b, self.hkv, self.h // self.hkv, self.dh)
            # preferred_element_type avoids materializing an f32 copy of the
            # whole cache (the dominant decode temp buffer otherwise). For the
            # int8 cache the per-token scales factor OUT of the contraction
            # (s_l = (q · k_l) · scale_l), so no dequantized copy exists at all.
            s = jnp.einsum("bkgd,bkld->bkgl", qg,
                           ck.astype(qg.dtype) if not quantized else
                           ck.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32) * (self.dh ** -0.5)
            if quantized:
                s = s * ks[:, :, None, :]
            if self.cfg.attn_logit_softcap:
                s = jnp.tanh(s / self.cfg.attn_logit_softcap) * self.cfg.attn_logit_softcap
            valid = (slot_pos >= 0) & (slot_pos <= pos[:, None])
            if self.window:
                valid &= (pos[:, None] - slot_pos) < self.window
            s = jnp.where(valid[:, None, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            if quantized:
                p = p * vs[:, :, None, :]          # fold V scales into probs
                out = jnp.einsum("bkgl,bkld->bkgd", p.astype(jnp.bfloat16),
                                 cv.astype(jnp.bfloat16),
                                 preferred_element_type=jnp.float32)
            else:
                out = jnp.einsum("bkgl,bkld->bkgd", p.astype(cv.dtype), cv,
                                 preferred_element_type=jnp.float32)
            out = out.reshape(b, self.h, 1, self.dh).astype(x_t.dtype)
            new_cache = {"k": ck, "v": cv, "slot_pos": slot_pos, "pos": pos + 1}
            if quantized:
                new_cache.update(k_scale=ks, v_scale=vs)

        out = out.transpose(0, 2, 1, 3).reshape(b, self.h * self.dh)
        return self.o_proj(params["o"], out), new_cache


# ---------------------------------------------------------------------------
# Multi-head latent attention (MiniCPM3 / DeepSeek-V2 style)
# ---------------------------------------------------------------------------

class MLAttention:
    def __init__(self, cfg):
        self.cfg = cfg
        m = cfg.mla
        self.m = m
        self.h = cfg.n_heads
        dt, pdt = cfg.activation_dtype, cfg.weight_dtype
        lin = cfg.policy.proj_linear()
        d = cfg.d_model
        qk_head = m.qk_nope_head_dim + m.qk_rope_head_dim
        self.qk_head = qk_head
        self.q_down = L.make_linear(lin, d, m.q_lora_rank, False, dt, pdt)
        self.q_up = L.make_linear(lin, m.q_lora_rank, self.h * qk_head, False, dt, pdt)
        self.kv_down = L.make_linear(lin, d, m.kv_lora_rank + m.qk_rope_head_dim,
                                     False, dt, pdt)
        self.kv_up = L.make_linear(lin, m.kv_lora_rank,
                                   self.h * (m.qk_nope_head_dim + m.v_head_dim),
                                   False, dt, pdt)
        self.o_proj = L.make_linear(lin, self.h * m.v_head_dim, d, False, dt, pdt)
        self.q_norm = L.RMSNorm(m.q_lora_rank, cfg.norm_eps, dt, pdt)
        self.kv_norm = L.RMSNorm(m.kv_lora_rank, cfg.norm_eps, dt, pdt)
        self.mode = cfg.policy.attention
        self.feature = "binary" if self.mode == "binary_linear" else "elu1"

    def init(self, key):
        ks = jax.random.split(key, 7)
        return {"q_down": self.q_down.init(ks[0]), "q_up": self.q_up.init(ks[1]),
                "kv_down": self.kv_down.init(ks[2]), "kv_up": self.kv_up.init(ks[3]),
                "o": self.o_proj.init(ks[4]), "q_norm": self.q_norm.init(ks[5]),
                "kv_norm": self.kv_norm.init(ks[6])}

    def spec(self, params):
        return {
            "q_down": L.match_linear_spec(params["q_down"], L.linear_spec("embed", None)),
            "q_up": L.match_linear_spec(params["q_up"], L.linear_spec(None, "heads")),
            "kv_down": L.match_linear_spec(params["kv_down"], L.linear_spec("embed", None)),
            "kv_up": L.match_linear_spec(params["kv_up"], L.linear_spec(None, "heads")),
            "o": L.match_linear_spec(params["o"], L.linear_spec("heads", "embed")),
            "q_norm": self.q_norm.spec(), "kv_norm": self.kv_norm.spec(),
        }

    def _project(self, params, x, positions):
        b, n, _ = x.shape
        m = self.m
        q = self.q_up(params["q_up"],
                      self.q_norm(params["q_norm"],
                                  self.q_down(params["q_down"], x)))
        q = q.reshape(b, n, self.h, self.qk_head).transpose(0, 2, 1, 3)
        q_nope, q_rope = jnp.split(q, [m.qk_nope_head_dim], axis=-1)
        kvd = self.kv_down(params["kv_down"], x)
        c_kv, k_rope = jnp.split(kvd, [m.kv_lora_rank], axis=-1)
        c_kv = self.kv_norm(params["kv_norm"], c_kv)                 # (B,N,r)
        k_rope = k_rope[:, None]                                     # (B,1,N,rope)
        if positions is not None:
            q_rope = L.apply_rope(q_rope, positions, self.cfg.rope_theta)
            k_rope = L.apply_rope(k_rope, positions, self.cfg.rope_theta)
        return q_nope, q_rope, c_kv, k_rope

    def _assemble_qkv(self, params, x, positions):
        """Full per-head (q, k, v) plus the latent (c_kv, k_rope) streams —
        shared by __call__ and prefill so their math can never diverge."""
        b, n, _ = x.shape
        m = self.m
        q_nope, q_rope, c_kv, k_rope = self._project(params, x, positions)
        kv = self.kv_up(params["kv_up"], c_kv)
        kv = kv.reshape(b, n, self.h, m.qk_nope_head_dim + m.v_head_dim)
        kv = kv.transpose(0, 2, 1, 3)
        k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            k_rope, (b, self.h, n, m.qk_rope_head_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        return q, k, v, c_kv, k_rope

    def __call__(self, params, x, positions=None, train=True):
        b, n, _ = x.shape
        m = self.m
        q, k, v, _, _ = self._assemble_qkv(params, x, positions)
        if self.mode == "dense":
            out = softmax_attention(q, k, v, causal=self.cfg.causal,
                                    chunk=min(512, n))
        else:
            out = la.binary_linear_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=self.cfg.causal,
                chunk=min(128, n), train=train,
                feature=self.feature).astype(x.dtype)
        out = out.transpose(0, 2, 1, 3).reshape(b, n, self.h * m.v_head_dim)
        return self.o_proj(params["o"], out)

    # -- decode: compressed latent cache + absorbed form ---------------------
    def init_cache(self, batch, max_len, dtype=jnp.bfloat16):
        m = self.m
        if self.mode in ("linear", "binary_linear"):
            return la.init_decode_state(batch, self.h, self.qk_head,
                                        m.v_head_dim, jnp.float32)
        return {
            "c_kv": jnp.zeros((batch, max_len, m.kv_lora_rank), dtype),
            "k_rope": jnp.zeros((batch, max_len, m.qk_rope_head_dim), dtype),
            "pos": jnp.zeros((batch,), jnp.int32),
        }

    def prefill(self, params, x, cache, positions=None, lengths=None):
        """Whole-prompt pass against a fresh cache → (y, decode-ready cache).

        Linear modes hand over the chunked pass's final recurrent carry; the
        dense mode bulk-writes the compressed latent (c_kv, k_rope) rows.
        """
        b, n, _ = x.shape
        m = self.m
        q, k, v, c_kv, k_rope = self._assemble_qkv(params, x, positions)
        if self.mode in ("linear", "binary_linear"):
            out, state = la.binary_linear_attention(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v.astype(jnp.float32), causal=self.cfg.causal,
                chunk=min(128, n), train=False, feature=self.feature,
                return_state=True, lengths=lengths)
            out = out.astype(x.dtype)
            # Additive carry: accumulate into the donated cache (see the
            # GQA prefill above — exact for zeros, JX005-consumable).
            new_cache = {name: cache[name] + state[name] for name in state}
        else:
            out = softmax_attention(q, k, v, causal=self.cfg.causal,
                                    chunk=min(512, n))
            ck = jax.lax.dynamic_update_slice(
                cache["c_kv"], c_kv.astype(cache["c_kv"].dtype), (0, 0, 0))
            cr = jax.lax.dynamic_update_slice(
                cache["k_rope"], k_rope[:, 0].astype(cache["k_rope"].dtype),
                (0, 0, 0))
            # Padded latent rows beyond lengths[b] hold garbage but stay
            # masked (valid = slot <= pos) until decode overwrites them
            # write-before-read as pos reaches each row.
            pos_new = (lengths.astype(jnp.int32) if lengths is not None
                       else jnp.full((b,), n, jnp.int32))
            new_cache = {"c_kv": ck, "k_rope": cr, "pos": pos_new}
        out = out.transpose(0, 2, 1, 3).reshape(b, n, self.h * m.v_head_dim)
        return self.o_proj(params["o"], out), new_cache

    def decode_step(self, params, x_t, cache):
        b = x_t.shape[0]
        m = self.m
        pos = cache["count"].astype(jnp.int32) if "count" in cache else cache["pos"]
        positions = pos[:, None]
        x = x_t[:, None, :]
        q_nope, q_rope, c_kv, k_rope = self._project(params, x, positions)

        if self.mode in ("linear", "binary_linear"):
            kv = self.kv_up(params["kv_up"], c_kv)
            kv = kv.reshape(b, 1, self.h, m.qk_nope_head_dim + m.v_head_dim)
            kv = kv.transpose(0, 2, 1, 3)
            k_nope, v = jnp.split(kv, [m.qk_nope_head_dim], axis=-1)
            k = jnp.concatenate([k_nope[:, :, 0], jnp.broadcast_to(
                k_rope[:, :, 0], (b, self.h, m.qk_rope_head_dim))], axis=-1)
            q = jnp.concatenate([q_nope[:, :, 0], q_rope[:, :, 0]], axis=-1)
            out, cache = la.binary_linear_attention_step(
                q.astype(jnp.float32), k.astype(jnp.float32),
                v[:, :, 0].astype(jnp.float32), cache, self.feature)
            out = out.astype(x_t.dtype)
        else:
            rows = jnp.arange(b)
            # Per-row latent write at each row's own position.
            ck = cache["c_kv"].at[rows, pos].set(
                c_kv[:, 0].astype(cache["c_kv"].dtype))
            cr = cache["k_rope"].at[rows, pos].set(
                k_rope[:, 0, 0].astype(cache["k_rope"].dtype))
            # Absorbed form: W_uk into q, W_uv out of the latent context.
            w_kv = params["kv_up"].get("kernel")
            if w_kv is None:  # shift-packed projections: reconstruct
                from repro.core.quant import po2_weight_from_packed
                w_kv = (po2_weight_from_packed(params["kv_up"]["w_packed"])
                        if "w_packed" in params["kv_up"]
                        else params["kv_up"]["w_latent"])
            w_kv = w_kv.reshape(m.kv_lora_rank, self.h,
                                m.qk_nope_head_dim + m.v_head_dim)
            w_uk, w_uv = jnp.split(w_kv, [m.qk_nope_head_dim], axis=-1)
            dt = ck.dtype
            q_abs = jnp.einsum("bhn,rhn->bhr", q_nope[:, :, 0].astype(dt),
                               w_uk.astype(dt), preferred_element_type=jnp.float32)
            s = jnp.einsum("bhr,blr->bhl", q_abs.astype(dt), ck,
                           preferred_element_type=jnp.float32)
            s += jnp.einsum("bhp,blp->bhl", q_rope[:, :, 0].astype(dt), cr,
                            preferred_element_type=jnp.float32)
            s *= self.qk_head ** -0.5
            valid = jnp.arange(ck.shape[1])[None, :] <= pos[:, None]
            s = jnp.where(valid[:, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            ctx = jnp.einsum("bhl,blr->bhr", p.astype(dt), ck,
                             preferred_element_type=jnp.float32)
            out = jnp.einsum("bhr,rhv->bhv", ctx.astype(dt), w_uv.astype(dt),
                             preferred_element_type=jnp.float32)
            out = out.astype(x_t.dtype)
            cache = {"c_kv": ck, "k_rope": cr, "pos": pos + 1}

        out = out.reshape(b, self.h * m.v_head_dim)
        return self.o_proj(params["o"], out), cache
