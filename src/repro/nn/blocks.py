"""Transformer block: (mixer, feed) pair selected by (config, policy).

mixer ∈ {GQA attention, local attention, MLA, RG-LRU, RWKV6 time-mix}
feed  ∈ {MLP (dense|shift), MoE-of-primitives (the paper), token-choice MoE
         (the architecture's own), RWKV6 channel-mix}

Pre-norm residual wiring; `parallel_block=True` gives the GPT-J/Command-R
parallel attention+FFN form. Every block returns (x, aux_scalars) where aux
carries MoE balance losses (summed over layers by the model).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.moe_primitives import MoEPrimitives
from repro.nn import layers as L
from repro.nn.attention import Attention, MLAttention
from repro.nn.moe import TokenChoiceMoE
from repro.nn.recurrent import RGLRUBlock, RWKV6ChannelMix, RWKV6TimeMix

ZERO_AUX = {"balance_loss": jnp.float32(0.0), "drop_fraction": jnp.float32(0.0)}


def _make_mixer(cfg, kind):
    if kind in ("attn", "local_attn"):
        if cfg.mla is not None:
            return MLAttention(cfg)
        return Attention(cfg, layer_kind=kind)
    if kind == "rglru":
        return RGLRUBlock(cfg)
    if kind == "rwkv6":
        return RWKV6TimeMix(cfg)
    raise ValueError(kind)


def _make_feed(cfg, kind):
    dt, pdt = cfg.activation_dtype, cfg.weight_dtype
    p = cfg.policy
    if kind == "rwkv6":
        return RWKV6ChannelMix(cfg)
    if cfg.moe is not None:
        return TokenChoiceMoE(cfg)
    if p.mlp == "moe_primitives":
        experts = [
            L.MLP(cfg.d_model, cfg.d_ff, cfg.mlp_kind,
                  "dense" if ek == "mult" else p.mlp_linear(),
                  cfg.use_bias, dt, pdt)
            for ek in p.moe_experts
        ]
        # No explicit latencies: the analytic model is evaluated at the
        # model's DEPLOYMENT per-group token count (a ViT dispatches one
        # image row of n_patches tokens per group — the regime the capacity
        # split serves in). LM configs have no fixed per-group count (prefill
        # groups a whole prompt, decode a single token), so they leave the
        # ref unset and keep the nominal-regime constant — the split must not
        # vary with group size or prefill and decode route differently. The
        # telemetry loop (serve.telemetry.apply_expert_latencies) drops
        # measured values in afterwards either way.
        return MoEPrimitives(cfg.d_model, cfg.d_ff, expert_kinds=p.moe_experts,
                             capacity_factor=cfg.moe_primitives_capacity,
                             latency_aware=p.latency_aware, router_noise=0.0,
                             dtype=dt, param_dtype=pdt, experts=experts,
                             capacity_ref_tokens=cfg.moe_capacity_ref_tokens)
    lin = p.mlp_linear() if p.mlp == "shift" else "dense"
    return L.MLP(cfg.d_model, cfg.d_ff, cfg.mlp_kind, lin, cfg.use_bias, dt, pdt)


class TransformerBlock:
    def __init__(self, cfg, kind="attn"):
        self.cfg = cfg
        self.kind = kind
        self.parallel = getattr(cfg, "parallel_block", False)
        self.mixer = _make_mixer(cfg, kind)
        self.feed = _make_feed(cfg, kind)
        dt, pdt = cfg.activation_dtype, cfg.weight_dtype
        self.norm1 = L.make_norm(cfg.norm, cfg.d_model, cfg.norm_eps, dt, pdt)
        self.norm2 = None if self.parallel else L.make_norm(
            cfg.norm, cfg.d_model, cfg.norm_eps, dt, pdt)
        self._feed_has_aux = isinstance(self.feed, (TokenChoiceMoE, MoEPrimitives))

    def init(self, key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        p = {"mixer": self.mixer.init(k1), "feed": self.feed.init(k2),
             "norm1": self.norm1.init(k3)}
        if self.norm2 is not None:
            p["norm2"] = self.norm2.init(k4)
        return p

    def spec(self, params):
        s = {"mixer": self.mixer.spec(params["mixer"]),
             "feed": self.feed.spec(params["feed"]),
             "norm1": self.norm1.spec()}
        if self.norm2 is not None:
            s["norm2"] = self.norm2.spec()
        return s

    def _apply_feed(self, params, x, train):
        if self._feed_has_aux:
            y, aux = self.feed(params["feed"], x, train=train)
            return y, {"balance_loss": aux["balance_loss"].astype(jnp.float32),
                       "drop_fraction": aux["drop_fraction"].astype(jnp.float32)}
        return self.feed(params["feed"], x), ZERO_AUX

    def __call__(self, params, x, positions=None, train=True):
        h = self.norm1(params["norm1"], x)
        mix = self.mixer(params["mixer"], h, positions=positions, train=train)
        if self.parallel:
            ff, aux = self._apply_feed(params, h, train)
            return x + mix + ff, aux
        x = x + mix
        h2 = self.norm2(params["norm2"], x)
        ff, aux = self._apply_feed(params, h2, train)
        return x + ff, aux

    # -- inference -----------------------------------------------------------
    # impl/tune thread from the engine to the kernel-selecting leaves
    # (ShiftLinear, the fused attention op) and stop at layers without one.
    def _infer_feed(self, params, x, impl=None, tune=None):
        if hasattr(self.feed, "infer"):
            if getattr(self.feed, "accepts_impl", False):
                return self.feed.infer(params["feed"], x, impl=impl,
                                       tune=tune)
            return self.feed.infer(params["feed"], x)
        if self._feed_has_aux:
            y, _ = self.feed(params["feed"], x, train=False)
            return y
        if getattr(self.feed, "accepts_impl", False):
            return self.feed(params["feed"], x, impl=impl, tune=tune)
        return self.feed(params["feed"], x)

    def _infer_mixer(self, params, h, positions, impl=None, tune=None):
        if hasattr(self.mixer, "infer"):
            if getattr(self.mixer, "accepts_impl", False):
                return self.mixer.infer(params["mixer"], h,
                                        positions=positions, impl=impl,
                                        tune=tune)
            return self.mixer.infer(params["mixer"], h, positions=positions)
        return self.mixer(params["mixer"], h, positions=positions, train=False)

    def infer(self, params, x, positions=None, impl=None, tune=None):
        """Aux-free inference forward: same residual wiring as __call__ with
        train=False, but mixers take their serving path (fused bidirectional
        Hamming attention for encoder binary-linear mode) and MoE feeds their
        deterministic gather dispatch (clean-logit argmax, no rng, no
        balance/drop bookkeeping) with capacity planned PER BATCH ROW — a
        row's output never depends on its co-batched neighbors, so the whole
        block forward is batch-invariant per row. Returns x only — the
        serving engines jit this, typically closed over a core.deploy
        DeployPlan's frozen params so no per-call weight decode survives in
        the compiled program.

        Named scopes `mixer` (norm1, the mixer, its residual add) and `feed`
        (norm2, the MLP or MoE, its residual add) cover every op of the
        block."""
        with jax.named_scope("mixer"):
            h = self.norm1(params["norm1"], x)
            mix = self._infer_mixer(params, h, positions, impl=impl,
                                    tune=tune)
            if not self.parallel:
                x = x + mix
        with jax.named_scope("feed"):
            if self.parallel:
                return x + mix + self._infer_feed(params, h, impl=impl,
                                                  tune=tune)
            h2 = self.norm2(params["norm2"], x)
            return x + self._infer_feed(params, h2, impl=impl, tune=tune)

    # -- decode ---------------------------------------------------------------
    def init_cache(self, batch, max_len, dtype=jnp.bfloat16):
        cache = {"mixer": self.mixer.init_cache(batch, max_len, dtype)}
        if hasattr(self.feed, "init_cache"):
            cache["feed"] = self.feed.init_cache(batch, max_len, dtype)
        return cache

    def prefill(self, params, x, cache, positions=None, lengths=None):
        """Whole-prompt pass against a fresh cache. x: (B, N, d_model) →
        (y (B, N, d_model), decode-ready cache). Same residual wiring as
        __call__; the mixer fills its decode state in one chunked pass.
        lengths (B,) int32 marks per-row valid prompt length for
        bucket-padded prompts (end padding never enters the handed-over
        state)."""
        h = self.norm1(params["norm1"], x)
        mix, mixer_cache = self.mixer.prefill(params["mixer"], h,
                                              cache["mixer"],
                                              positions=positions,
                                              lengths=lengths)
        new_cache = {"mixer": mixer_cache}
        if self.parallel:
            ff, fc = self._feed_prefill(params, h, cache, lengths)
            if fc is not None:
                new_cache["feed"] = fc
            return x + mix + ff, new_cache
        x = x + mix
        h2 = self.norm2(params["norm2"], x)
        ff, fc = self._feed_prefill(params, h2, cache, lengths)
        if fc is not None:
            new_cache["feed"] = fc
        return x + ff, new_cache

    def _feed_prefill(self, params, h, cache, lengths=None):
        if hasattr(self.feed, "prefill"):
            return self.feed.prefill(params["feed"], h, cache["feed"],
                                     lengths=lengths)
        if self._feed_has_aux:
            y, _ = self.feed(params["feed"], h, train=False)
            return y, None
        return self.feed(params["feed"], h), None

    def decode_step(self, params, x_t, cache):
        """x_t: (B, d_model) → (y_t, cache)."""
        h = self.norm1(params["norm1"], x_t[:, None])[:, 0]
        mix, mixer_cache = self.mixer.decode_step(params["mixer"], h, cache["mixer"])
        new_cache = {"mixer": mixer_cache}
        if self.parallel:
            ff, fc = self._feed_step(params, h, cache)
            if fc is not None:
                new_cache["feed"] = fc
            return x_t + mix + ff, new_cache
        x_t = x_t + mix
        h2 = self.norm2(params["norm2"], x_t[:, None])[:, 0]
        ff, fc = self._feed_step(params, h2, cache)
        if fc is not None:
            new_cache["feed"] = fc
        return x_t + ff, new_cache

    def _feed_step(self, params, h, cache):
        if hasattr(self.feed, "decode_step"):
            return self.feed.decode_step(params["feed"], h, cache["feed"])
        if self._feed_has_aux:
            y, _ = self.feed(params["feed"], h[:, None], train=False)
            return y[:, 0], None
        return self.feed(params["feed"], h[:, None])[:, 0], None
