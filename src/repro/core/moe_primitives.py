"""Heterogeneous mixture of multiplication primitives (paper §4.2).

Experts are *unequal*: a powerful `Mult.` expert (dense linears) and a cheap
`Shift` expert (power-of-two linears). A learned router sends each token to
its top-1 expert; the latency-aware load-balancing loss (core.losses) trains
the router so the token split matches the experts' speed ratio.

TPU adaptation of the paper's TVM/Nimble dynamic dispatch (DESIGN.md §2):
**static capacity dispatch** (GShard/Switch one-hot einsums) with
**latency-aware capacities** — expert i's capacity ∝ 1/Lat_i, the static-shape
twin of the LL-loss objective. Experts run as independent sharded branches, so
the paper's "ideal parallelism" (modularized latency = max over experts) is
the native execution model under SPMD, not a simulation.

Training groups tokens across the flattened co-batch (`group_tokens`);
SERVING plans capacity per image row (`group_rows` + the memoized per-image
`capacity_plan`), so an image's routing — and therefore its logits — is
independent of whatever the scheduler co-batched it with (ISSUE 5 tentpole;
the batch-invariance property tier pins it).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.core import energy, losses
from repro.core.dense import Dense
from repro.core.shift_linear import ShiftLinear


def _act(name):
    return {"gelu": jax.nn.gelu, "silu": jax.nn.silu, "relu": jax.nn.relu}[name]


class _MLPExpert:
    """Two-linear expert of a given primitive kind ("mult" | "shift")."""

    def __init__(self, d_model, d_hidden, kind, activation="gelu",
                 dtype=jnp.float32, param_dtype=jnp.float32):
        linear = Dense if kind == "mult" else ShiftLinear
        self.kind = kind
        self.up = linear(d_model, d_hidden, dtype=dtype, param_dtype=param_dtype)
        self.down = linear(d_hidden, d_model, dtype=dtype, param_dtype=param_dtype)
        self.activation = _act(activation)

    def init(self, key):
        k1, k2 = jax.random.split(key)
        return {"up": self.up.init(k1), "down": self.down.init(k2)}

    def spec(self, params):
        def lin(p, axes):
            return {k: (axes if k != "bias" else (axes[-1],)) for k in p}
        return {"up": lin(params["up"], ("embed", "mlp")),
                "down": lin(params["down"], ("mlp", "embed"))}

    accepts_impl = True

    def __call__(self, params, x, impl=None, tune=None):
        up, down = self.up, self.down
        if getattr(up, "accepts_impl", False):          # shift expert
            h = up(params["up"], x, impl=impl, tune=tune)
            return down(params["down"], self.activation(h), impl=impl,
                        tune=tune)
        return down(params["down"], self.activation(up(params["up"], x)))


class _LinearExpert:
    """Single-linear expert — for MoE applied to attention projections ("Both")."""

    def __init__(self, d_in, d_out, kind, dtype=jnp.float32, param_dtype=jnp.float32):
        linear = Dense if kind == "mult" else ShiftLinear
        self.kind = kind
        self.proj = linear(d_in, d_out, dtype=dtype, param_dtype=param_dtype)

    def init(self, key):
        return {"proj": self.proj.init(key)}

    def spec(self, params):
        return {"proj": {k: (("embed", "mlp") if k != "bias" else ("mlp",))
                         for k in params["proj"]}}

    accepts_impl = True

    def __call__(self, params, x, impl=None, tune=None):
        if getattr(self.proj, "accepts_impl", False):   # shift expert
            return self.proj(params["proj"], x, impl=impl, tune=tune)
        return self.proj(params["proj"], x)


class MoEPrimitives:
    """Token-routed mixture of {Mult, Shift} experts with latency-aware dispatch.

    Args:
      d_model: token dim.
      d_hidden: expert hidden dim (expert_type="mlp") or output dim ("linear").
      expert_kinds: e.g. ("mult", "shift") — the paper's pairing. Any number
        and mix of kinds is supported (the paper notes more unbalanced experts
        ⇒ larger LL-loss wins).
      capacity_factor: slack multiplier on the latency-proportional capacities.
      latency_aware: if False, capacities are uniform and α_i = 1/n (ablation
        arm of paper Tab. 7).
    """

    def __init__(self, d_model, d_hidden, expert_kinds=("mult", "shift"),
                 expert_type="mlp", activation="gelu", capacity_factor=1.25,
                 latency_aware=True, router_noise=1.0,
                 dtype=jnp.float32, param_dtype=jnp.float32, name="moe",
                 experts=None, latencies=None, capacity_ref_tokens=None):
        """If `experts` (list of init/apply modules) is given it overrides the
        built-in expert construction — used by repro.nn to pair the
        architecture's own MLP flavor (SwiGLU, channel-mix, ...) as the Mult
        expert against its Shift twin. `latencies` must then be supplied (or
        is estimated from the default MLP shape)."""
        self.d_model = int(d_model)
        self.d_hidden = int(d_hidden)
        self.expert_kinds = tuple(expert_kinds)
        self.n_experts = len(experts) if experts is not None else len(self.expert_kinds)
        self.capacity_factor = float(capacity_factor)
        self.latency_aware = latency_aware
        self.router_noise = router_noise
        self.dtype = dtype
        self.name = name
        self._capacity_plans = {}   # n_tokens → (caps, offsets) memo
        self.router = Dense(d_model, self.n_experts, use_bias=False,
                            dtype=jnp.float32, param_dtype=jnp.float32)
        if experts is not None:
            self.experts = list(experts)
        elif expert_type == "mlp":
            self.experts = [
                _MLPExpert(d_model, d_hidden, kind, activation, dtype, param_dtype)
                for kind in self.expert_kinds
            ]
        else:
            self.experts = [
                _LinearExpert(d_model, d_hidden, kind, dtype, param_dtype)
                for kind in self.expert_kinds
            ]
        # Per-expert latency estimates — used for α_i (LL-loss) and the static
        # capacity split. Explicit values (serving telemetry, caller override)
        # win; otherwise the analytic energy model is evaluated at
        # `capacity_ref_tokens` — the DEPLOYMENT per-group token count (a
        # ViT's per-image patch count), which sets the compute/memory-bound
        # regime. The α/capacity regime is a per-feed constant, never a
        # per-call function of the group size: one model must route
        # identically across group sizes (LM prefill routes a whole prompt,
        # decode routes single tokens — a size-dependent split would diverge
        # them), so callers that dispatch varying group sizes leave the ref
        # unset and get the NOMINAL_MOE_TOKENS fallback.
        self.capacity_ref_tokens = (None if capacity_ref_tokens is None
                                    else int(capacity_ref_tokens))
        self._explicit_latencies = None
        if latencies is not None:
            self.latencies = list(latencies)

    @property
    def latencies(self):
        """Per-expert latencies backing α_i and capacities — the feed's
        regime constant. Reads return the explicit override when one was set
        (telemetry table / caller), else the analytic model at
        `capacity_ref_tokens` (falling back to `energy.NOMINAL_MOE_TOKENS`
        when no deployment token count was pinned)."""
        return self.latencies_at(None)

    @latencies.setter
    def latencies(self, value):
        """Setting latencies (e.g. dropping in measured telemetry) invalidates
        the memoized capacity plans — engines must be (re)built afterwards so
        their frozen programs see the new split."""
        self._explicit_latencies = (None if value is None
                                    else [float(v) for v in value])
        self._capacity_plans.clear()

    def latencies_at(self, n_tokens=None):
        """Latencies at a per-group token count — the single source of truth
        for α_i and the capacity split. Explicit telemetry latencies are
        measured at serving geometry already and are returned as-is; the
        analytic fallback is evaluated at `n_tokens`, defaulting to the
        feed's `capacity_ref_tokens` regime (serving buckets run 196-token
        per-image groups, not the 1024-token nominal regime) and then to
        NOMINAL_MOE_TOKENS."""
        if self._explicit_latencies is not None:
            return list(self._explicit_latencies)
        if n_tokens is None:
            n_tokens = self.capacity_ref_tokens or energy.NOMINAL_MOE_TOKENS
        return energy.expert_latencies(int(n_tokens), self.d_model,
                                       self.d_hidden, self.expert_kinds)

    # -- parameters ---------------------------------------------------------
    def init(self, key):
        keys = jax.random.split(key, self.n_experts + 1)
        return {
            "router": self.router.init(keys[0]),
            "experts": [e.init(k) for e, k in zip(self.experts, keys[1:])],
        }

    def spec(self, params):
        return {
            "router": {k: ("embed", None) for k in params["router"]},
            "experts": [e.spec(p) for e, p in zip(self.experts, params["experts"])],
        }

    # -- capacity schedule ---------------------------------------------------
    def _capacity_weights(self):
        # Regime latencies, NOT a function of the group size being planned:
        # caps(n) and caps(m) must be the same split at different scales or
        # mixed-group dispatch (LM prefill vs decode) routes inconsistently.
        if self.latency_aware:
            return energy.inverse_latency_weights(self.latencies_at(None))
        return [1.0 / self.n_experts] * self.n_experts

    def capacities(self, n_tokens: int):
        """Static per-expert capacities; latency-aware split sends more tokens
        to faster experts (inverse-latency weights).

        Invariant: capacity_factor >= 1.0 ⇒ sum(caps) >= n_tokens. Per-term
        ceil usually gets there on its own, but the guarantee is structural,
        not a float-rounding accident: any deficit left after the
        min(c, n_tokens) clamp is topped back up, largest-weight experts
        first, so small groups can never silently shrink total capacity below
        the token count.
        """
        weights = self._capacity_weights()
        caps = [min(int(math.ceil(self.capacity_factor * n_tokens * w)), n_tokens)
                for w in weights]
        if self.capacity_factor >= 1.0:
            deficit = n_tokens - sum(caps)
            for i in sorted(range(self.n_experts), key=lambda j: -weights[j]):
                if deficit <= 0:
                    break
                bump = min(deficit, n_tokens - caps[i])
                caps[i] += bump
                deficit -= bump
        return caps

    def capacity_plan(self, n_tokens: int):
        """Memoized (caps, offsets) for a per-group token count — the static
        capacity math hoisted out of every trace. At serve time the group IS
        one image row (`nn.dispatch.group_rows`), so `n_tokens` is the
        tokens-PER-IMAGE count and the plan is the per-image capacity split:
        every image gets the same static caps regardless of what it is
        co-batched with. `core.deploy`'s prepare_inference warms this for
        the serving geometry at engine-build time; cold lookups still
        compute (and memoize) on first trace."""
        plan = self._capacity_plans.get(n_tokens)
        if plan is None:
            caps = self.capacities(n_tokens)
            offsets = [0]
            for c in caps:
                offsets.append(offsets[-1] + c)
            plan = (tuple(caps), tuple(offsets[:-1]))
            self._capacity_plans[n_tokens] = plan
        return plan

    # -- forward ------------------------------------------------------------
    def _run_experts(self, params, buf, daux, caps, s):
        """Run each expert on its static row segment of the dispatch buffer
        and combine back to (G, S, d). Heterogeneous experts are independent
        branches — parallel under SPMD, the paper's "ideal parallelism"
        natively (DESIGN.md §2)."""
        from repro.nn.dispatch import combine

        outs = []
        off = 0
        for i, expert in enumerate(self.experts):
            seg = buf[:, off:off + caps[i], :]
            outs.append(expert(params["experts"][i], seg))
            off += caps[i]
        expert_out = jnp.concatenate(outs, axis=1)               # (G, total, d)
        return combine(expert_out, daux, s, self.d_model)

    @staticmethod
    def _gates(select_logits, clean_logits):
        """THE gating rule, single home for train and serving: top-1 on
        `select_logits` (noisy while training, clean at inference), gate from
        the clean softmax. Returns (probs (G,S,E), top1 (G,S), gate (G,S,1)).

        The gate is a select over the experts, not a gather: one term of the
        sum is nonzero, so it equals probs[top1] exactly, with the same
        gradient (see `nn.dispatch` on why a gather is avoided)."""
        probs = jax.nn.softmax(clean_logits, axis=-1)
        top1 = jnp.argmax(select_logits, axis=-1)
        hit = top1[..., None] == jnp.arange(probs.shape[-1])
        gate = jnp.sum(jnp.where(hit, probs, 0.0), axis=-1, keepdims=True)
        return probs, top1, gate

    def _route_dispatch(self, params, xg, select_logits, clean_logits, stats):
        """Training routing: `_gates` then sort-based capacity dispatch. The
        serving path (`infer`) consumes the same `_gates` via `_route_infer`
        with the gather-ordered dispatch."""
        from repro.nn.dispatch import dispatch

        s = xg.shape[1]
        probs, top1, gate = self._gates(select_logits, clean_logits)
        caps, _ = self.capacity_plan(s)
        buf, daux = dispatch(xg.astype(self.dtype), top1[..., None],
                             gate.astype(jnp.float32), caps, stats=stats)
        return probs, top1, caps, buf, daux

    def _route_infer(self, params, xg):
        """Clean-logit argmax routing for serving (no noise, no rng): the
        shared `_gates` rule with clean logits on both slots. Returns
        (top1 (G,S), gate (G,S))."""
        clean_logits = self.router(params["router"], xg.astype(jnp.float32))
        _, top1, gate = self._gates(clean_logits, clean_logits)
        return top1, gate[..., 0].astype(jnp.float32)

    def _dispatch_tokens(self, params, x):
        """Shared serving front half: group → route (clean argmax) →
        gather-ordered dispatch. Returns (buf, info, segments, ungroup) with
        `segments` the per-expert static views of the buffer. Single home so
        `infer` and the expert telemetry probe (serve.telemetry) can never
        diverge on the dispatch they serve/measure.

        Capacity is planned PER BATCH ROW (`nn.dispatch.group_rows`): each
        image competes only with itself for expert slots, so per-image
        outputs are independent of co-batching — the batch-invariance
        contract."""
        from repro.nn.dispatch import dispatch_infer, group_rows

        xg, ungroup = group_rows(x, self.d_model)
        _, s, _ = xg.shape
        top1, gate = self._route_infer(params, xg)
        caps, offsets = self.capacity_plan(s)
        buf, info = dispatch_infer(xg.astype(self.dtype), top1, gate, caps)
        segments = [buf[:, off:off + cap, :]
                    for off, cap in zip(offsets, caps)]
        return buf, info, segments, ungroup

    # Serving threads kernel impl/tune through to the shift experts.
    accepts_impl = True

    def infer(self, params, x, impl=None, tune=None):
        """Deterministic inference dispatch — the serving fast path.

        Routes on clean-logit argmax (no router noise, no rng) with static
        latency-aware capacities planned PER IMAGE ROW (one routing group
        per batch row, capacities from the per-image token count), and
        computes none of the aux/LL-loss statistics. Dispatch is the
        gather-ordered segment path (nn.dispatch.dispatch_infer): no
        scatter-into-zeros, experts consume per-expert static views, the
        combine is a per-token gather — and the capacity/offset math comes
        from the memoized `capacity_plan` (warmed by core.deploy at engine
        build). Two calls on the same input produce identical outputs, and
        a given image's output is bit-identical regardless of which
        neighbors it is batched with, its row position, or batch padding
        (no token ever competes with another image's tokens for capacity).
        Returns y only.

        Named scopes: `moe_dispatch` (router and dispatch), one
        `expert_<kind>` per expert, `moe_combine`.
        """
        from repro.nn.dispatch import combine_infer

        with jax.named_scope("moe_dispatch"):
            _, info, segments, ungroup = self._dispatch_tokens(params, x)
        outs = []
        for i, (expert, seg) in enumerate(zip(self.experts, segments)):
            with jax.named_scope(f"expert_{self.expert_kinds[i]}"):
                outs.append(
                    expert(params["experts"][i], seg, impl=impl, tune=tune)
                    if getattr(expert, "accepts_impl", False)
                    else expert(params["experts"][i], seg))
        with jax.named_scope("moe_combine"):
            return ungroup(combine_infer(outs, info)).astype(x.dtype)

    def __call__(self, params, x, train=True, rng=None):
        """x: (..., d_model). Tokens are routed in sharded groups
        (repro.nn.dispatch) with latency-aware per-expert capacities.

        Returns (y, aux) where aux carries the LL-loss ingredients and
        dispatch statistics (paper Fig. 6 visualizations read these).
        """
        from repro.nn.dispatch import group_tokens

        xg, ungroup = group_tokens(x, self.d_model)
        g, s, _ = xg.shape

        clean_logits = self.router(params["router"], xg.astype(jnp.float32))
        if train and rng is not None and self.router_noise > 0:
            noisy = clean_logits + self.router_noise * jax.random.normal(
                rng, clean_logits.shape)
        else:
            noisy = clean_logits
        probs, top1, caps, buf, daux = self._route_dispatch(
            params, xg, noisy, clean_logits, stats=True)
        y = ungroup(self._run_experts(params, buf, daux, caps, s)).astype(x.dtype)

        # latency_aware=False is the paper's baseline arm (Tab. 7 ablation):
        # homogeneous treatment — uniform α — rather than no balance at all.
        # α is evaluated at the feed's regime token count (capacity_ref_
        # tokens) so the loss and the capacity split (same `latencies_at`)
        # always agree on the regime, independent of this call's group size.
        loss_lat = (jnp.asarray(self.latencies_at(None)) if self.latency_aware
                    else jnp.ones((self.n_experts,)))
        alpha = losses.latency_coefficients(loss_lat)
        balance = losses.latency_aware_moe_loss(
            clean_logits, probs, loss_lat, self.router_noise)
        aux = {
            "balance_loss": balance,
            "probs": probs.reshape(g * s, self.n_experts),
            "logits": clean_logits.reshape(g * s, self.n_experts),
            "top1": top1.reshape(g * s),
            "tokens_per_expert": daux["tokens_per_expert"],
            "drop_fraction": daux["drop_fraction"],
            "alpha": alpha,
            "capacities": jnp.asarray(caps, jnp.int32),
        }
        return y, aux
