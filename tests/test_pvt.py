"""PVTv2 in the program: the fused binary attention with fewer keys than
queries, the MoE's shared `mid` between the experts' two linears, one
capacity plan per stage, and the model served through the same entry points
as ShiftAddViT."""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

from repro.core.moe_primitives import MoEPrimitives
from repro.core.policy import DENSE, SHIFTADD
from repro.kernels import bidir_linear_attention as _bidir
from repro.kernels import ops, ref
from repro.kernels.padding import pad_to_multiple
from repro.nn import layers as L
from repro.nn.dispatch import combine_infer
from repro.nn.pvt import PVTv2, PVTv2Config
from repro.serve.replicas import make_replicas

# ---------------------------------------------------------------------------
# Fused bidirectional kernel: n_q queries against n_kv keys
# ---------------------------------------------------------------------------


def _qkv(seed, b, h, nq, nkv, dk, dv):
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (b, h, nq, dk)),
            jax.random.normal(kk, (b, h, nkv, dk)),
            jax.random.normal(kv, (b, h, nkv, dv)))


# PVTv2-B2's stage-1 shape scaled down (3136 queries, 49 keys, head dim 64),
# and lengths and head dims that are no multiples of 8 or 128.
@pytest.mark.parametrize("b, h, nq, nkv, dk, dv", [
    (2, 1, 392, 49, 64, 64), (1, 2, 61, 13, 16, 24), (2, 3, 7, 20, 32, 8)])
@pytest.mark.parametrize("impl", ["interpret", "xla"])
def test_bidir_attention_with_fewer_keys_matches_the_oracle(impl, b, h, nq,
                                                            nkv, dk, dv):
    q, k, v = _qkv(nq + nkv, b, h, nq, nkv, dk, dv)
    got = ops.binary_linear_attention_bidir(q, k, v, impl=impl)
    want = ref.binary_linear_attention_ref(q, k, v, causal=False)
    assert got.shape == (b, h, nq, dv)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5,
                               atol=2e-5)


# The kernel as it was when queries and keys had one length, kept verbatim
# as the oracle of the self-attention call: with n_q = n_kv the kernel has
# to give the same bits.
def _self_attention_kernel(dk_true, n_true):
    def kernel(q_ref, k_ref, v_ref, o_ref):
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        n_pad, dk_pad = q.shape
        dv_pad = v.shape[-1]
        lane = jax.lax.broadcasted_iota(jnp.int32, (n_pad, dk_pad), 1)
        lane_valid = (lane < dk_true).astype(jnp.float32)
        bq = jnp.where(q >= 0, 1.0, -1.0) * lane_valid
        bk = jnp.where(k >= 0, 1.0, -1.0) * lane_valid
        row_k = jax.lax.broadcasted_iota(jnp.int32, (n_pad, dk_pad), 0)
        bk = bk * (row_k < n_true).astype(jnp.float32)
        row_v = jax.lax.broadcasted_iota(jnp.int32, (n_pad, dv_pad), 0)
        v = v * (row_v < n_true).astype(jnp.float32)
        d = jnp.float32(dk_true)
        kv = jnp.dot(bk.T, v, preferred_element_type=jnp.float32)
        ksum = jnp.sum(bk, axis=0, keepdims=True)
        vsum = jnp.sum(v, axis=0, keepdims=True)
        num = jnp.dot(bq, kv, preferred_element_type=jnp.float32)
        num += d * vsum
        den = jnp.sum(bq * ksum, axis=-1) + d * jnp.float32(n_true)
        o_ref[0] = (num / (den[:, None] + 1e-6)).astype(o_ref.dtype)
    return kernel


def _self_attention_call(q, k, v):
    b, h, n, dk = q.shape
    dv = v.shape[-1]
    qp, kp, vp = (pad_to_multiple(pad_to_multiple(t.reshape(b * h, n, -1),
                                                  128, 2), 8, 1)
                  for t in (q, k, v))
    g, n_pad, dk_pad = qp.shape
    dv_pad = vp.shape[-1]
    out = pl.pallas_call(
        _self_attention_kernel(dk, n), grid=(g,),
        in_specs=[pl.BlockSpec((1, n_pad, dk_pad), lambda gg: (gg, 0, 0)),
                  pl.BlockSpec((1, n_pad, dk_pad), lambda gg: (gg, 0, 0)),
                  pl.BlockSpec((1, n_pad, dv_pad), lambda gg: (gg, 0, 0))],
        out_specs=pl.BlockSpec((1, n_pad, dv_pad), lambda gg: (gg, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((g, n_pad, dv_pad), vp.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=True)(qp, kp, vp)
    return out[:, :n, :dv].reshape(b, h, n, dv)


@pytest.mark.parametrize("b, h, n, dk, dv", [(2, 3, 196, 64, 64),
                                             (1, 2, 13, 16, 24)])
def test_bidir_attention_at_equal_lengths_is_the_self_attention_kernel(
        b, h, n, dk, dv):
    q, k, v = _qkv(n, b, h, n, n, dk, dv)
    got = ops.binary_linear_attention_bidir(q, k, v, impl="interpret")
    assert np.array_equal(np.asarray(got),
                          np.asarray(_self_attention_call(q, k, v)))


def test_bidir_kernel_refuses_a_sequence_over_its_vmem_cap():
    q = jnp.zeros((1, _bidir.MAX_FUSED_N + 8, 128))
    k = jnp.zeros((1, 8, 128))
    with pytest.raises(AssertionError):
        _bidir.bidir_binary_attention_pallas(q, k, k, interpret=True)


# ---------------------------------------------------------------------------
# MoEPrimitives.infer(mid=...)
# ---------------------------------------------------------------------------

D, F = 16, 32


def _moe(capacity_factor):
    experts = [L.MLP(D, F, "mlp", "dense" if kind == "mult" else "shift",
                     True, jnp.float32, jnp.float32)
               for kind in ("mult", "shift")]
    moe = MoEPrimitives(D, F, capacity_factor=capacity_factor,
                        router_noise=0.0, experts=experts,
                        capacity_ref_tokens=12)
    params = moe.init(jax.random.PRNGKey(3))
    params["router"]["kernel"] = jax.random.normal(jax.random.PRNGKey(4),
                                                   (D, 2))
    for e in params["experts"]:
        for name in ("up", "down"):
            e[name]["bias"] = 0.1 * jnp.ones_like(e[name]["bias"])
    x = jax.random.normal(jax.random.PRNGKey(5), (3, 12, D))
    return moe, params, x


def _token_mixing_mid(h):
    """A token-wise function that needs the neighbours, like a conv."""
    return jax.nn.gelu(h + 0.5 * jnp.roll(h, 1, axis=-2), approximate=False)


def _plain_mid_moe(moe, params, x, mid):
    """Both experts' fc1 on every token, selected by route and keep; mid on
    the grid; both fc2 likewise; times gate and keep."""
    logits = x @ params["router"]["kernel"]
    top1 = jnp.argmax(logits, axis=-1)
    gate = jnp.max(jax.nn.softmax(logits, axis=-1), axis=-1)
    onehot = jax.nn.one_hot(top1, 2, dtype=jnp.int32)
    rank = jnp.sum((jnp.cumsum(onehot, axis=1) - onehot) * onehot, axis=-1)
    caps = jnp.asarray(moe.capacity_plan(x.shape[1])[0])
    keep = (rank < caps[top1]).astype(x.dtype)[..., None]

    def linear(name, h):
        outs = [L.call_linear(getattr(e, name), p[name], h)
                for e, p in zip(moe.experts, params["experts"])]
        return jnp.where((top1 == 0)[..., None], outs[0], outs[1])

    hid = linear("up", x) * keep
    return linear("down", mid(hid)) * gate[..., None] * keep


@pytest.mark.parametrize("capacity_factor", [0.6, 1.25])
def test_moe_mid_matches_a_plain_select_and_mask(capacity_factor):
    moe, params, x = _moe(capacity_factor)
    caps = moe.capacity_plan(12)[0]
    if capacity_factor < 1:
        assert sum(caps) < 12          # some tokens are over capacity
    got = moe.infer(params, x, impl="xla", mid=_token_mixing_mid)
    want = _plain_mid_moe(moe, params, x, _token_mixing_mid)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5,
                               atol=1e-6)


def test_moe_without_mid_is_the_whole_expert_path_bit_for_bit():
    # mid=None runs each expert whole on its segment and combines, as
    # before the hook existed.
    moe, params, x = _moe(0.6)
    _, info, segments, ungroup = moe._dispatch_tokens(params, x)
    outs = [e(p, s, impl="xla") for e, p, s in
            zip(moe.experts, params["experts"], segments)]
    want = ungroup(combine_infer(outs, info))
    got = moe.infer(params, x, impl="xla")
    assert np.array_equal(np.asarray(got), np.asarray(want))
    # The experts' own activation as `mid` gives the same numbers.
    act = moe.experts[0].act
    np.testing.assert_allclose(
        np.asarray(moe.infer(params, x, impl="xla", mid=act)),
        np.asarray(got), rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# The model: per-stage capacity plans, serving
# ---------------------------------------------------------------------------

TINY = PVTv2Config(image_size=64, n_classes=10, widths=(16, 32, 40, 64),
                   depths=(1, 1, 2, 1), heads=(1, 2, 5, 8),
                   mlp_ratios=(8, 8, 4, 4))


@functools.lru_cache(maxsize=None)
def _shiftadd():
    dense = PVTv2(TINY)
    model = PVTv2(dataclasses.replace(TINY, policy=SHIFTADD))
    params = model.convert_from(dense, dense.init(jax.random.PRNGKey(0)),
                                stage=2)
    for blk in (b for st in params["stages"] for b in st["blocks"]):
        blk["feed"]["router"]["kernel"] = jax.random.normal(
            jax.random.PRNGKey(blk["feed"]["router"]["kernel"].shape[0]),
            blk["feed"]["router"]["kernel"].shape)
    return model, params


def _images(n, seed=1):
    return np.random.default_rng(seed).integers(0, 256, (n, 64, 64, 3),
                                                dtype=np.uint8)


def test_grids_and_token_counts():
    assert TINY.grids == (16, 8, 4, 2)
    assert PVTv2Config().token_counts == (3136, 784, 196, 49)


def test_prepare_inference_warms_one_plan_per_stage_and_tracing_plans_none(
        monkeypatch):
    model, params = _shiftadd()
    for blk in model.blocks:
        blk.feed._capacity_plans.clear()
    plan = model.prepare_inference(params, impl="xla",
                                   token_counts=model.token_counts)
    assert plan.moe_layers == 5 and plan.token_counts == (4, 16, 64, 256)
    for st, n in zip(model.stages, model.token_counts):
        for blk in st["blocks"]:
            assert n in blk.feed._capacity_plans

    def refuse(self, n_tokens):
        raise AssertionError(f"capacity planned at trace time ({n_tokens})")
    monkeypatch.setattr(MoEPrimitives, "capacities", refuse)
    out = jax.jit(lambda x: model.infer(plan.params, x, impl="xla"))(
        jnp.asarray(_images(2), jnp.float32))
    assert out.shape == (2, 10)


def test_served_through_thread_replicas_as_the_vit_is():
    model, params = _shiftadd()
    replicas = make_replicas(model, params, n_replicas=2, arm="thread",
                             buckets=(4,), impl="xla")
    try:
        replicas.warmup()
        engine = replicas.engines[0]
        traces = engine.trace_count
        images = _images(3)
        futures = [replicas.submit(slot, images) for slot in (0, 1)]
        logits = [np.asarray(f.result()[0]) for f in futures]
        assert engine.trace_count == traces
        want = model.infer(engine.plan.params,
                           jnp.asarray(np.concatenate([images, images[:1]]),
                                       jnp.float32), impl="xla")
        # Both replicas run the one bucket program; it closes over the
        # frozen weights as constants, which XLA folds, so against the
        # eager forward it agrees to float32 rounding.
        assert logits[0].shape == (3, 10)
        np.testing.assert_array_equal(logits[0], logits[1])
        np.testing.assert_allclose(logits[0], np.asarray(want)[:3],
                                   rtol=1e-5, atol=1e-5)
    finally:
        replicas.close()


def test_call_and_infer_agree_and_the_moe_refuses_training():
    model, params = _shiftadd()
    images = jnp.asarray(_images(2), jnp.float32)
    logits, aux = model(params, images, train=False)
    np.testing.assert_allclose(np.asarray(logits),
                               np.asarray(model.infer(params, images,
                                                      impl="xla")),
                               rtol=1e-5, atol=1e-5)
    assert float(aux["balance_loss"]) == 0.0
    with pytest.raises(NotImplementedError):
        model(params, images, train=True)


def test_dense_arm_trains_through_call():
    dense = PVTv2(dataclasses.replace(TINY, policy=DENSE))
    params = dense.init(jax.random.PRNGKey(2))
    images = jnp.asarray(_images(2), jnp.float32)

    def loss(p):
        return jnp.mean(dense(p, images, train=True)[0] ** 2)
    grads = jax.grad(loss)(params)
    norms = [float(jnp.abs(g).max()) for g in jax.tree_util.tree_leaves(grads)]
    assert all(np.isfinite(norms)) and max(norms) > 0


def test_attention_kv_defaults_to_the_tokens_themselves():
    model, params = _shiftadd()
    blk, p = model.blocks[-1], params["stages"][-1]["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 4, 64))
    a = blk.mixer.infer(p["mixer"], x, impl="xla")
    b = blk.mixer.infer(p["mixer"], x, impl="xla", kv=x)
    assert np.array_equal(np.asarray(a), np.asarray(b))


def test_contract_cell_of_the_reduced_attention():
    from repro.analysis.kernel_contracts import bidir_attention_cell

    same = bidir_attention_cell(32, 96, 196, 64, 64)
    assert bidir_attention_cell(32, 96, 196, 64, 64, n_kv=196) == same
    cell = bidir_attention_cell(32, 32, 3136, 64, 64, n_kv=49)
    # q and out at 3136 rows, k and v at 56 (49 sublane-aligned), 128
    # lanes, double-buffered; feasible in VMEM.
    assert cell.vmem_bytes == 2 * (3136 + 56) * 2 * 128 * 4
    assert cell.classification != "vmem_overflow"
    assert cell.geometry["n_kv"] == 49
