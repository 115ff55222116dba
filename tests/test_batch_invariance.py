"""Batch-invariance property tier (ISSUE 5).

The serving contract: a given image's logits are BIT-IDENTICAL no matter
(a) which row of the batch it sits in, (b) which neighbor images it is
co-batched with, (c) which engine bucket it is padded into, and (d) whether
it is served at batch=1 or inside a batch=N — for EVERY sweep policy,
shiftadd included. Two mechanisms carry it: MoE inference plans expert
capacity PER IMAGE ROW (`nn.dispatch.group_rows` + the per-image
`capacity_plan`), so no token ever competes with another image's tokens for
expert slots; and every reduction in `ShiftAddViT.infer` is within-row
(including the explicitly row-wise classifier head). The per-image dispatch
buffers are additionally pinned against a numpy oracle.

Deterministic example tests run in tier-1; the hypothesis sweeps (via the
optional `_propshim`) are marked `slow` and run in the vit-serve CI job.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propshim import given, settings, st  # optional-hypothesis shim

from repro.core.policy import DENSE
from repro.nn.dispatch import combine_infer, dispatch_infer
from repro.nn.vit import ShiftAddViT, ViTConfig
from repro.serve.vision import (SWEEP_POLICIES, BucketedViTEngine,
                                build_policy_model)

POLICIES = tuple(SWEEP_POLICIES)          # ("dense", "stage1", "shiftadd")

CFG = ViTConfig(image_size=16, patch_size=4, n_layers=2, d_model=32,
                n_heads=2, d_ff=64)


@functools.lru_cache(maxsize=None)
def _arm(policy):
    """(model, params, jitted infer) for one sweep arm — cached so every
    test (and every hypothesis example) reuses the same compiled programs."""
    dense_model = ShiftAddViT(dataclasses.replace(CFG, policy=DENSE))
    dense_params = dense_model.init(jax.random.PRNGKey(0))
    model, params = build_policy_model(CFG, policy, dense_model, dense_params)
    infer = jax.jit(lambda imgs: model.infer(params, imgs))
    return model, params, infer


@functools.lru_cache(maxsize=None)
def _engine(policy):
    model, params, _ = _arm(policy)
    return BucketedViTEngine(model, params, buckets=(1, 4, 8)).warmup()


def _imgs(n, seed=0):
    return jax.random.normal(
        jax.random.PRNGKey(seed),
        (n, CFG.image_size, CFG.image_size, CFG.in_channels))


# ---------------------------------------------------------------------------
# (a) batch-row permutation
# ---------------------------------------------------------------------------

def _check_permutation(policy, n, perm_seed, img_seed=1):
    _, _, infer = _arm(policy)
    imgs = _imgs(n, seed=img_seed)
    base = np.asarray(infer(imgs))
    perm = np.random.default_rng(perm_seed).permutation(n)
    np.testing.assert_array_equal(np.asarray(infer(imgs[perm])), base[perm])


@pytest.mark.parametrize("policy", POLICIES)
def test_row_permutation_invariance(policy):
    _check_permutation(policy, n=6, perm_seed=0)
    _check_permutation(policy, n=6, perm_seed=3)


# ---------------------------------------------------------------------------
# (b) co-batching with arbitrary neighbors
# ---------------------------------------------------------------------------

def _check_cobatch(policy, neighbor_seed, img_seed=2):
    """Image 0's logits must not move when its co-batch changes entirely."""
    _, _, infer = _arm(policy)
    probe = _imgs(1, seed=img_seed)
    alone = np.asarray(infer(probe))
    for n_neighbors in (1, 3, 7):
        neighbors = _imgs(n_neighbors, seed=neighbor_seed)
        batched = np.asarray(
            infer(jnp.concatenate([probe, neighbors], axis=0)))
        np.testing.assert_array_equal(batched[:1], alone)


@pytest.mark.parametrize("policy", POLICIES)
def test_cobatch_neighbor_invariance(policy):
    _check_cobatch(policy, neighbor_seed=10)
    _check_cobatch(policy, neighbor_seed=11)


# ---------------------------------------------------------------------------
# (c) padding to any engine bucket
# ---------------------------------------------------------------------------

def _check_bucket_padding(policy, n, img_seed=3):
    """The engine pads n images up to its covering bucket (and 20 > max
    bucket exercises the chunked path); real rows must equal the direct
    unpadded jitted forward bit-for-bit."""
    _, _, infer = _arm(policy)
    engine = _engine(policy)
    imgs = _imgs(n, seed=img_seed)
    want = np.asarray(infer(imgs))
    np.testing.assert_array_equal(np.asarray(engine.infer(imgs)), want)


@pytest.mark.parametrize("policy", POLICIES)
def test_bucket_padding_invariance(policy):
    for n in (1, 2, 3, 5, 8, 20):
        _check_bucket_padding(policy, n)


@pytest.mark.parametrize("policy", POLICIES)
def test_explicit_zero_padding_rows_are_inert(policy):
    """Same property without the engine in the loop: appending zero rows
    (what bucket padding does) must not perturb the real rows."""
    _, _, infer = _arm(policy)
    imgs = _imgs(3, seed=4)
    base = np.asarray(infer(imgs))
    pad = jnp.zeros((5,) + imgs.shape[1:], imgs.dtype)
    padded = np.asarray(infer(jnp.concatenate([imgs, pad], axis=0)))
    np.testing.assert_array_equal(padded[:3], base)


# ---------------------------------------------------------------------------
# (d) batch=1 vs batch=N
# ---------------------------------------------------------------------------

def _check_one_vs_n(policy, n, img_seed=5):
    _, _, infer = _arm(policy)
    imgs = _imgs(n, seed=img_seed)
    batched = np.asarray(infer(imgs))
    rows = np.concatenate(
        [np.asarray(infer(imgs[i:i + 1])) for i in range(n)], axis=0)
    np.testing.assert_array_equal(batched, rows)


@pytest.mark.parametrize("policy", POLICIES)
def test_batch_one_vs_n_bit_identical(policy):
    _check_one_vs_n(policy, n=5)


# ---------------------------------------------------------------------------
# Hypothesis sweeps over (policy, composition, seeds) — slow tier
# ---------------------------------------------------------------------------

@pytest.mark.slow
@settings(max_examples=20, deadline=None)
@given(st.sampled_from(POLICIES), st.integers(2, 8), st.integers(0, 10_000),
       st.integers(0, 10_000))
def test_permutation_invariance_property(policy, n, perm_seed, img_seed):
    _check_permutation(policy, n, perm_seed, img_seed=img_seed % 7)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(st.sampled_from(POLICIES), st.integers(0, 10_000))
def test_cobatch_invariance_property(policy, neighbor_seed):
    _check_cobatch(policy, neighbor_seed)


@pytest.mark.slow
@settings(max_examples=15, deadline=None)
@given(st.sampled_from(POLICIES), st.integers(1, 8), st.integers(0, 6))
def test_bucket_padding_invariance_property(policy, n, img_seed):
    _check_bucket_padding(policy, n, img_seed=img_seed)


@pytest.mark.slow
@settings(max_examples=10, deadline=None)
@given(st.sampled_from(POLICIES), st.integers(2, 8), st.integers(0, 6))
def test_one_vs_n_property(policy, n, img_seed):
    _check_one_vs_n(policy, n, img_seed=img_seed)


# ---------------------------------------------------------------------------
# Numpy oracle for the per-image dispatch buffers
# ---------------------------------------------------------------------------

def _np_per_image_dispatch(x, idx, gate, caps):
    """Reference per-image dispatch: for each batch row independently,
    tokens fill their expert's segment in token order up to its capacity.
    Returns (segments, y, pos, keep): `segments[b][e]` the live buffer rows
    of expert e for image b, `y` the identity-expert combine
    (gate·keep-scaled tokens), plus each token's within-expert rank and
    keep flag. Nothing here reads across rows — the oracle IS the
    independence statement the vmapped dispatch must reproduce."""
    b, s, d = x.shape
    y = np.zeros_like(x)
    pos = np.zeros((b, s), np.int64)
    keep = np.zeros((b, s), bool)
    segments = []
    for bi in range(b):
        fill = [0] * len(caps)
        segs = [[] for _ in caps]
        for t in range(s):
            e = int(idx[bi, t])
            pos[bi, t] = fill[e]
            if fill[e] < caps[e]:
                keep[bi, t] = True
                segs[e].append(x[bi, t])
                y[bi, t] = gate[bi, t] * x[bi, t]
            fill[e] += 1
        segments.append([
            np.asarray(sg, x.dtype).reshape(len(sg), d) for sg in segs])
    return segments, y, pos, keep


def _identity_segments(buf, caps):
    outs, off = [], 0
    for c in caps:
        outs.append(buf[:, off:off + c, :])
        off += c
    return outs


def _check_dispatch_vs_oracle(b, s, e, caps, seed, all_to=None):
    """`all_to` routes every token to that expert instead of at random."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(ks[0], (b, s, 4))
    idx = jax.random.randint(ks[1], (b, s), 0, e)
    if all_to is not None:
        idx = jnp.full_like(idx, all_to)
    gate = jax.nn.softmax(jax.random.normal(ks[2], (b, s, e)), -1)[..., 0]
    buf, info = dispatch_infer(x, idx, gate, caps)
    y = combine_infer(_identity_segments(buf, caps), info)
    segs, y_np, pos, keep = _np_per_image_dispatch(
        np.asarray(x), np.asarray(idx), np.asarray(gate), caps)
    np.testing.assert_array_equal(np.asarray(info["pos"]), pos)
    np.testing.assert_array_equal(np.asarray(info["keep"]), keep)
    np.testing.assert_array_equal(np.asarray(y), y_np)
    # Live buffer rows per (image, expert) — rows past the live count are
    # deliberately unmasked (combine never reads them), so only live rows
    # are comparable.
    buf_np = np.asarray(buf)
    off = 0
    for ei, cap in enumerate(caps):
        for bi in range(b):
            live = segs[bi][ei][:cap]
            np.testing.assert_array_equal(
                buf_np[bi, off:off + len(live)], live)
        off += cap
    # Row independence at the buffer level: dispatching any single row
    # alone reproduces exactly that row's buffers, info and combine.
    for bi in range(b):
        buf1, info1 = dispatch_infer(x[bi:bi + 1], idx[bi:bi + 1],
                                     gate[bi:bi + 1], caps)
        np.testing.assert_array_equal(np.asarray(info1["pos"])[0], pos[bi])
        np.testing.assert_array_equal(np.asarray(info1["keep"])[0], keep[bi])
        y1 = combine_infer(_identity_segments(buf1, caps), info1)
        np.testing.assert_array_equal(np.asarray(y1)[0], y_np[bi])


def test_per_image_dispatch_matches_numpy_oracle_examples():
    for seed, (b, s, e, caps) in enumerate([
            (1, 8, 2, [4, 5]),           # single image, possible drops
            (4, 16, 2, [10, 11]),        # the cf-1.25 serving split shape
            (3, 12, 3, [2, 3, 5]),       # heterogeneous capacities
            (2, 10, 2, [1, 10]),         # starved expert 0
            (3, 6, 2, [8, 9]),           # capacities past the row length
    ]):
        _check_dispatch_vs_oracle(b, s, e, caps, seed)
    # Every token to the last expert, past its capacity.
    _check_dispatch_vs_oracle(2, 10, 3, [2, 3, 4], 5, all_to=2)


@pytest.mark.slow
@settings(max_examples=30, deadline=None)
@given(st.integers(1, 4), st.integers(4, 20), st.integers(2, 4),
       st.integers(1, 8), st.integers(0, 10_000))
def test_per_image_dispatch_matches_numpy_oracle_property(b, s, e, cap, seed):
    _check_dispatch_vs_oracle(b, s, e, [cap] * e, seed)


# ---------------------------------------------------------------------------
# MoE-level: the served dispatch is the per-image one
# ---------------------------------------------------------------------------

def test_moe_dispatch_info_is_per_image():
    """`MoEPrimitives._dispatch_tokens` (the serving front half) must route
    one group per batch row with the per-image capacity plan, and each row's
    routing info must be reproducible from that row alone."""
    model, params, _ = _arm("shiftadd")
    moe = model.blocks[0].feed
    p = params["blocks"][0]["feed"]
    x = jax.random.normal(jax.random.PRNGKey(8), (5, CFG.n_patches,
                                                  CFG.d_model))
    _, info, _, _ = moe._dispatch_tokens(p, x)
    assert info["expert"].shape == (5, CFG.n_patches)      # G == batch rows
    assert info["caps"] == moe.capacity_plan(CFG.n_patches)[0]
    for bi in range(5):
        _, info1, _, _ = moe._dispatch_tokens(p, x[bi:bi + 1])
        for key in ("expert", "pos", "keep", "gate"):
            np.testing.assert_array_equal(np.asarray(info1[key])[0],
                                          np.asarray(info[key])[bi])
