"""Property tests for the grouped capacity dispatcher (nn/dispatch.py) —
the component both MoE flavors (and their TPU sharding) rest on."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _propshim import given, settings, st  # optional-hypothesis shim

from repro.core.moe_primitives import MoEPrimitives
from repro.nn.dispatch import choose_groups, combine, dispatch


def _route(g, s, d, e, k, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    xg = jax.random.normal(ks[0], (g, s, d))
    idx = jax.random.randint(ks[1], (g, s, k), 0, e)
    gate = jax.nn.softmax(jax.random.normal(ks[2], (g, s, k)), -1)
    return xg, idx, gate


def test_identity_experts_reconstruct_gated_input():
    """With identity experts and no drops, combine(dispatch(x)) must equal
    sum_k gate_k * x for every token."""
    g, s, d, e, k = 2, 16, 8, 4, 2
    xg, idx, gate = _route(g, s, d, e, k)
    caps = [s * k] * e           # no drops possible
    buf, aux = dispatch(xg, idx, gate, caps)
    assert float(aux["drop_fraction"]) == 0.0
    y = combine(buf, aux, s, d)  # identity experts: out = buf
    expect = jnp.sum(gate[..., None] * xg[:, :, None, :], axis=2)
    np.testing.assert_allclose(np.asarray(y), np.asarray(expect),
                               rtol=1e-5, atol=1e-5)


def test_capacity_drops_excess_in_token_order():
    g, s, d, e = 1, 10, 4, 1
    xg = jnp.ones((g, s, d))
    idx = jnp.zeros((g, s, 1), jnp.int32)          # everyone → expert 0
    gate = jnp.ones((g, s, 1))
    buf, aux = dispatch(xg, idx, gate, [4])
    assert float(aux["drop_fraction"]) == pytest.approx(0.6)
    y = combine(buf, aux, s, d)
    # first 4 tokens kept (token-order priority), rest zero
    np.testing.assert_allclose(np.asarray(y[0, :4]), 1.0)
    np.testing.assert_allclose(np.asarray(y[0, 4:]), 0.0)


def test_heterogeneous_capacity_segments():
    """Experts own disjoint static row segments sized by their capacities."""
    g, s, d = 1, 8, 4
    xg = jnp.arange(g * s * d, dtype=jnp.float32).reshape(g, s, d)
    idx = jnp.asarray([[0, 0, 1, 1, 1, 1, 1, 1]], jnp.int32)[..., None]
    gate = jnp.ones((g, s, 1))
    caps = [2, 6]
    buf, aux = dispatch(xg, idx, gate, caps)
    np.testing.assert_allclose(np.asarray(buf[0, :2]), np.asarray(xg[0, :2]))
    np.testing.assert_allclose(np.asarray(buf[0, 2:8]), np.asarray(xg[0, 2:8]))


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 3), st.sampled_from([8, 16, 32]),
       st.integers(2, 6), st.integers(1, 2), st.integers(0, 100))
def test_conservation_property(g, s, e, k, seed):
    """No token is double-processed; kept fraction matches capacity math."""
    d = 4
    xg, idx, gate = _route(g, s, d, e, k, seed)
    caps = [max(1, s // e)] * e
    buf, aux = dispatch(xg, idx, gate, caps)
    kept = (1 - float(aux["drop_fraction"])) * g * s * k
    per_expert = np.asarray(aux["tokens_per_expert"])
    expect_kept = sum(min(caps[i] * g, int(per_expert[i])) for i in range(e))
    # tokens_per_expert is summed over groups; per-group capping can only
    # reduce the kept count further:
    assert kept <= expect_kept + 1e-6
    assert np.isfinite(np.asarray(buf)).all()


@pytest.mark.parametrize("tokens,expect", [
    (4096 * 64, 64), (1_048_576, 256), (65536, 32), (128, 1), (2048, 32),
    (7, 1),
])
def test_choose_groups(tokens, expect):
    g = choose_groups(tokens)
    assert g == expect
    assert tokens % g == 0


# ---------------------------------------------------------------------------
# Numpy oracle + property tests (satellite: dispatch/combine coverage)
# ---------------------------------------------------------------------------

def _np_dispatch_oracle(idx, caps):
    """Reference bookkeeping: token-order keep mask + per-expert routed
    counts (pre-capping, summed over groups) — what dispatch() must report."""
    g, s, k = idx.shape
    kept = np.zeros((g, s, k), bool)
    counts = np.zeros(len(caps), np.int64)
    for gi in range(g):
        fill = [0] * len(caps)
        for t in range(s):           # token-order priority, k-major within t
            for kk in range(k):
                e = int(idx[gi, t, kk])
                counts[e] += 1
                if fill[e] < caps[e]:
                    kept[gi, t, kk] = True
                    fill[e] += 1
    return kept, counts


def _check_exact_reconstruction(g, s, e, k, seed):
    d = 4
    xg, idx, gate = _route(g, s, d, e, k, seed)
    caps = [s * k] * e               # capacities cover every token: no drops
    buf, aux = dispatch(xg, idx, gate, caps)
    assert float(aux["drop_fraction"]) == 0.0
    y = combine(buf, aux, s, d)      # identity experts
    expect = jnp.sum(gate[..., None] * xg[:, :, None, :], axis=2)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(expect))


def _check_bookkeeping_oracle(g, s, e, k, cap, seed):
    d = 4
    xg, idx, gate = _route(g, s, d, e, k, seed)
    caps = [cap] * e
    _, aux = dispatch(xg, idx, gate, caps)
    kept, counts = _np_dispatch_oracle(np.asarray(idx), caps)
    np.testing.assert_array_equal(np.asarray(aux["tokens_per_expert"]), counts)
    assert float(aux["drop_fraction"]) == pytest.approx(1.0 - kept.mean())


def test_exact_reconstruction_examples():
    """Deterministic arm of the property below (runs without hypothesis)."""
    for seed, (g, s, e, k) in enumerate([(1, 8, 2, 1), (2, 16, 4, 2),
                                         (3, 32, 3, 2)]):
        _check_exact_reconstruction(g, s, e, k, seed)


def test_bookkeeping_oracle_examples():
    for seed, (g, s, e, k, cap) in enumerate([(1, 10, 2, 1, 3),
                                              (2, 16, 3, 2, 4),
                                              (1, 32, 4, 1, 2)]):
        _check_bookkeeping_oracle(g, s, e, k, cap, seed)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 3), st.integers(4, 24), st.integers(2, 5),
       st.integers(1, 2), st.integers(0, 10_000))
def test_exact_reconstruction_property(g, s, e, k, seed):
    """combine(dispatch(x)) == Σ_k gate_k · x EXACTLY whenever capacities
    cover all tokens (identity experts; no droppage ⇒ bit-exact scatter)."""
    _check_exact_reconstruction(g, s, e, k, seed)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 3), st.integers(4, 24), st.integers(2, 5),
       st.integers(1, 2), st.integers(1, 6), st.integers(0, 10_000))
def test_bookkeeping_matches_numpy_oracle(g, s, e, k, cap, seed):
    """tokens_per_expert / drop_fraction under droppage must match the naive
    numpy re-implementation of token-order capacity filling."""
    _check_bookkeeping_oracle(g, s, e, k, cap, seed)


# ---------------------------------------------------------------------------
# Gather-ordered inference dispatch (ISSUE 3): parity with the scatter path
# ---------------------------------------------------------------------------

def _identity_expert_outs(buf, caps):
    """Per-expert static views of the segment buffer (identity experts)."""
    outs, off = [], 0
    for c in caps:
        outs.append(buf[:, off:off + c, :])
        off += c
    return outs


def _check_infer_matches_scatter(g, s, e, caps, seed, all_to=None):
    """combine_infer(dispatch_infer(x)) with identity experts must equal the
    training scatter path bit-for-bit (same token-order priority, same
    drops) — the gather rewrite may not change a single logit. `all_to`
    routes every token to that expert instead of at random."""
    from repro.nn.dispatch import combine_infer, dispatch_infer

    d = 4
    xg, idx, gate = _route(g, s, d, e, 1, seed)
    if all_to is not None:
        idx = jnp.full_like(idx, all_to)
    buf_t, aux_t = dispatch(xg, idx, gate, caps, stats=False)
    y_t = combine(buf_t, aux_t, s, d)
    buf_i, info = dispatch_infer(xg, idx[..., 0], gate[..., 0], caps)
    y_i = combine_infer(_identity_expert_outs(buf_i, caps), info)
    np.testing.assert_array_equal(np.asarray(y_t), np.asarray(y_i))
    # Live buffer rows must agree too (dead rows are deliberately unmasked
    # in the gather path — nothing reads them back, so only live rows are
    # comparable).
    idx_np = np.asarray(idx[..., 0])
    bt, bi = np.asarray(buf_t), np.asarray(buf_i)
    off = 0
    for ei, cap in enumerate(caps):
        for gi in range(g):
            live = min(int((idx_np[gi] == ei).sum()), cap)
            np.testing.assert_array_equal(bt[gi, off:off + live],
                                          bi[gi, off:off + live])
        off += cap


def test_infer_dispatch_matches_scatter_examples():
    for seed, (g, s, e, caps) in enumerate([
            (1, 8, 2, [4, 4]),          # balanced, possible drops
            (2, 16, 2, [16, 16]),       # no drops possible
            (1, 10, 3, [2, 3, 5]),      # heterogeneous capacities
            (3, 12, 2, [1, 12]),        # starved expert 0
            (2, 6, 2, [9, 8]),          # capacities past the row length
    ]):
        _check_infer_matches_scatter(g, s, e, caps, seed)
    # Every token to the last expert: the others' segments hold no live row.
    _check_infer_matches_scatter(2, 10, 3, [2, 3, 4], 7, all_to=2)


def test_infer_dispatch_all_tokens_one_expert():
    """Everyone routes to expert 0 and overflows its capacity: kept prefix in
    token order, dropped tokens contribute exactly zero."""
    from repro.nn.dispatch import combine_infer, dispatch_infer

    g, s, d = 1, 10, 4
    xg = jnp.arange(g * s * d, dtype=jnp.float32).reshape(g, s, d)
    idx = jnp.zeros((g, s), jnp.int32)
    gate = jnp.ones((g, s))
    caps = [4, 3]
    buf, info = dispatch_infer(xg, idx, gate, caps)
    np.testing.assert_array_equal(np.asarray(buf[0, :4]), np.asarray(xg[0, :4]))
    y = combine_infer(_identity_expert_outs(buf, caps), info)
    np.testing.assert_array_equal(np.asarray(y[0, :4]), np.asarray(xg[0, :4]))
    np.testing.assert_array_equal(np.asarray(y[0, 4:]), 0.0)


@settings(max_examples=30, deadline=None)
@given(st.integers(1, 3), st.integers(4, 24), st.integers(2, 4),
       st.integers(1, 8), st.integers(0, 10_000))
def test_infer_dispatch_matches_scatter_property(g, s, e, cap, seed):
    _check_infer_matches_scatter(g, s, e, [cap] * e, seed)


def test_stats_false_skips_bookkeeping_but_combines_identically():
    """The inference dispatch path: same buffer and combine aux, no stats."""
    g, s, d, e, k = 2, 16, 8, 4, 1
    xg, idx, gate = _route(g, s, d, e, k)
    caps = [s] * e
    buf_t, aux_t = dispatch(xg, idx, gate, caps, stats=True)
    buf_i, aux_i = dispatch(xg, idx, gate, caps, stats=False)
    assert "tokens_per_expert" not in aux_i and "drop_fraction" not in aux_i
    np.testing.assert_array_equal(np.asarray(buf_t), np.asarray(buf_i))
    np.testing.assert_array_equal(np.asarray(combine(buf_t, aux_t, s, d)),
                                  np.asarray(combine(buf_i, aux_i, s, d)))


# ---------------------------------------------------------------------------
# Gating rule and the lowered serving MoE
# ---------------------------------------------------------------------------

def test_gates_match_numpy_oracle():
    """`_gates` gives probs[top1] bit for bit, with top1 the argmax of the
    selecting logits: clean ones (serving) and noisy ones whose argmax
    differs from the clean one (training); its gradient is that of the
    indexed lookup."""
    ks = jax.random.split(jax.random.PRNGKey(3), 2)
    clean = jax.random.normal(ks[0], (3, 64, 3))
    noisy = clean + 2.0 * jax.random.normal(ks[1], clean.shape)
    assert (np.argmax(noisy, -1) != np.argmax(clean, -1)).any()
    for select in (clean, noisy):
        probs, top1, gate = MoEPrimitives._gates(select, clean)
        want_top1 = np.argmax(np.asarray(select), axis=-1)
        np.testing.assert_array_equal(np.asarray(top1), want_top1)
        want = np.take_along_axis(np.asarray(probs), want_top1[..., None], -1)
        np.testing.assert_array_equal(np.asarray(gate), want)

        def indexed(c, select=select):
            p = jax.nn.softmax(c, axis=-1)
            t = jnp.argmax(select, axis=-1)
            return jnp.sum(jnp.take_along_axis(p, t[..., None], -1) ** 2)

        got = jax.grad(lambda c, select=select: jnp.sum(
            MoEPrimitives._gates(select, c)[2] ** 2))(clean)
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(jax.grad(indexed)(clean)))


def test_serving_moe_lowers_without_index_gathers():
    """The compiled serving MoE moves rows with 1 + n_experts gathers (the
    flat dispatch gather, one combine gather per expert) and computes its
    index math (gate, rank, source rows) with none: a batched gather of
    scalars runs as a serial loop on TPU."""
    moe = MoEPrimitives(64, 128, ("mult", "shift"), capacity_ref_tokens=196)
    params = moe.init(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 196, 64))
    hlo = jax.jit(moe.infer).lower(params, x).compile().as_text()
    gathers = [ln for ln in hlo.splitlines()
               if re.search(r"= \S+ gather\(", ln)]
    assert len(gathers) == 1 + moe.n_experts, gathers
    assert not [ln for ln in gathers if "take_along_axis" in ln]
