"""Grouped capacity dispatch for MoE layers (GShard-style groups, sort-based).

Why groups: routing over the *global* token axis (argsort/cumsum/scatter over
~1M tokens) forces GSPMD to replicate dispatch buffers on every device — the
69 GiB/device failure mode. Tokens are instead split into G groups that shard
over the (pod, data) mesh axes; every dispatch op is per-group, so routing
stays device-local and the only cross-device movement is the expert-parallel
reshard of the (G, E, cap, d) buffer on the model axis (the classic MoE
all-to-all, inserted by GSPMD at the sharding constraint).

Capacity is per-group (cap_e per expert per group) — statistically equivalent
to global capacity for iid token order, and the paper's latency-aware
capacities translate per group unchanged. Supports heterogeneous per-expert
capacities (the MoE-of-primitives needs them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.sharding import constrain


def choose_groups(tokens: int, target_group=4096, min_groups=32) -> int:
    """Number of routing groups: ≥ min_groups when possible (so groups shard
    over pod×data), each ≥64 tokens (smaller groups degrade routing quality
    more than replication costs at that size), and G | tokens."""
    if tokens % target_group == 0 and tokens // target_group >= min_groups:
        return tokens // target_group
    for size in (2048, 1024, 512, 256, 128, 64):
        if tokens % size == 0 and tokens // size >= min_groups:
            return tokens // size
    if tokens % min_groups == 0 and tokens // min_groups >= 64:
        return min_groups
    return 1


def dispatch(xg, expert_idx, keep_gate, caps, stats=True):
    """Per-group sort-based dispatch, vmapped over the leading group axis.

    xg: (G, S, d); expert_idx: (G, S, k); keep_gate: (G, S, k) combine weights.
    caps: python list of per-expert capacities (static).
    Returns (buf (G, total, d), aux) where total = sum(caps); expert e owns
    rows [offset_e, offset_e + cap_e). aux carries what combine() needs.

    stats=False is the inference path: aux carries only what combine() needs,
    no tokens_per_expert / drop_fraction bookkeeping (the serving engine never
    reads them, and leaving them out keeps the compiled program free of the
    cross-group reductions).
    """
    n_exp = len(caps)
    offsets = [0]
    for c in caps:
        offsets.append(offsets[-1] + c)
    total = offsets[-1]
    caps_arr = jnp.asarray(caps, jnp.int32)
    offs_arr = jnp.asarray(offsets[:-1], jnp.int32)

    def one(x, idx, gate):
        s, k = idx.shape
        flat_e = idx.reshape(s * k)
        flat_g = gate.reshape(s * k)
        flat_t = jnp.repeat(jnp.arange(s), k)
        counts = jnp.bincount(flat_e, length=n_exp)
        starts = jnp.cumsum(counts) - counts
        order = jnp.argsort(flat_e, stable=True)      # token-order priority
        e_sorted = flat_e[order]
        pos = jnp.arange(s * k) - starts[e_sorted]
        keep = pos < caps_arr[e_sorted]
        slot = jnp.where(keep, offs_arr[e_sorted] + pos, total)
        tok = flat_t[order]
        gathered = x[tok] * keep[:, None].astype(x.dtype)
        buf = jnp.zeros((total + 1, x.shape[-1]), x.dtype).at[slot].set(gathered)
        w = flat_g[order] * keep.astype(flat_g.dtype)
        return buf[:-1], slot, tok, w, counts, keep

    buf, slot, tok, w, counts, keep = jax.vmap(one)(xg, expert_idx, keep_gate)
    aux = {"slot": slot, "tok": tok, "w": w, "total": total}
    if stats:
        aux["tokens_per_expert"] = jnp.sum(counts, axis=0)
        aux["drop_fraction"] = 1.0 - jnp.mean(keep.astype(jnp.float32))
    return buf, aux


def combine(expert_out_flat, aux, s, d):
    """expert_out_flat: (G, total, d) expert outputs in slot order → (G, S, d)."""
    total = aux["total"]

    def one(out_flat, slot, tok, w):
        y_sorted = out_flat[jnp.minimum(slot, total - 1)]
        contrib = y_sorted * w[:, None].astype(y_sorted.dtype)
        return jnp.zeros((s, d), out_flat.dtype).at[tok].add(contrib)

    return jax.vmap(one)(expert_out_flat, aux["slot"], aux["tok"], aux["w"])


# ---------------------------------------------------------------------------
# Inference dispatch: gather-ordered segment buffer, gather-free index math
# ---------------------------------------------------------------------------
#
# The training dispatch above scatters tokens into a zeroed buffer
# (`zeros().at[slot].set`) and the combine scatter-adds back — correct under
# vmap/grad, but at serve time (top-1, no drop statistics) both scatters are
# avoidable: the buffer can be built by a GATHER from the token array (rows
# in expert-segment order), experts run on per-expert static views of it,
# and each token's output is a gather from its expert's segment. No
# scatter-into-zeros, no concatenate of expert outputs.
#
# The index math that feeds those gathers uses none: selects over the E
# experts and one stable sort per expert. A batched gather of scalars
# (`take_along_axis` over (G, S) rows) lowers on TPU to a serial loop at
# about 10 ns per element fetched; on a TPU v5e trace of one DeiT-Tiny
# bucket-32 forward, three such gathers per layer (a token's rank in its
# expert, its gate, the buffer's source rows) took 2.575 of its 10.84 ms,
# while the one flat row gather of 6 MB took 22 us per layer.

def dispatch_infer(xg, expert_idx, gate, caps):
    """Top-1 inference dispatch. xg: (G, S, d); expert_idx: (G, S) int;
    gate: (G, S) combine weights; caps: python list of static capacities.

    Returns (buf (G, total, d), info). Expert e owns rows
    [offset_e, offset_e + cap_e) of buf; rows are filled by gathering the
    tokens routed to e in token order (priority identical to `dispatch`).
    Only the first min(count_e, cap_e) rows of a segment are live; the rest
    hold some real token of the same row, which nothing reads back. info
    carries what `combine_infer` needs: each token's within-expert rank
    (pos), its keep flag, its expert and its gate.

    All row movement is a single FLAT gather from the (G·S, d) token array,
    and the index math has no gather at all: a vmapped per-group gather
    lowers to a batched gather that TPU XLA executes as a scalar loop (see
    the comment above). A token's rank and capacity are selects over a
    one-hot of its expert; segment e's source rows are the first cap_e
    entries of a stable argsort of (expert_idx != e), a static slice. E
    sorts of a row cost O(E·S log S), like one sort, so long rows pay no
    quadratic term.
    """
    g, s, d = xg.shape
    n_exp = len(caps)
    onehot = (expert_idx[..., None] == jnp.arange(n_exp)).astype(jnp.int32)
    # Token-order rank of each token within its expert (same priority rule
    # as the sort-based dispatch: earlier tokens win capacity ties), read
    # off its own expert's running count by a select: exactly one term of
    # each sum is nonzero, so both are exact.
    pos = jnp.sum(onehot * (jnp.cumsum(onehot, axis=1) - onehot), axis=-1)
    keep = pos < jnp.sum(onehot * jnp.asarray(caps, jnp.int32), axis=-1)
    # Tokens routed to e sort first, in token order (the sort is stable). A
    # capacity past the row length repeats the last entry.
    segments = []
    for e, cap in enumerate(caps):
        order = jnp.argsort((expert_idx != e).astype(jnp.int32), axis=-1,
                            stable=True)[:, :cap]
        if cap > s:
            order = jnp.pad(order, ((0, 0), (0, cap - s)), mode="edge")
        segments.append(order)
    src = jnp.concatenate(segments, axis=-1)                   # (G, total)
    flat_src = (src + jnp.arange(g, dtype=src.dtype)[:, None] * s).reshape(-1)
    buf = xg.reshape(g * s, d)[flat_src].reshape(g, src.shape[1], d)
    # Rows past an expert's live token count are deliberately unmasked:
    # combine_infer reads only a segment's first min(count_e, cap_e) rows
    # back, so zeroing the dead rows would be a (G, total, d) elementwise op
    # spent on values nothing consumes. (The training `dispatch` zero-fills
    # because its scatter-add combine touches every buffer row.)
    info = {"expert": expert_idx, "pos": pos, "keep": keep, "gate": gate,
            "caps": tuple(caps)}
    return buf, info


def combine_infer(expert_outs, info):
    """expert_outs: list of (G, cap_e, d) per-expert outputs in segment order
    → (G, S, d). Pure gathers: each token reads row `pos` of its expert's
    segment (top-1 ⇒ exactly one contribution), scaled by gate·keep. Flat
    single-gather per expert, same rationale as dispatch_infer."""
    expert, pos, keep, gate = (info["expert"], info["pos"], info["keep"],
                               info["gate"])
    g, s = expert.shape
    y = None
    for e, out_e in enumerate(expert_outs):
        cap_e = out_e.shape[1]
        sel = jnp.clip(pos, 0, cap_e - 1)
        flat = (sel + jnp.arange(g, dtype=sel.dtype)[:, None] * cap_e).reshape(-1)
        got = out_e.reshape(g * cap_e, -1)[flat].reshape(g, s, -1)
        got = jnp.where((expert == e)[..., None], got, 0.0)
        y = got if y is None else y + got
    w = (gate * keep.astype(gate.dtype)).astype(y.dtype)
    return y * w[..., None]


def group_tokens(x, d_model, target_group=4096, min_groups=32):
    """(..., d) → (G, S, d) plus an ungroup closure.

    TRAINING grouping: the token axis is flattened across batch rows and cut
    into G size-balanced groups (see module docstring for why). Group
    boundaries therefore ignore image/sequence boundaries — a token's
    capacity competitors are whatever the flattening put next to it, which
    is statistically fine for training but makes an image's routing depend
    on its co-batched neighbors. Serving uses `group_rows` instead."""
    lead = x.shape[:-1]
    tokens = 1
    for s in lead:
        tokens *= int(s)
    g = choose_groups(tokens, target_group, min_groups)
    xg = x.reshape(g, tokens // g, d_model)
    xg = constrain(xg, ("batch", None, None))

    def ungroup(y):
        return y.reshape(*lead, d_model)

    return xg, ungroup


def group_rows(x, d_model):
    """(..., S, d) → (G, S, d) with ONE routing group per batch row, plus an
    ungroup closure — the SERVING grouping (ISSUE 5 tentpole).

    Each image (batch row) is its own capacity domain: expert capacities are
    planned from the per-row token count and every dispatch op is vmapped
    over rows, so a row's routing reads nothing but that row's tokens. This
    is the batch-invariance contract the shiftadd serving path asserts:
    per-image logits are bit-identical across batch composition, row order,
    bucket padding and replica count. Tokens-per-row is static per engine
    bucket, so shapes (and the memoized capacity plan) stay jit-stable.

    A 2-D input (S, d) is treated as a single row. Rows shard over the
    mesh's batch axes exactly like the flattened grouping did — per-row
    dispatch is device-local under the `batch → data` rule."""
    if x.ndim == 2:
        xg = x[None]
    else:
        lead = x.shape[:-2]
        rows = 1
        for s in lead:
            rows *= int(s)
        xg = x.reshape(rows, x.shape[-2], d_model)
    xg = constrain(xg, ("batch", None, None))

    def ungroup(y):
        return y.reshape(*x.shape[:-1], d_model)

    return xg, ungroup
