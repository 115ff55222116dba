"""Logical-axis sharding rules → mesh PartitionSpecs (DESIGN.md §3).

Model modules annotate parameters with logical axis names via `.spec()`;
this module maps them onto the production mesh:

    batch   → (pod, data)   activations / inputs (DP)
    embed   → data          FSDP weight shard of d_model dims
    vocab, heads, mlp, experts → model   (TP / EP)
    layers  → (replicated)  scan-stacked depth dim

Params are therefore sharded over BOTH data (FSDP) and model (TP) inside a
pod and replicated across pods (gradients all-reduce over `pod`). A logical
axis maps to nothing if its mesh axis is absent (single-pod mesh has no
`pod`) or if the dim is smaller than the mesh axis (e.g. kv_heads=1 MQA).
"""
from __future__ import annotations

import math

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

def make_mesh(shape, axes, *, devices=None):
    """jax.make_mesh with every axis Auto (GSPMD propagates shardings)."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(shape, axes, **kwargs,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


LOGICAL_AXIS_RULES = {
    "batch": ("pod", "data"),
    "embed": ("data",),
    "vocab": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "mlp": ("model",),
    "experts": ("model",),
    "seq": ("data",),
    # Sequence-parallel fallback: used by attention internals so that archs
    # whose head count doesn't divide the model axis (e.g. 40 heads on 16)
    # still shard their O(N·chunk) score buffers — over the query length.
    "seq_model": ("model",),
    # §Perf experiment: weights FSDP-sharded on the OUT dim over (data,model)
    # with the contraction dim unsharded (avoids per-layer contraction
    # all-reduces over data; GSPMD gathers the weight shard instead).
    "fsdp_out": ("data", "model"),
    "layers": (),
    None: (),
}


def spec_to_out_fsdp(spec_tree):
    """Rewrite 2D linear specs (in→data, out→model) to (None, fsdp_out)."""
    def is_axes(x):
        return isinstance(x, tuple) and all(isinstance(a, str) or a is None
                                            for a in x)

    def one(axes):
        a = tuple(axes)
        core = a[-2:] if len(a) >= 2 else a
        if len(a) >= 2 and core[0] == "embed" and core[1] in (
                "heads", "mlp", "vocab", "kv_heads"):
            return a[:-2] + (None, "fsdp_out")
        return a

    return jax.tree_util.tree_map(one, spec_tree, is_leaf=is_axes)


def spec_to_tp_zero1(spec_tree):
    """TP + ZeRO-1: drop the data-axis (embed) shard from weight matrices so
    contractions never partial-sum over `data` (no per-layer per-microbatch
    activation all-reduces). Weights are then replicated over data; the
    optimizer state keeps the full (data, model) shard (ZeRO-1) — dryrun
    passes the original spec for m/v. Embedding tables keep their vocab
    shard (gathers don't contract)."""
    def is_axes(x):
        return isinstance(x, tuple) and all(isinstance(a, str) or a is None
                                            for a in x)

    def one(axes):
        a = tuple(axes)
        if len(a) >= 2 and a == ("vocab", "embed"):
            return a                       # embedding table: keep
        return tuple(None if x == "embed" else x for x in a)

    return jax.tree_util.tree_map(one, spec_tree, is_leaf=is_axes)


def _mesh_axes_for(logical, mesh, dim_size=None, used=()):
    axes = LOGICAL_AXIS_RULES.get(logical, ())
    present = tuple(a for a in axes if a in mesh.axis_names and a not in used)
    if not present:
        return None
    total = math.prod(mesh.shape[a] for a in present)
    if dim_size is not None and dim_size % total != 0:
        # Uneven shard: prefer dropping axes (right-to-left) until divisible;
        # fall back to replication. Keeps GSPMD away from padded shards on
        # dims like kv_heads=1 or odd vocab sizes.
        while present:
            total = math.prod(mesh.shape[a] for a in present)
            if dim_size % total == 0:
                break
            present = present[:-1]
        if not present:
            return None
    return present if len(present) > 1 else present[0]


def logical_to_pspec(axes, mesh, shape=None):
    """axes: tuple of logical names (len == rank). shape optional for
    divisibility-aware fallback. A mesh axis is used at most once — later
    dims lose (enables 'shard heads if divisible, else the seq dim' specs)."""
    entries = []
    used = []
    for i, name in enumerate(axes):
        dim = None if shape is None else shape[i]
        e = _mesh_axes_for(name, mesh, dim, used=tuple(used))
        if e is not None:
            used.extend(e if isinstance(e, tuple) else (e,))
        entries.append(e)
    while entries and entries[-1] is None:
        entries.pop()
    return P(*entries)


def shardings_from_spec(spec_tree, shape_tree, mesh):
    """Map a logical-axis spec tree + matching shape tree (arrays or
    ShapeDtypeStructs) to NamedShardings."""
    is_axes = lambda x: isinstance(x, tuple) and all(
        isinstance(a, str) or a is None for a in x)

    def one(axes, arr):
        return NamedSharding(mesh, logical_to_pspec(axes, mesh, arr.shape))

    return jax.tree_util.tree_map(one, spec_tree, shape_tree, is_leaf=is_axes)


def batch_sharding(mesh, rank=2, extra=None):
    """Inputs: leading dim over (pod, data); rest replicated.
    extra: logical names for trailing dims."""
    axes = ["batch"] + [None] * (rank - 1)
    if extra:
        axes[1:1 + len(extra)] = list(extra)
    return NamedSharding(mesh, logical_to_pspec(tuple(axes), mesh))


def replicated(mesh):
    return NamedSharding(mesh, P())


_ACTIVE_MESH = None


def set_active_mesh(mesh):
    """Declare the mesh used by subsequent traces so `constrain` can resolve
    logical activation shardings (dryrun/train set this; tests leave None)."""
    global _ACTIVE_MESH
    _ACTIVE_MESH = mesh


def constrain(x, axes, mesh=None):
    """Activation sharding constraint by logical names (no-op outside mesh).
    A constraint the mesh cannot take raises: it never silently vanishes."""
    mesh = mesh or _ACTIVE_MESH
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, logical_to_pspec(axes, mesh, x.shape)))
