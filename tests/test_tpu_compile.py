"""Compile the serving kernels and the frozen DeiT-Tiny forward for a TPU v5e
that is described, not attached.

The TPU compiler is installed next to the CPU backend, so
`jax.jit(f).lower(shapes on a described device).compile()` raises whatever
Mosaic would raise on the chip: unaligned blocks, vector ops it cannot
lower, casts it does not support, kernels it cannot partition. Interpret
mode (tests/test_kernels.py) cannot see any of these. Nothing runs: these
tests say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and a file that decided at import whether
its tests exist would give pytest-xdist workers different collections.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

# DeiT-Tiny serving geometry: 196 patches, d_model 192, 3 heads of 64, d_ff
# 768; batch 8 for the attention kernels and the whole forward.
BATCH, HEADS, TOKENS, HEAD_DIM = 8, 3, 196, 64
SHIFT_WIDTHS = {"qkvo": (192, 192), "fc1": (192, 768), "fc2": (768, 192)}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    # Describing a topology loads libtpu, which otherwise writes its logs to
    # the fixed, shared /tmp/tpu_logs.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without one; keep these compiles out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _mosaic_calls(f, *shapes):
    text = jax.jit(f).lower(*shapes).compile().as_text()
    return text.count("tpu_custom_call")


@pytest.mark.parametrize("width", sorted(SHIFT_WIDTHS))
@pytest.mark.parametrize("m", [1, 32 * TOKENS])
def test_shift_matmul_compiles(one_chip, m, width):
    k, n = SHIFT_WIDTHS[width]
    x = jax.ShapeDtypeStruct((m, k), jnp.float32, sharding=one_chip)
    w = jax.ShapeDtypeStruct((k, n), jnp.int8, sharding=one_chip)
    assert _mosaic_calls(lambda a, b: ops.shift_matmul(a, b, "pallas"),
                         x, w) >= 1


def test_add_matmul_compiles(one_chip):
    g = BATCH * HEADS
    x = jax.ShapeDtypeStruct((g, TOKENS, HEAD_DIM), jnp.float32,
                             sharding=one_chip)
    b = jax.ShapeDtypeStruct((g, HEAD_DIM, HEAD_DIM), jnp.int8,
                             sharding=one_chip)
    assert _mosaic_calls(lambda a, c: ops.add_matmul(a, c, "pallas"),
                         x, b) >= 1


def test_add_matmul_packed_compiles(one_chip):
    g = BATCH * HEADS
    x = jax.ShapeDtypeStruct((g, TOKENS, HEAD_DIM), jnp.float32,
                             sharding=one_chip)
    packed = jax.ShapeDtypeStruct((g, HEAD_DIM // 8, HEAD_DIM), jnp.uint8,
                                  sharding=one_chip)
    assert _mosaic_calls(
        lambda a, p: ops.add_matmul_bitpacked(a, p, "pallas"), x, packed) >= 1


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bidir_attention_compiles(one_chip, dtype):
    qkv = [jax.ShapeDtypeStruct((BATCH, HEADS, TOKENS, HEAD_DIM), dtype,
                                sharding=one_chip)] * 3
    assert _mosaic_calls(
        lambda q, k, v: ops.binary_linear_attention_bidir(q, k, v,
                                                          impl="pallas"),
        *qkv) >= 1


@pytest.mark.parametrize("return_state", [False, True])
def test_causal_attention_compiles(one_chip, return_state):
    qkv = [jax.ShapeDtypeStruct((BATCH, HEADS, TOKENS, HEAD_DIM),
                                jnp.float32, sharding=one_chip)] * 3
    assert _mosaic_calls(
        lambda q, k, v: ops.binary_linear_attention_fused(
            q, k, v, impl="pallas", return_state=return_state),
        *qkv) >= 1


def test_frozen_shiftadd_deit_tiny_engine_compiles(one_chip):
    """The engine's own program: frozen weights closed over as constants,
    every shift linear and attention through Mosaic."""
    from repro.core.policy import DENSE
    from repro.nn.vit import ShiftAddViT, ViTConfig
    from repro.serve.vision import BucketedViTEngine, build_policy_model

    cfg = ViTConfig(image_size=224, patch_size=16, n_classes=1000,
                    n_layers=12, d_model=192, n_heads=HEADS, d_ff=768)
    dense = ShiftAddViT(dataclasses.replace(cfg, policy=DENSE))
    model, params = build_policy_model(cfg, "shiftadd", dense,
                                       dense.init(jax.random.PRNGKey(0)))
    engine = BucketedViTEngine(model, params, buckets=(BATCH,),
                               impl="pallas")
    images = jax.ShapeDtypeStruct((BATCH, 224, 224, 3), jnp.float32,
                                  sharding=one_chip)
    text = engine._call.lower(images).compile().as_text()
    # 12 layers x (4 shift projections + fused attention + 2 shift-expert
    # linears) = 84 kernels.
    assert text.count("tpu_custom_call") == 12 * 7
