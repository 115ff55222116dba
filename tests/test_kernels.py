"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs ref.py oracles.

Tolerances follow the bf16 reality of the MXU path: the kernels cast inputs
to bf16 before the dot, so comparisons are made against a bf16-cast oracle
with rtol≈2e-2 on output-scale-normalized error.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.kernels import ops, ref


def _close(a, b, tol=2e-2):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    scale = max(np.std(b), 1e-3)
    err = np.max(np.abs(a - b)) / scale
    assert err < tol, f"scaled err {err}"


SHIFT_SHAPES = [(8, 32, 16), (70, 300, 200), (128, 512, 128), (1, 64, 640)]


@pytest.mark.parametrize("m,k,n", SHIFT_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_shift_matmul_sweep(m, k, n, dtype):
    w = jax.random.normal(jax.random.PRNGKey(0), (k, n)) * 0.05
    wp = quant.pack_from_dense(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k)).astype(dtype)
    out_ref = ref.shift_matmul_ref(x.astype(jnp.float32), wp)
    out_pal = ops.shift_matmul(x, wp, "interpret")
    out_xla = ops.shift_matmul(x, wp, "xla")
    _close(out_pal, out_ref)
    _close(out_xla, out_ref, tol=1e-2 if dtype == jnp.float32 else 2e-2)


def test_shift_matmul_grad_matches_dense():
    w = jax.random.normal(jax.random.PRNGKey(0), (64, 32)) * 0.1
    wp = quant.pack_from_dense(w)
    wq = quant.po2_weight_from_packed(wp, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 64))
    g1 = jax.grad(lambda xx: ops.shift_matmul(xx, wp, "xla").sum())(x)
    g2 = jax.grad(lambda xx: (xx @ wq).sum())(x)
    _close(g1, g2, tol=1e-3)


ADD_SHAPES = [(2, 8, 32, 16), (6, 50, 100, 60), (1, 128, 512, 128)]


@pytest.mark.parametrize("g,m,k,n", ADD_SHAPES)
def test_add_matmul_sweep(g, m, k, n):
    b = (jax.random.randint(jax.random.PRNGKey(2), (g, k, n), 0, 2, jnp.int8)
         * 2 - 1).astype(jnp.int8)
    x = jax.random.normal(jax.random.PRNGKey(3), (g, m, k))
    out_ref = ref.add_matmul_ref(x, b)
    _close(ops.add_matmul(x, b, "interpret"), out_ref)
    _close(ops.add_matmul(x, b, "xla"), out_ref, tol=1e-3)


def test_add_matmul_zero_entries_skip():
    """b=0 encodes skipped weights — they must contribute nothing."""
    b = jnp.zeros((1, 16, 8), jnp.int8)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 4, 16))
    out = ops.add_matmul(x, b, "interpret")
    np.testing.assert_allclose(np.asarray(out), 0.0)


LINATTN_SHAPES = [
    (1, 1, 128, 16, 16), (2, 2, 256, 64, 64), (1, 3, 512, 80, 80),
    (2, 1, 384, 128, 96),
]


@pytest.mark.parametrize("b,h,n,dk,dv", LINATTN_SHAPES)
def test_binary_linear_attention_kernel_sweep(b, h, n, dk, dv):
    q = jax.random.normal(jax.random.PRNGKey(4), (b, h, n, dk))
    k = jax.random.normal(jax.random.PRNGKey(5), (b, h, n, dk))
    v = jax.random.normal(jax.random.PRNGKey(6), (b, h, n, dv))
    out_ref = ref.binary_linear_attention_ref(q, k, v, causal=True)
    out_pal = ops.binary_linear_attention_fused(q, k, v, chunk=128,
                                                impl="interpret")
    _close(out_pal, out_ref, tol=1e-3)


@pytest.mark.parametrize("g,m,k,n", [(2, 16, 64, 32), (1, 50, 128, 96),
                                     (3, 8, 256, 128)])
def test_add_matmul_bitpacked_sweep(g, m, k, n):
    """Beyond-paper 1-bit packed operand: 8× less traffic, same math."""
    from repro.kernels.add_matmul_packed import pack_bits, unpack_bits

    b = (jax.random.randint(jax.random.PRNGKey(g), (g, k, n), 0, 2, jnp.int8)
         * 2 - 1).astype(jnp.int8)
    packed = pack_bits(b)
    np.testing.assert_array_equal(np.asarray(unpack_bits(packed)),
                                  np.asarray(b, np.float32))
    x = jax.random.normal(jax.random.PRNGKey(g + 7), (g, m, k))
    out_ref = ref.add_matmul_ref(x, b)
    _close(ops.add_matmul_bitpacked(x, packed, "interpret"), out_ref)
    _close(ops.add_matmul_bitpacked(x, packed, "xla"), out_ref, tol=1e-3)


ALL_BYTES = np.arange(256, dtype=np.uint8)


def test_shift_kernel_decode_bitexact_all_bytes():
    """The kernel's 32-bit decode of every packed byte equals
    po2_weight_from_packed bit for bit: x = I picks each weight row out of
    the MXU product unchanged (one nonzero term per output)."""
    w_packed = jnp.asarray(ALL_BYTES.view(np.int8).reshape(256, 1))
    y = ops.shift_matmul(jnp.eye(256, dtype=jnp.float32), w_packed,
                         "interpret")
    want = quant.po2_weight_from_packed(w_packed, jnp.float32)
    np.testing.assert_array_equal(np.asarray(y).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


def test_packed_bits_kernel_decode_bitexact_all_bytes():
    """The kernel's int32 bit unpack of every byte equals unpack_bits:
    x = I (8 logical K rows) reads the ±1 operand back out exactly."""
    from repro.kernels.add_matmul_packed import unpack_bits

    packed = jnp.asarray(ALL_BYTES.reshape(1, 1, 256))
    y = ops.add_matmul_bitpacked(jnp.eye(8, dtype=jnp.float32)[None],
                                 packed, "interpret")
    np.testing.assert_array_equal(np.asarray(y),
                                  np.asarray(unpack_bits(packed)))


@pytest.mark.parametrize("b,h,n,dk,dv", [(1, 2, 256, 16, 16),
                                         (2, 1, 300, 24, 20),
                                         (2, 3, 196, 64, 64)])
def test_linattn_kernel_returns_final_carry(b, h, n, dk, dv):
    """return_state must emit the exact recurrent carry (kv, ksum, vsum) the
    O(1) decode step resumes from — including when N is padded to the chunk."""
    q = jax.random.normal(jax.random.PRNGKey(10), (b, h, n, dk))
    k = jax.random.normal(jax.random.PRNGKey(11), (b, h, n, dk))
    v = jax.random.normal(jax.random.PRNGKey(12), (b, h, n, dv))
    state_ref = ref.binary_linear_attention_state_ref(q, k, v)
    for impl in ("interpret", "xla"):
        out, state = ops.binary_linear_attention_fused(
            q, k, v, chunk=128, impl=impl, return_state=True)
        _close(out, ref.binary_linear_attention_ref(q, k, v, causal=True),
               tol=1e-3)
        for key in ("kv", "ksum", "vsum", "count"):
            _close(state[key], state_ref[key], tol=1e-3)


BIDIR_SHAPES = [
    (1, 2, 64, 32, 32),      # aligned ViT bucket shape
    (2, 2, 197, 64, 48),     # DeiT token count: odd N, odd Dv
    (1, 3, 196, 80, 80),     # the benchmark's 56×56/4 geometry
    (2, 1, 8, 16, 16),       # tiny: N below one sublane tile
]


@pytest.mark.parametrize("b,h,n,dk,dv", BIDIR_SHAPES)
def test_bidir_binary_attention_kernel_sweep(b, h, n, dk, dv):
    """Fused encoder kernel (interpret) and sign-trick XLA twin vs the
    quadratic oracle with causal=False — the ViT serving attention."""
    q = jax.random.normal(jax.random.PRNGKey(20), (b, h, n, dk))
    k = jax.random.normal(jax.random.PRNGKey(21), (b, h, n, dk))
    v = jax.random.normal(jax.random.PRNGKey(22), (b, h, n, dv))
    out_ref = ref.binary_linear_attention_ref(q, k, v, causal=False)
    _close(ops.binary_linear_attention_bidir(q, k, v, impl="interpret"),
           out_ref, tol=1e-3)
    _close(ops.binary_linear_attention_bidir(q, k, v, impl="xla"),
           out_ref, tol=1e-3)


def test_bidir_matches_core_bidirectional():
    """The serving op must agree with the training-path `_bidirectional`
    (STE einsums) — same Hamming kernel, different machinery."""
    from repro.core.add_attention import binary_linear_attention

    b, h, n, dk = 2, 2, 50, 24
    q = jax.random.normal(jax.random.PRNGKey(23), (b, h, n, dk))
    k = jax.random.normal(jax.random.PRNGKey(24), (b, h, n, dk))
    v = jax.random.normal(jax.random.PRNGKey(25), (b, h, n, dk))
    want = binary_linear_attention(q, k, v, causal=False, train=False)
    for impl in ("xla", "interpret"):
        _close(ops.binary_linear_attention_bidir(q, k, v, impl=impl), want,
               tol=1e-4)


PAD_SHAPES = [(197, 100, 60),      # DeiT token count: the shape that used to
              (197, 192, 197),     # trip the m % bm hard-assert
              (5, 7, 3), (130, 513, 129)]


@pytest.mark.parametrize("m,k,n", PAD_SHAPES)
def test_shift_matmul_pallas_self_pads(m, k, n):
    """The Pallas entry point itself must pad-and-slice: direct calls with
    tile-indivisible shapes (197-token ViT batches) match the oracle."""
    from repro.kernels import shift_matmul as _shiftmm

    w = jax.random.normal(jax.random.PRNGKey(0), (k, n)) * 0.05
    wp = quant.pack_from_dense(w)
    x = jax.random.normal(jax.random.PRNGKey(1), (m, k))
    out = _shiftmm.shift_matmul_pallas(x, wp, interpret=True)
    assert out.shape == (m, n)
    _close(out, ref.shift_matmul_ref(x, wp))


@pytest.mark.parametrize("g,m,k,n", [(1, 197, 64, 48), (2, 197, 100, 60),
                                     (1, 3, 5, 2)])
def test_add_matmul_pallas_self_pads(g, m, k, n):
    from repro.kernels import add_matmul as _addmm

    b = (jax.random.randint(jax.random.PRNGKey(2), (g, k, n), 0, 2, jnp.int8)
         * 2 - 1).astype(jnp.int8)
    x = jax.random.normal(jax.random.PRNGKey(3), (g, m, k))
    out = _addmm.add_matmul_pallas(x, b, interpret=True)
    assert out.shape == (g, m, n)
    _close(out, ref.add_matmul_ref(x, b))


@pytest.mark.parametrize("g,m,k,n", [(1, 197, 64, 48), (2, 33, 72, 60)])
def test_add_matmul_packed_pallas_self_pads(g, m, k, n):
    from repro.kernels import add_matmul_packed as _pk

    b = (jax.random.randint(jax.random.PRNGKey(4), (g, k, n), 0, 2, jnp.int8)
         * 2 - 1).astype(jnp.int8)
    x = jax.random.normal(jax.random.PRNGKey(5), (g, m, k))
    out = _pk.add_matmul_packed_pallas(x, _pk.pack_bits(b), interpret=True)
    assert out.shape == (g, m, n)
    _close(out, ref.add_matmul_ref(x, b))


@pytest.mark.parametrize("m,k,n", [(197, 100, 60), (197, 192, 197)])
def test_padded_vs_unpadded_parity(m, k, n):
    """Padding must be invisible: the wrapper's answer on an odd shape equals
    the answer computed on a manually pre-padded problem, sliced back."""
    w = jax.random.normal(jax.random.PRNGKey(6), (k, n)) * 0.05
    wp = quant.pack_from_dense(w)
    x = jax.random.normal(jax.random.PRNGKey(7), (m, k))
    out = ops.shift_matmul(x, wp, "interpret")
    x_pad = jnp.pad(x, ((0, 256 - m), (0, 512 - k)))
    wp_pad = jnp.pad(wp, ((0, 512 - k), (0, 256 - n)))
    out_pad = ops.shift_matmul(x_pad, wp_pad, "interpret")[:m, :n]
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_pad),
                               rtol=1e-5, atol=1e-5)


def test_linattn_kernel_state_locality():
    """Chunked kernel must equal the oracle even when the sequence spans many
    chunks (state carried in VMEM scratch across grid steps)."""
    b, h, n, d = 1, 2, 1024, 32
    q = jax.random.normal(jax.random.PRNGKey(7), (b, h, n, d))
    k = jax.random.normal(jax.random.PRNGKey(8), (b, h, n, d))
    v = jax.random.normal(jax.random.PRNGKey(9), (b, h, n, d))
    out_ref = ref.binary_linear_attention_ref(q, k, v, causal=True)
    out_pal = ops.binary_linear_attention_fused(q, k, v, chunk=128,
                                                impl="interpret")
    _close(out_pal, out_ref, tol=1e-3)
