"""The trip-count-corrected HLO analyzer must agree with unrolled ground truth
(this is the §Roofline 'profiler'; XLA's own cost_analysis counts loop bodies
once — verified here so the methodology stays honest)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch import hlo_analysis as H


def _flops(fn, *args):
    comp = jax.jit(fn).lower(*args).compile()
    cost = comp.cost_analysis()
    return H.analyze(comp.as_text()), cost


def test_scan_flops_match_unrolled():
    d = 64
    x = jax.ShapeDtypeStruct((8, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((6, d, d), jnp.float32)

    def unrolled(x, ws):
        for i in range(6):
            x = jnp.tanh(x @ ws[i])
        return x

    def scanned(x, ws):
        return jax.lax.scan(lambda x, w: (jnp.tanh(x @ w), None), x, ws)[0]

    cu, _ = _flops(unrolled, x, ws)
    cs, xla = _flops(scanned, x, ws)
    analytic = 2 * 8 * d * d * 6
    assert cu.flops == pytest.approx(analytic, rel=0.01)
    assert cs.flops == pytest.approx(analytic, rel=0.01)
    # and XLA undercounts the scanned one (the reason this module exists)
    assert xla["flops"] < analytic * 0.5


def test_nested_scan_trip_multiplication():
    d = 32
    x = jax.ShapeDtypeStruct((4, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((3, d, d), jnp.float32)

    def nested(x, ws):
        def outer(x, w):
            def inner(x, _):
                return x @ w, None
            return jax.lax.scan(inner, x, jnp.arange(5))[0], None
        return jax.lax.scan(outer, x, ws)[0]

    c, _ = _flops(nested, x, ws)
    assert c.flops == pytest.approx(2 * 4 * d * d * 3 * 5, rel=0.01)


def test_collective_bytes_parsed(tmp_path):
    import os
    import subprocess
    import sys
    import textwrap

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.distributed import sharding as sl
        from repro.launch import hlo_analysis as H
        mesh = sl.make_mesh((4,), ("model",))
        def f(x, w):
            return x @ w                       # contraction over sharded dim
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32)
        w = jax.ShapeDtypeStruct((64, 16), jnp.float32)
        with mesh:
            comp = jax.jit(f, in_shardings=(
                NamedSharding(mesh, P(None, "model")),
                NamedSharding(mesh, P("model", None)))).lower(x, w).compile()
        c = H.analyze(comp.as_text())
        assert c.collective_bytes > 0, "expected an all-reduce"
        assert "all-reduce" in c.collective_breakdown
        print("COLL", c.collective_bytes)
    """)
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    assert "COLL" in out.stdout


def test_dot_flops_from_shapes():
    txt = """
HloModule m
ENTRY %main.1 (p0: f32[8,32], p1: f32[32,16]) -> f32[8,16] {
  %p0 = f32[8,32]{1,0} parameter(0)
  %p1 = f32[32,16]{1,0} parameter(1)
  ROOT %dot.1 = f32[8,16]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}
"""
    c = H.analyze(txt)
    assert c.flops == 2 * 8 * 32 * 16
