"""Multi-device behavior (8 simulated host devices in a subprocess):
sharded train step, compressed psum via shard_map, logical sharding rules."""
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.distributed import sharding as sl
from repro.distributed.sharding import LOGICAL_AXIS_RULES, logical_to_pspec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run_subprocess(code):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_logical_rules_cover_required_axes():
    for name in ("batch", "embed", "vocab", "heads", "mlp", "experts"):
        assert name in LOGICAL_AXIS_RULES


def test_pspec_divisibility_fallback():
    # AbstractMesh carries shape/axis_names without requiring real devices.
    mesh = jax.sharding.AbstractMesh((2, 4), ("data", "model"))
    # indivisible dims fall back to replication
    spec = logical_to_pspec(("batch", "vocab"), mesh, (3, 5))
    assert all(s is None for s in spec) or len(spec) == 0
    # divisible dims shard
    spec = logical_to_pspec(("batch", "vocab"), mesh, (4, 8))
    assert spec[0] == ("data",) or spec[0] == "data"
    assert spec[1] == ("model",) or spec[1] == "model"
    # a mesh axis is used at most once across dims
    spec = logical_to_pspec(("vocab", "mlp"), mesh, (8, 8))
    flat = [a for s in spec if s is not None
            for a in (s if isinstance(s, tuple) else (s,))]
    assert len(flat) == len(set(flat))


def test_sharded_train_step_runs_on_mesh():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ModelConfig, TrainConfig
        from repro.nn.model import LanguageModel
        from repro.train.step import init_train_state, make_train_step
        from repro.distributed import sharding as sl
        from repro.launch.dryrun import state_shardings, batch_shardings

        mesh = sl.make_mesh((2, 4), ("data", "model"))
        sl.set_active_mesh(mesh)
        cfg = ModelConfig(name="t", family="dense", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=64,
                          dtype="float32", scan_layers=True, remat="none")
        tcfg = TrainConfig(learning_rate=1e-3, total_steps=4, global_batch=8,
                           seq_len=16, microbatch=2)
        model = LanguageModel(cfg)
        with mesh:
            state = init_train_state(model, tcfg, jax.random.PRNGKey(0))
            shapes = jax.eval_shape(lambda: state)
            pshard = sl.shardings_from_spec(
                model.spec(shapes["params"]), shapes["params"], mesh)
            st = state_shardings(shapes, pshard, mesh)
            state = jax.tree_util.tree_map(jax.device_put, state, st)
            step = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
            import numpy as np
            batch = {"inputs": np.zeros((8, 16), np.int32),
                     "labels": np.ones((8, 16), np.int32)}
            for _ in range(3):
                state, metrics = step(state, batch)
            loss = float(metrics["loss"])
            assert np.isfinite(loss)
            print("LOSS", loss)
    """)
    assert "LOSS" in out


def test_compressed_psum_matches_plain_psum():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed import sharding as sl
        from repro.distributed.collectives import compressed_psum

        mesh = sl.make_mesh((8,), ("pod",))

        def f(x):
            reduced, residual = compressed_psum(x, "pod")
            exact = jax.lax.psum(x, "pod")
            return reduced, exact, residual

        x = jax.random.normal(jax.random.PRNGKey(0), (8, 64))
        r, e, res = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=P("pod"),
                                          out_specs=P("pod")))(x)
        rel = float(jnp.max(jnp.abs(r - e)) / (jnp.max(jnp.abs(e)) + 1e-9))
        # int8 quantization: ~1% relative error on the reduction
        assert rel < 0.05, rel
        # error feedback residual equals the local quantization error
        assert float(jnp.max(jnp.abs(res))) < float(jnp.max(jnp.abs(x))) / 64
        print("REL", rel)
    """)
    assert "REL" in out


def test_moe_dispatch_shards_over_groups():
    out = _run_subprocess("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs.base import ModelConfig, MoEConfig
        from repro.nn.moe import TokenChoiceMoE
        from repro.distributed import sharding as sl

        mesh = sl.make_mesh((2, 4), ("data", "model"))
        sl.set_active_mesh(mesh)
        cfg = ModelConfig(name="t", family="moe", n_layers=1, d_model=64,
                          n_heads=4, n_kv_heads=4, d_ff=64, vocab_size=64,
                          dtype="float32",
                          moe=MoEConfig(n_experts=8, top_k=2, d_expert=64))
        moe = TokenChoiceMoE(cfg)
        params = moe.init(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 64, 64))
        with mesh:
            xs = jax.device_put(x, NamedSharding(mesh, P("data", None, None)))
            y, aux = jax.jit(lambda p, x: moe(p, x, train=False))(params, xs)
        assert y.shape == x.shape
        assert np.isfinite(np.asarray(y)).all()
        print("MOE-OK", float(aux["drop_fraction"]))
    """)
    assert "MOE-OK" in out
