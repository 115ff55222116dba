"""Analyzer self-tests (ISSUE 6): each pass reports exactly the planted
violations in tests/analysis_fixtures/ and NOTHING on the clean tree, plus
regression tests pinning the pre-existing violations this PR fixed (weak
where-branches in core.quant / core.losses, the engine's unconsumable image
donation, the linear-attention prefill ignoring its donated cache).
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import jaxpr_audit, kernel_contracts, lint
from repro.analysis.findings import Finding, split_allowlisted
from repro.analysis.jaxpr_audit import audit_closed_jaxpr, check_donation

FIXTURES = os.path.join(os.path.dirname(__file__), "analysis_fixtures")


def _rules(findings):
    return sorted(f.rule for f in findings)


# ---------------------------------------------------------------------------
# AST lint: planted fixtures
# ---------------------------------------------------------------------------

def test_lint_planted_violations_exactly():
    f = lint.lint_file(os.path.join(FIXTURES, "planted_lint.py"))
    # numpy_on_traced, item, float, rng-in-infer (param AND call), self-
    # mutation, missing donation — the waived LT004 must NOT appear.
    assert _rules(f) == ["LT001", "LT002", "LT002", "LT003", "LT003",
                         "LT004", "LT005"]


def test_lint_allow_comment_suppresses():
    f = lint.lint_file(os.path.join(FIXTURES, "planted_lint.py"))
    lt004 = [x for x in f if x.rule == "LT004"]
    assert len(lt004) == 1           # the un-waived one only
    assert "make_counted_step" not in lt004[0].message


def test_lint_clean_module_is_clean():
    assert lint.lint_file(os.path.join(FIXTURES, "clean_module.py")) == []


def test_lint_static_argnames_not_traced():
    src = (
        "import functools, jax\n"
        "@functools.partial(jax.jit, static_argnames=('n',))\n"
        "def f(x, n):\n"
        "    return x * int(n)\n"
    )
    assert lint.lint_source(src, "mod.py") == []


def test_lint_src_repro_is_clean():
    findings, n_files = lint.run()
    assert n_files > 50
    assert findings == [], [f.format() for f in findings]


# ---------------------------------------------------------------------------
# jaxpr audit: planted fixtures
# ---------------------------------------------------------------------------

def _fixture_jaxpr(fn, *args):
    return jax.make_jaxpr(fn)(*args)


def test_jaxpr_planted_callback():
    from analysis_fixtures import planted_jaxpr as p
    closed = _fixture_jaxpr(jax.jit(p.callback_under_jit),
                            jnp.zeros((4,), jnp.float32))
    assert "JX001" in _rules(audit_closed_jaxpr(closed, "fixture"))


def test_jaxpr_planted_weak_boundary():
    from analysis_fixtures import planted_jaxpr as p
    closed = _fixture_jaxpr(p.weak_boundary, jnp.zeros((4,), jnp.float32))
    assert "JX003" in _rules(audit_closed_jaxpr(closed, "fixture"))


def test_jaxpr_planted_rng_in_infer():
    from analysis_fixtures import planted_jaxpr as p
    closed = _fixture_jaxpr(p.rng_in_infer, jnp.zeros((4,), jnp.float32))
    rules = _rules(audit_closed_jaxpr(closed, "fixture"))
    assert "JX006" in rules
    # the same program is legal on a sampling path:
    sampling = audit_closed_jaxpr(closed, "fixture", deterministic=False)
    assert "JX006" not in _rules(sampling)


def test_jaxpr_planted_float_scatter_add():
    from analysis_fixtures import planted_jaxpr as p
    closed = _fixture_jaxpr(p.float_scatter_add, jnp.zeros((4,), jnp.float32))
    assert "JX007" in _rules(audit_closed_jaxpr(closed, "fixture"))
    # integer scatter-adds are deterministic and must pass:
    closed_int = _fixture_jaxpr(p.float_scatter_add,
                                jnp.zeros((4,), jnp.int32))
    assert "JX007" not in _rules(audit_closed_jaxpr(closed_int, "fixture"))


def test_jaxpr_planted_f64():
    from analysis_fixtures import planted_jaxpr as p
    with jax.enable_x64(True):
        closed = _fixture_jaxpr(p.f64_promotion, jnp.zeros((4,), jnp.float32))
    assert "JX002" in _rules(audit_closed_jaxpr(closed, "fixture"))


def test_jaxpr_dtype_signature_drift_detected():
    def bucket_small(x):
        return x * 2.0

    def bucket_big(x):          # shape-dependent dtype: the recompile hazard
        return (x.astype(jnp.bfloat16) * 2).astype(jnp.float32)

    sig_a = jaxpr_audit.dtype_signature(
        jax.make_jaxpr(bucket_small)(jnp.zeros((1, 4), jnp.float32)))
    sig_b = jaxpr_audit.dtype_signature(
        jax.make_jaxpr(bucket_big)(jnp.zeros((32, 4), jnp.float32)))
    assert sig_a != sig_b
    # and batch-only variation is signature-identical:
    sig_c = jaxpr_audit.dtype_signature(
        jax.make_jaxpr(bucket_small)(jnp.zeros((32, 4), jnp.float32)))
    assert sig_a == sig_c


def test_donation_unconsumed_flagged_and_consumed_passes():
    def no_alias(x):             # (4,) in → (2,) out: nothing to alias
        return x[:2]

    f = check_donation(no_alias, (0,),
                       (jax.ShapeDtypeStruct((4,), jnp.float32),), "fx")
    assert _rules(f) == ["JX005"]

    def in_place(x):             # same shape/dtype: donation consumable
        return x * 2.0

    assert check_donation(in_place, (0,),
                          (jax.ShapeDtypeStruct((4,), jnp.float32),),
                          "fx") == []


# ---------------------------------------------------------------------------
# kernel contracts: coverage + planted geometries
# ---------------------------------------------------------------------------

def test_contract_table_covers_every_kernel_and_bucket():
    from repro.serve.vision import DEFAULT_BUCKETS
    _, rows = kernel_contracts.run()
    covered = {(c.kernel, c.bucket) for c in rows}
    for name in kernel_contracts.pallas_kernel_names():
        for b in DEFAULT_BUCKETS:
            assert (name, b) in covered, (name, b)
    assert {c.classification for c in rows} <= {
        "tile_aligned", "pad_and_slice", "vmem_overflow"}


def test_contract_table_clean_at_serving_geometry():
    findings, rows = kernel_contracts.run()
    assert findings == [], [f.format() for f in findings]
    # CIFAR-scale geometry rides the pad-and-slice path (K 128 → 512 pad on
    # the matmuls, head-dim 32 → 128 lane pad on the attention kernels):
    assert all(c.classification == "pad_and_slice" for c in rows)
    qkvo = next(c for c in rows if c.site == "qkvo_proj" and c.bucket == 8)
    assert qkvo.padded["k"] == 512 and qkvo.geometry["k"] == 128
    assert qkvo.pad_mac_waste == pytest.approx(0.75)


def test_planted_misaligned_tile_geometry():
    # DeiT's 197-token sequence: M=197 → bm=128 cover pads M to 256.
    cell = kernel_contracts.matmul_cell(
        "shift_matmul", "deit_tokens", 1, 1, 197, 512, 512,
        w_bytes=1, adapt_bn=False)
    assert cell.classification == "pad_and_slice"
    assert cell.padded["m"] == 256 and cell.pad_mac_waste > 0.2


def test_planted_vmem_overflow_geometry():
    # A sequence past MAX_FUSED_N cannot keep q/k/v/out resident: the fused
    # bidirectional kernel must be classified vmem_overflow, and run() must
    # surface it as a KC001 finding.
    cell = kernel_contracts.bidir_attention_cell(1, 4, 8192, 128, 128)
    assert cell.classification == "vmem_overflow"

    from repro.nn.vit import ViTConfig
    big = ViTConfig(image_size=512, patch_size=2)    # 65536 patches
    findings, _ = kernel_contracts.run(base_cfg=big, buckets=(1,))
    assert "KC001" in _rules(findings)


def test_tile_aligned_geometry_exists():
    # A fully tile-shaped problem must classify clean — the autotune layer's
    # target state.
    cell = kernel_contracts.matmul_cell(
        "shift_matmul", "aligned", 1, 1, 256, 512, 256,
        w_bytes=1, adapt_bn=False)
    assert cell.classification == "tile_aligned"
    assert cell.pad_mac_waste == 0.0


# ---------------------------------------------------------------------------
# clean tree end-to-end + allowlist
# ---------------------------------------------------------------------------

def test_allowlist_partitions():
    f1 = Finding("JX005", "vit/x/donation", "m", "jaxpr")
    f2 = Finding("LT004", "serve/vision.py:1", "m", "lint")
    active, waived = split_allowlisted(
        [f1, f2], allowlist=(("JX005", "vit/", "reason"),))
    assert active == [f2] and waived == [f1]


@pytest.mark.slow
def test_cli_clean_tree_passes(tmp_path):
    from repro.analysis import check
    rc = check.main(["--fail-on-findings",
                     "--table", str(tmp_path / "contracts.json")])
    assert rc == 0
    assert (tmp_path / "contracts.json").exists()


# ---------------------------------------------------------------------------
# regressions for the violations this PR fixed
# ---------------------------------------------------------------------------

def test_regression_quant_weak_types():
    from repro.core.quant import binarize, po2_quantize
    x = jnp.zeros((4, 4), jnp.float32)
    b, scale = jax.eval_shape(binarize, x)
    sign, p = jax.eval_shape(po2_quantize, x)
    assert not b.weak_type and not scale.weak_type
    assert not sign.weak_type
    closed = jax.make_jaxpr(lambda v: jax.jit(binarize)(v)[0])(x)
    assert audit_closed_jaxpr(closed, "quant.binarize") == []


def test_regression_losses_weak_types():
    from repro.core.losses import smooth_top1_prob
    logits = jnp.zeros((2, 8, 4), jnp.float32)
    out = jax.eval_shape(smooth_top1_prob, logits)
    assert not out.weak_type
    closed = jax.make_jaxpr(lambda v: jax.jit(smooth_top1_prob)(v))(logits)
    assert audit_closed_jaxpr(closed, "losses.smooth_top1_prob") == []


def test_regression_engine_never_donates_images():
    from repro.nn.vit import ShiftAddViT, ViTConfig
    cfg = ViTConfig(n_layers=1, d_model=32, n_heads=2, d_ff=64)
    model = ShiftAddViT(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.serve.vision import BucketedViTEngine
    engine = BucketedViTEngine(model, params, buckets=(2,), freeze=True)
    # The fixed contract: no declared donation on the image buffer...
    assert engine.donate_argnums == ()
    # ...and the analyzer WOULD catch the removed hazard (images can never
    # alias logits, so a donation there is dead weight):
    spec = jax.ShapeDtypeStruct(
        (2, cfg.image_size, cfg.image_size, cfg.in_channels), jnp.float32)
    f = check_donation(engine._fwd, (0,), (spec,), "vit/regression")
    assert _rules(f) == ["JX005"]


@pytest.mark.parametrize("policy_name", ["dense", "stage1"])
def test_regression_lm_prefill_consumes_donated_cache(policy_name):
    # Pre-fix, the stage1 (linear-attention) prefill rebuilt the recurrent
    # carry from scratch and the donated cache aliased NOTHING; the additive
    # carry fix makes prefill accumulate into the donated buffers.
    from repro.core.policy import STAGE1
    from repro.serve.decode import make_prefill
    model = jaxpr_audit._tiny_lm(None if policy_name == "dense" else STAGE1)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(2, max_len=21))
    prompts = jax.ShapeDtypeStruct((2, 13), jnp.int32)
    assert check_donation(make_prefill(model), (2,),
                          (params, prompts, cache),
                          f"lm/{policy_name}/prefill") == []


def test_regression_vit_serving_audit_clean():
    # The full ViT sweep audit (every policy × bucket, frozen + live) must
    # stay clean — this is where the quant weak-type fix is load-bearing
    # (the live arm runs the per-call po2 decode through core.quant).
    findings, audited = jaxpr_audit.audit_vit_serving()
    assert findings == [], [f.format() for f in findings]
    names = {a.where for a in audited}
    from repro.serve.vision import DEFAULT_BUCKETS, SWEEP_POLICIES
    for pol in SWEEP_POLICIES:
        for b in DEFAULT_BUCKETS:
            assert f"vit/{pol}/frozen/bucket={b}" in names


def test_regression_elastic_warm_pool_audit_clean_and_exact():
    # The elastic audit must cover EXACTLY the surface the zero-recompile
    # invariant counts: every reserve engine (parked spares included) ×
    # every bucket, on both the dense primary and the shiftadd degrade arm
    # — and every reserve engine must be a drop-in replica of engine 0
    # (JX008), or warm-pool replacement would break bit-identical replay.
    findings, audited = jaxpr_audit.audit_elastic_serving(
        max_replicas=2, spares=1)
    assert findings == [], [f.format() for f in findings]
    from repro.serve.vision import DEFAULT_BUCKETS
    names = {a.where for a in audited}
    expected = {f"elastic/primary/engine={e}/bucket={b}"
                for e in range(3) for b in DEFAULT_BUCKETS}
    expected |= {f"elastic/degrade/engine=0/bucket={b}"
                 for b in DEFAULT_BUCKETS}
    assert names == expected
    assert len(audited) == len(expected)        # counts exact, no dupes
    # Engines of one arm trace byte-for-byte comparable programs: the
    # inventory's equation counts must agree per (arm, bucket).
    by_key = {}
    for a in audited:
        arm, _, bucket = a.where.split("/")[1:]
        by_key.setdefault((arm, bucket), set()).add(a.n_eqns)
    assert all(len(v) == 1 for v in by_key.values()), by_key
