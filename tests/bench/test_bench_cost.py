"""Operations and bytes against counts made by hand."""
import json

import pytest

from bench.lib import cost, spec
from bench_fixtures import REPO

VIT = spec.load_family(REPO, {"family": "vit"})


def config(arm):
    return json.loads((REPO / f"bench/configs/deit-tiny-{arm}.json").read_text())


def test_dense_deit_tiny_ops_per_image():
    # 196 patches; patch embedding 196*768*192 MACs; per layer q,k,v,o
    # 4*196*192*192, q k^T and p v 2*3*196*196*64, MLP 2*196*192*768; head
    # 192*1000. Two operations per MAC.
    macs = (196 * 768 * 192
            + 12 * (4 * 196 * 192 * 192 + 2 * 3 * 196 * 196 * 64
                    + 2 * 196 * 192 * 768)
            + 192 * 1000)
    assert VIT.cost.ops_per_image(config("dense")) == pytest.approx(2 * macs)
    assert 2.48e9 < 2 * macs < 2.50e9


def test_shiftadd_deit_tiny_ops_per_image():
    # Attention is k^T v and q (k^T v): 2*3*196*64*64 MACs; the V-branch
    # convolution 196*192*3 and the router 196*192*2; each token through
    # one expert: 2*196*192*768.
    macs = (196 * 768 * 192
            + 12 * (4 * 196 * 192 * 192 + 2 * 3 * 196 * 64 * 64
                    + 196 * 192 * 3 + 196 * 192 * 2 + 2 * 196 * 192 * 768)
            + 192 * 1000)
    assert VIT.cost.ops_per_image(config("shiftadd")) == pytest.approx(2 * macs)
    assert 2.24e9 < 2 * macs < 2.27e9


def test_shift_matmul_cost_by_hand():
    ops, nbytes = cost.shift_matmul_cost(6272, 192, 768)
    assert ops == 2 * 6272 * 192 * 768
    assert nbytes == 4 * 6272 * 192 + 192 * 768 + 4 * 6272 * 768


def test_bidir_attention_cost_by_hand():
    ops, nbytes = cost.bidir_attn_cost(96, 196, 64, 64)
    assert ops == 96 * (4 * 196 * 64 * 64 + 2 * 196 * 64 + 2 * 196 * 64)
    assert nbytes == 4 * 96 * 196 * 256


def test_calls_of_one_shiftadd_forward():
    cfg = config("shiftadd")
    calls = VIT.cost.kernel_calls(cfg, 32)
    shift = calls["shift_matmul"]
    assert len(shift) == 12 * 6
    assert shift[:6] == [(6272, 192, 192)] * 4 + [(32 * 138, 192, 768),
                                                  (32 * 138, 768, 192)]
    assert calls["bidir_attn"] == [(96, 196, 64, 64)] * 12


def test_roofline_is_the_larger_bound():
    assert cost.roofline_s(197e12, 1.0, 197e12, 819e9) == pytest.approx(1.0)
    assert cost.roofline_s(1.0, 819e9, 197e12, 819e9) == pytest.approx(1.0)
