"""Roofline and MFU arithmetic against numbers worked by hand from shapes."""

import pytest

from benchmark import peaks
from benchmark.families import gpt2 as family

XL = {"n_embd": 1600, "n_layer": 48, "n_head": 25, "n_positions": 1024, "vocab_size": 50257}
SMALL = {"n_embd": 768, "n_layer": 12, "n_head": 12, "n_positions": 1024, "vocab_size": 50257}
V5E = "TPU v5 lite"


def test_parameter_counts_are_the_published_ones():
    assert family.params_count(SMALL) == 124_439_808
    assert family.params_count(XL) == 1_557_611_200
    # 12 d^2 a layer and the tied head
    assert peaks.gpt2_matmul_params(SMALL) == 12 * 12 * 768 * 768 + 50257 * 768


def test_mfu_by_hand():
    # 118,500 tokens/s x 6 x 123.5 M = 87.8 TFLOP/s of 197
    n = 12 * 12 * 768 * 768 + 50257 * 768
    assert n == 123_532_032
    assert peaks.mfu(118_500.0, SMALL, 1, V5E) == pytest.approx(
        100 * 118_500 * 6 * n / 197e12
    )
    assert peaks.mfu(118_500.0, SMALL, 1, V5E) == pytest.approx(44.585, abs=0.01)
    # four chips at four times the rate read the same
    assert peaks.mfu(474_000.0, SMALL, 4, V5E) == pytest.approx(
        peaks.mfu(118_500.0, SMALL, 1, V5E)
    )


def test_decode_step_mfu_by_hand():
    # weights 2 x 1,557,611,200 = 3.115 GB; K and V of 24 rows at 150
    # tokens: 24 x 150 x 2 x 48 x 1600 x 2 = 1.106 GB; 4.221 GB / 819 GB/s
    step_bytes = family.decode_step_bytes(XL, 24, 150)
    assert step_bytes == pytest.approx(3_115_222_400 + 1_105_920_000)
    least = (3_115_222_400 + 1_105_920_000) / 819e9
    assert least == pytest.approx(5.154e-3, rel=1e-3)
    assert peaks.decode_step_mfu(0.262, step_bytes, V5E) == pytest.approx(100 * least / 0.262)
    assert peaks.decode_step_mfu(0.262, step_bytes, V5E) == pytest.approx(1.967, abs=0.01)


def test_flash_costs_by_hand():
    # [384, 1024, 64]: one product is 2 x 384 x 1024 x 1024 x 64 = 51.5 GFLOP,
    # the lower triangle half of it
    fwd = peaks.flash_fwd_cost(384, 1024, 64)
    assert fwd["flops"] == pytest.approx(2 * 51_539_607_552 * 0.5)
    assert fwd["bytes"] == 4 * 2 * 384 * 1024 * 64
    bwd = peaks.flash_bwd_cost(384, 1024, 64)
    assert bwd["flops"] == pytest.approx(2.5 * fwd["flops"])
    assert bwd["bytes"] == 2 * fwd["bytes"]


def test_roofline_share_says_which_bound_holds():
    fwd = peaks.flash_fwd_cost(384, 1024, 64)
    by_flops = fwd["flops"] / 197e12  # 0.2616 ms
    by_bytes = fwd["bytes"] / 819e9  # 0.2458 ms
    assert by_flops == pytest.approx(2.616e-4, rel=1e-3)
    assert by_bytes == pytest.approx(2.458e-4, rel=1e-3)
    got = peaks.roofline_share(fwd, 2.14e-3, V5E)
    assert got["bound"] == "compute"
    assert got["share"] == pytest.approx(100 * by_flops / 2.14e-3)
    # a kernel that took exactly its least time reads 100, never more
    assert peaks.roofline_share(fwd, by_flops, V5E)["share"] == pytest.approx(100.0)
    thin = {"flops": 1e9, "bytes": 8.19e9}
    assert peaks.roofline_share(thin, 0.02, V5E) == {"share": pytest.approx(50.0), "bound": "bandwidth"}


def test_an_unknown_device_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peak("TPU v9")
    with pytest.raises(KeyError):
        peaks.mfu(1.0, SMALL, 1, "cpu")
