"""Work counts against hand counts at small shapes."""
from __future__ import annotations

import pytest

from bench_tiny import BENCH  # noqa: F401
from harness import counts, peaks

M = {"d": 8, "f": 16, "h": 4, "kv": 2, "hd": 2, "v": 32, "layers": 3}


def test_layer_params_by_hand():
    # q 8x8, k and v 8x4 each, o 8x8, gate/up/down 8x16 each
    assert counts.layer_matmul_params(8, 16, 4, 2, 2) == (
        64 + 2 * 32 + 64 + 3 * 128)


def test_decode_token_flops_by_hand():
    ctx = 5
    per_layer = 2 * 576 + 4 * 4 * 2 * ctx  # matmuls, then QK and PV
    assert counts.decode_token_flops(M, ctx) == 3 * per_layer + 2 * 32 * 8


def test_flash_decode_work_by_hand():
    flops, bytes_ = counts.flash_decode_work(M, ctx_sum=10, tokens=2)
    assert flops == 3 * 4 * 4 * 2 * 10
    kv_bytes = 10 * 2 * 2 * 2 * 2  # positions x (K,V) x kv heads x hd x bf16
    q_out = 2 * 4 * 2 * (2 + 4)  # tokens x heads x hd x (bf16 q + f32 out)
    assert bytes_ == 3 * (kv_bytes + q_out)


def test_loglinear_nominal_work():
    assert counts.loglinear_theta_flops(
        1_281_167, 256, 1131, 16, 3456, 3456) == pytest.approx(13.4e6,
                                                               rel=0.01)


def test_least_time_is_the_larger_bound():
    row = peaks.peaks("TPU v5 lite")
    assert peaks.least_time_s(197e12, 0, row) == pytest.approx(1.0)
    assert peaks.least_time_s(0, 819e9, row) == pytest.approx(1.0)
    with pytest.raises(ValueError):
        peaks.peaks("TPU v9 imaginary")
