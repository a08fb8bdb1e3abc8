"""Operation and byte counts against shapes worked out by hand."""

import pytest

from bench import model, ops

CFG = model.load("kws-int8")


def test_gemm_shapes_at_1024_streams():
    assert ops.gemm_shapes(CFG, 1024) == [
        ("gru0.w_i", 1024, 16, 144), ("gru0.w_h", 1024, 48, 144),
        ("gru1.w_i", 1024, 48, 144), ("gru1.w_h", 1024, 48, 144),
        ("fc", 1024, 48, 12)]


def test_gemm_ops_and_bytes():
    # 2 * 1024 * 16 * 144
    assert ops.gemm_ops(1024, 16, 144) == 4_718_592
    # 2 B per activation code, 1 B per weight, 4 B per int32 result
    assert ops.gemm_bytes(1024, 16, 144) == 32_768 + 2_304 + 589_824


def test_gemm_roofline_is_memory_bound_at_serving_width():
    p = ops.peaks("TPU v5 lite")
    t, bound = ops.gemm_roofline_s(1024, 48, 144, p["int8_ops_per_s"],
                                   p["hbm_bytes_per_s"])
    assert bound == "memory"
    assert t == pytest.approx((98_304 + 6_912 + 589_824) / 819e9)


def test_hop_ops():
    # MACs per hop: 16*144 + 3 * 48*144 + 48*12 = 23_616; the frontend
    # runs on the edge device and is not counted
    assert ops.hop_ops(CFG) == 2 * 23_616


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        ops.peaks("TPU v9 imaginary")
