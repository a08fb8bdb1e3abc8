"""Roofline HLO analyzer: trip-count scaling, collectives, window rules.

Also documents WHY the analyzer exists: cost_analysis counts while
bodies once (demonstrated below).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.roofline import (
    HARDWARE,
    _shape_numel_bytes,
    analyze_hlo,
)


def _compile_scan(n_steps=5, dim=64):
    def f(w, x):
        def body(x, wi):
            return jnp.tanh(x @ wi), None

        x, _ = jax.lax.scan(body, x, w)
        return x.sum()

    w = jax.ShapeDtypeStruct((n_steps, dim, dim), jnp.float32)
    x = jax.ShapeDtypeStruct((8, dim), jnp.float32)
    return jax.jit(f).lower(w, x).compile()


def test_cost_analysis_undercounts_scan_and_we_dont():
    n, dim = 5, 64
    compiled = _compile_scan(n, dim)
    per_step = 2 * 8 * dim * dim
    from repro.launch.roofline import cost_analysis_dict

    ca_flops = cost_analysis_dict(compiled).get("flops", 0)
    assert ca_flops < 2 * per_step  # body counted ~once
    ours = analyze_hlo(compiled.as_text()).flops
    assert abs(ours - n * per_step) / (n * per_step) < 0.01


def test_trip_count_scales_with_length():
    f5 = analyze_hlo(_compile_scan(5).as_text()).flops
    f10 = analyze_hlo(_compile_scan(10).as_text()).flops
    assert abs(f10 / f5 - 2.0) < 0.05


def test_shape_parsing():
    assert _shape_numel_bytes("bf16[8,64]{1,0}") == (512, 1024)
    assert _shape_numel_bytes("f32[2,3]") == (6, 24)
    n, b = _shape_numel_bytes("(s32[], f32[4]{0})")
    assert n == 5 and b == 20
    assert _shape_numel_bytes("pred[10]")[1] == 10


def test_collective_ring_model():
    hlo = """
HloModule test, is_scheduled=true

ENTRY %main (p: f32[1024]) -> f32[1024] {
  %p = f32[1024]{0} parameter(0)
  ROOT %ar = f32[1024]{0} all-reduce(%p), replica_groups=[2,4]<=[8], to_apply=%add
}
"""
    a = analyze_hlo(hlo)
    # all-reduce of 4096 B over groups of 4: 2*B*(n-1)/n = 6144
    assert abs(a.wire_bytes - 2 * 4096 * 3 / 4) < 1
    assert "all-reduce" in a.collective_breakdown


def test_dynamic_slice_window_not_full_operand():
    hlo = """
HloModule test, is_scheduled=true

ENTRY %main (p: f32[100,64]) -> f32[1,64] {
  %p = f32[100,64]{1,0} parameter(0)
  %c = s32[] constant(3)
  ROOT %ds = f32[1,64]{1,0} dynamic-slice(%p, %c, %c), dynamic_slice_sizes={1,64}
}
"""
    a = analyze_hlo(hlo)
    assert a.hbm_bytes == 2 * 64 * 4  # window, not 100x64


def test_hardware_constants_match_spec():
    assert HARDWARE.peak_flops == 197e12
    assert HARDWARE.hbm_bw == 819e9
    assert HARDWARE.ici_bw == 50e9


def test_peak_table_is_keyed_by_device_kind():
    from repro.launch.roofline import PEAKS, peaks_for

    assert peaks_for("TPU v5 lite") is HARDWARE
    assert set(PEAKS) == {"TPU v5 lite"}
    with pytest.raises(KeyError, match="no peak table entry"):
        peaks_for("cpu")
