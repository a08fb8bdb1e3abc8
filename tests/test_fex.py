"""Software-model FEx: shapes, stage invariants (hypothesis), ablation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.core import quant
from repro.core.fex import (
    FExConfig,
    FExNormStats,
    biquad_filterbank,
    fex_forward,
    fex_frames,
    fit_norm_stats,
    frame_average,
    full_wave_rectify,
    oversample2x,
)

CFG = FExConfig()


def test_frame_math():
    assert CFG.fs_internal == 32000.0
    assert CFG.frame_len == 512  # 16 ms @ 32 kHz


def test_oversample2x_shape_and_interp():
    x = jnp.asarray([[0.0, 1.0, 0.0, -1.0]])
    y = oversample2x(x)
    assert y.shape == (1, 8)
    np.testing.assert_allclose(y[0, :4], [0.0, 0.5, 1.0, 0.5], atol=1e-6)


def test_fex_frames_shape():
    audio = jnp.zeros((3, 16000))
    fr = fex_frames(audio, CFG)
    assert fr.shape == (3, 62, 16)  # 1 s -> 62 full 16 ms frames


def test_sine_selects_matching_channel():
    coeffs = CFG.filterbank()
    f0 = np.asarray(coeffs.f0)
    t = np.arange(16000) / 16000.0
    audio = jnp.asarray(0.2 * np.sin(2 * np.pi * f0[5] * t), jnp.float32)
    fr = np.asarray(fex_frames(audio[None], CFG))[0, 10:]  # settled
    assert fr.mean(0).argmax() == 5


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2**32 - 1))
def test_rectified_frames_nonnegative(seed):
    rng = np.random.default_rng(seed)
    audio = jnp.asarray(
        rng.standard_normal((1, 2048)).astype(np.float32) * 0.3
    )
    fr = fex_frames(audio, CFG)
    assert bool((fr >= 0).all())


@settings(max_examples=20, deadline=None)
@given(st.floats(0.01, 0.5))
def test_frame_average_bounded_by_peak(amp):
    audio = jnp.full((1, 2048), float(amp), jnp.float32)
    y = biquad_filterbank(oversample2x(audio), CFG.filterbank())
    fr = frame_average(full_wave_rectify(y), CFG.frame_len)
    assert float(fr.max()) <= float(jnp.abs(y).max()) + 1e-6


def test_quantizer_monotone_and_range():
    x = jnp.linspace(0, 1.0, 100)
    q = quant.quantize_unsigned(x, 12, 0.7)
    assert bool((jnp.diff(q) >= 0).all())
    assert float(q.min()) == 0.0 and float(q.max()) == 4095.0


@pytest.mark.parametrize("frame,code", [(0.04452992, 260.0), (0.011025642, 64.0)])
def test_quantizer_divides_by_the_full_scale(frame, code):
    """Frames whose x / 0.7 * 4095 is a float32 tie (260.5, 64.5) round to
    the even code; a multiplication by the reciprocal of 0.7, which XLA
    makes of a division by a constant, rounded them up under jit."""
    x = np.float32(frame)
    assert np.float32(x / np.float32(0.7)) * np.float32(4095) == code + 0.5
    for fn in (quant.quantize_unsigned,
               jax.jit(quant.quantize_unsigned, static_argnums=(1, 2))):
        assert float(fn(jnp.full((4,), x), 12, 0.7)[0]) == code


def test_log_compress_monotone_10bit():
    codes = jnp.arange(4096.0)
    out = quant.log_compress_lut(codes, 12, 10)
    assert bool((jnp.diff(out) >= 0).all())
    assert float(out.min()) == 0.0 and float(out.max()) == 1023.0
    lut = quant.make_log_lut(12, 10)
    np.testing.assert_allclose(out, lut, atol=0)


# The 12 -> 10-bit ROM in float64. Codes 3, 63 and 1023 sit on ties
# (1023 * log2(1 + v) / 12 = 170.5, 511.5 and 852.5), which a float32
# log2 a hair off rounds the other way: under jit code 63 came out 511.
_CODES = np.arange(4096.0)
_TABLE = np.round(1023.0 * np.log2(1.0 + _CODES) / 12.0)


def _ste_closed_form(v):
    """The log compression as it was evaluated before the ROM read: its
    gradient is the one QAT keeps."""
    x = jnp.clip(v, 0.0, 4095.0)
    return quant.ste_round(1023.0 * jnp.log2(1.0 + x) / 12.0)


@pytest.mark.parametrize(
    "case", ["eager", "jit", "step_count", "make_log_lut", "gradient"])
def test_log_rom_is_the_float64_table(case):
    assert _TABLE[[3, 63, 1023]].tolist() == [170.0, 512.0, 852.0]
    codes = jnp.asarray(_CODES, jnp.float32)
    if case == "eager":
        got = quant.log_compress_lut(codes, 12, 10)
    elif case == "jit":
        got = jax.jit(quant.log_compress_lut, static_argnums=(1, 2))(
            codes, 12, 10)
    elif case == "step_count":  # the form accelerators run, on the CPU
        got = jax.jit(lambda c: quant._count_steps(
            c, *quant._log_steps(12, 10)))(
            jnp.arange(-70, 4096 + 70, dtype=jnp.int32))
        want = _TABLE[np.clip(np.arange(-70, 4096 + 70), 0, 4095)]
        np.testing.assert_array_equal(np.asarray(got), want)
        return
    elif case == "make_log_lut":
        got = quant.make_log_lut(12, 10)
    else:
        spread = jnp.asarray([-3.0, 0.0, 1.0, 3.0, 17.0, 63.0, 64.0, 512.0,
                              1023.0, 2047.5, 4095.0, 4100.0])
        got = jax.vmap(jax.grad(
            lambda v: quant.log_compress_lut(v, 12, 10)))(spread)
        want = jax.vmap(jax.grad(_ste_closed_form))(spread)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
        assert float(got[2]) > 0.0
        return
    assert got.dtype == np.float32
    np.testing.assert_array_equal(np.asarray(got), _TABLE)


def test_ablation_paths_differ_and_norm_is_zero_mean():
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    frames = fex_frames(audio, CFG)
    fv_raw = quant.quantize_unsigned(frames, 12, CFG.quant_full_scale)
    fv_log = quant.log_compress_lut(fv_raw, 12, 10)
    stats = fit_norm_stats(fv_log)
    base, _ = fex_forward(audio, CFG, use_log=False, use_norm=False)
    logd, _ = fex_forward(audio, CFG, use_log=True, use_norm=False)
    norm, _ = fex_forward(
        audio, CFG, norm_stats=stats, use_log=True, use_norm=True
    )
    assert not np.allclose(base, logd)
    assert not np.allclose(logd, norm)
    # normalized features ~zero mean unit-ish variance per channel
    m = np.asarray(norm).reshape(-1, 16).mean(0)
    assert np.abs(m).max() < 0.5
    # all within the Q6.8 representable range
    assert float(jnp.abs(norm).max()) <= quant.ACT_Q6_8.max_value


def test_fex_is_differentiable():
    audio = jnp.ones((1, 4096)) * 0.1
    stats = FExNormStats(mu=jnp.full((16,), 100.0), sigma=jnp.full((16,), 50.0))

    def loss(a):
        fv, _ = fex_forward(a, CFG, stats)
        return jnp.sum(fv**2)

    g = jax.grad(loss)(audio)
    assert bool(jnp.isfinite(g).all())
    assert float(jnp.abs(g).max()) > 0
