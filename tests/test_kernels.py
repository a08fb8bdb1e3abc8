"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.fex import FExConfig
from repro.core.filters import design_filterbank
from repro.core.tdfex import TDFExConfig, draw_chip
from repro.kernels.fex_fused import fex_fused, fex_fused_ref
from repro.kernels.gru import gru_sequence, gru_sequence_ref
from repro.kernels.intgemm import intgemm, intgemm_ref
from repro.kernels.tdc import tdc_counts, tdc_counts_ref

RNG = np.random.default_rng(42)


# ---------------- fex_fused ----------------

@pytest.mark.parametrize("batch,t,channels,frame", [
    (1, 1024, 16, 512),
    (3, 2048, 16, 512),
    (8, 1536, 8, 256),
    (5, 4096, 4, 128),
])
def test_fex_fused_sweep(batch, t, channels, frame):
    coeffs = design_filterbank(channels, 32000.0)
    x = jnp.asarray(RNG.standard_normal((batch, t)).astype(np.float32) * 0.2)
    out = fex_fused(x, coeffs, frame)
    ref = fex_fused_ref(x, coeffs, frame)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=2e-5, atol=1e-6
    )


def test_fex_fused_trims_partial_frames():
    coeffs = design_filterbank(16, 32000.0)
    x = jnp.zeros((2, 1000), jnp.float32)
    assert fex_fused(x, coeffs, 512).shape == (2, 1, 16)


def test_fex_fused_state_carries_across_frames():
    """An impulse in frame 0 must ring into frame 1 (IIR state carry)."""
    coeffs = design_filterbank(16, 32000.0)
    x = np.zeros((1, 1024), np.float32)
    x[0, 500] = 1.0  # near the end of frame 0
    out = np.asarray(fex_fused(jnp.asarray(x), coeffs, 512))
    assert out[0, 1].max() > 1e-4  # ringing continues into frame 1


# ---------------- gru ----------------

@pytest.mark.parametrize("b,t,i,h", [
    (1, 5, 16, 48),
    (4, 20, 16, 48),
    (9, 7, 32, 64),
    (2, 62, 16, 48),  # the paper's frame count
])
def test_gru_sequence_sweep(b, t, i, h):
    xs = jnp.asarray(RNG.standard_normal((b, t, i)).astype(np.float32))
    w = jnp.asarray(RNG.standard_normal((i, 3 * h)).astype(np.float32) * 0.2)
    u = jnp.asarray(RNG.standard_normal((h, 3 * h)).astype(np.float32) * 0.2)
    bi = jnp.asarray(RNG.standard_normal(3 * h).astype(np.float32) * 0.1)
    bh = jnp.asarray(RNG.standard_normal(3 * h).astype(np.float32) * 0.1)
    out = gru_sequence(xs, w, u, bi, bh)
    ref = jnp.moveaxis(
        gru_sequence_ref(
            jnp.moveaxis(xs, 1, 0), w, u, bi, bh,
            jnp.zeros((b, h), jnp.float32),
        ), 0, 1,
    )
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=1e-5
    )


def test_gru_nonzero_initial_state():
    b, t, i, h = 2, 4, 8, 16
    xs = jnp.zeros((b, t, i))
    w = jnp.zeros((i, 3 * h))
    u = jnp.asarray(RNG.standard_normal((h, 3 * h)).astype(np.float32) * 0.3)
    bi = jnp.zeros(3 * h)
    bh = jnp.zeros(3 * h)
    h0 = jnp.asarray(RNG.standard_normal((b, h)).astype(np.float32))
    out = gru_sequence(xs, w, u, bi, bh, h0=h0)
    ref = jnp.moveaxis(
        gru_sequence_ref(jnp.moveaxis(xs, 1, 0), w, u, bi, bh, h0), 0, 1
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


# ---------------- intgemm ----------------

# dispatch="interpret" forces the Pallas kernel body (under the
# interpreter off-TPU) — the auto path resolves to the jnp reference on
# CPU, which would make a kernel-vs-reference comparison vacuous.

@pytest.mark.parametrize("m,k,n", [
    (1, 16, 12),
    (7, 100, 30),
    (8, 512, 8),
    (33, 144, 48),  # GRU-shaped
])
def test_intgemm_exact_sweep(m, k, n):
    x = jnp.asarray(RNG.integers(-8191, 8192, (m, k)), jnp.int32)
    w = jnp.asarray(RNG.integers(-128, 128, (k, n)), jnp.int32)
    out = intgemm(x, w, dispatch="interpret")
    assert bool((out == intgemm_ref(x, w)).all())


def test_intgemm_saturates_at_24bit():
    x = jnp.full((8, 512), 8191, jnp.int32)
    w = jnp.full((512, 8), 127, jnp.int32)
    out = intgemm(x, w, dispatch="interpret")
    assert int(out[0, 0]) == 2**23 - 1
    out2 = intgemm(x, -w, dispatch="interpret")
    assert int(out2[0, 0]) == -(2**23)


def test_intgemm_dispatch_paths_agree():
    """reference and interpret dispatch are the same function; auto off
    TPU resolves to reference and must inline under an outer jit."""
    x = jnp.asarray(RNG.integers(-8191, 8192, (5, 33)), jnp.int32)
    w = jnp.asarray(RNG.integers(-128, 128, (33, 20)), jnp.int32)
    ref = np.asarray(intgemm(x, w, dispatch="reference"))
    itp = np.asarray(intgemm(x, w, dispatch="interpret"))
    auto = np.asarray(intgemm(x, w))
    np.testing.assert_array_equal(ref, itp)
    np.testing.assert_array_equal(ref, auto)
    inside = np.asarray(jax.jit(lambda a, b: intgemm(a, b))(x, w))
    np.testing.assert_array_equal(ref, inside)


@settings(max_examples=25, deadline=None)
@given(
    m=st.integers(min_value=1, max_value=17),
    k=st.integers(min_value=1, max_value=700),
    n=st.integers(min_value=1, max_value=33),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    full_scale=st.booleans(),
)
def test_intgemm_matches_integer_oracle_property(m, k, n, seed, full_scale):
    """Property sweep: the Pallas kernel body (interpret) and the jnp
    reference both match an exact int64 numpy oracle bit-for-bit across
    odd/unpadded (M, K, N) shapes, including magnitudes that drive the
    accumulator to (and past) the 24-bit saturation rails (skipped when
    the hypothesis test extra is absent)."""
    rng = np.random.default_rng(seed)
    if full_scale:
        # near-24-bit accumulators: full-range 14-bit x 8-bit codes,
        # |sum| up to k * 2^20 — saturation binds for k >= 8
        x = rng.integers(-8191, 8192, (m, k))
        w = rng.integers(-128, 128, (k, n))
    else:
        x = rng.integers(-512, 513, (m, k))
        w = rng.integers(-128, 128, (k, n))
    oracle = np.clip(
        x.astype(np.int64) @ w.astype(np.int64), -(2**23), 2**23 - 1
    ).astype(np.int32)
    xj = jnp.asarray(x, jnp.int32)
    wj = jnp.asarray(w, jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(intgemm(xj, wj, dispatch="reference")), oracle
    )
    np.testing.assert_array_equal(
        np.asarray(intgemm(xj, wj, dispatch="interpret")), oracle
    )


@pytest.mark.parametrize("under_jit", [False, True],
                         ids=["top-level", "under-jit"])
def test_kernel_entry_points_run_at_top_level_and_under_jit(under_jit):
    """intgemm and tdc_counts with dispatch="interpret" run both as
    top-level calls (their own jit) and inlined into a caller's jit,
    the way the serving tick traces them."""
    cfg = TDFExConfig()
    spf = cfg.decimation // cfg.tdc_oversample
    x = jnp.asarray(RNG.integers(-8191, 8192, (5, 33)), jnp.int32)
    w = jnp.asarray(RNG.integers(-128, 128, (33, 20)), jnp.int32)
    u = jnp.asarray(
        np.abs(RNG.standard_normal((2, spf * 2, 16))).astype(np.float32)
        * 0.2
    )

    def both(x, w, u):
        return (intgemm(x, w, dispatch="interpret"),
                tdc_counts(u, cfg, dispatch="interpret"))

    gemm, counts = (jax.jit(both) if under_jit else both)(x, w, u)
    np.testing.assert_array_equal(np.asarray(gemm),
                                  np.asarray(intgemm_ref(x, w)))
    ref = tdc_counts_ref(
        np.asarray(u), np.full(16, cfg.f_free_hz),
        np.full(16, cfg.k_sro_hz), spf, cfg.tdc_oversample, cfg.f_tdc,
    )
    assert np.abs(np.asarray(counts) - ref).max() <= 1.0


def test_intgemm_split_covers_delta_codes():
    """The int8 hi/lo split is exact over |x| < 2^14, the range of Q6.8
    codes and of their ΔGRU deltas (x - x_ref)."""
    x = jnp.asarray([[-16383, -8192, -129, -128, -1, 0, 1, 127, 128,
                      8191, 16383]], jnp.int32)
    w = jnp.asarray(RNG.integers(-128, 128, (11, 9)), jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(intgemm(x, w, dispatch="interpret")),
        np.asarray(intgemm_ref(x, w)),
    )


# ---------------- tdc ----------------

@pytest.mark.parametrize("b,frames,c", [(1, 3, 16), (3, 6, 16), (2, 4, 4)])
def test_tdc_matches_float64_oracle(b, frames, c):
    cfg = TDFExConfig()
    spf = cfg.decimation // cfg.tdc_oversample
    u = jnp.asarray(
        np.abs(RNG.standard_normal((b, spf * frames, c))).astype(np.float32)
        * 0.2
    )
    out = np.asarray(tdc_counts(u, cfg))
    ref = tdc_counts_ref(
        np.asarray(u),
        np.full(c, cfg.f_free_hz),
        np.full(c, cfg.k_sro_hz),
        spf, cfg.tdc_oversample, cfg.f_tdc,
    )
    assert np.abs(out - ref).max() <= 1.0  # <= 1 LSB (noise-shaped)


def test_tdc_with_chip_mismatch():
    cfg = TDFExConfig()
    chip = draw_chip(jax.random.PRNGKey(5), cfg)
    spf = cfg.decimation // cfg.tdc_oversample
    u = jnp.asarray(
        np.abs(RNG.standard_normal((2, spf * 3, 16))).astype(np.float32) * 0.1
    )
    g = np.asarray(1.0 + chip.gain_mismatch)
    out = np.asarray(tdc_counts(u, cfg, chip))
    ref = tdc_counts_ref(
        np.asarray(u), cfg.f_free_hz * g, cfg.k_sro_hz * g,
        spf, cfg.tdc_oversample, cfg.f_tdc,
    )
    assert np.abs(out - ref).max() <= 1.0


# ---------------- wkv6 ----------------

@pytest.mark.parametrize("b,t,h,p", [(1, 8, 1, 4), (3, 24, 2, 8), (2, 16, 4, 16)])
def test_wkv6_kernel_matches_sequential(b, t, h, p):
    from repro.kernels.wkv6 import wkv6, wkv6_ref

    r = jnp.asarray(RNG.standard_normal((b, t, h, p)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, t, h, p)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, t, h, p)).astype(np.float32))
    lw = jnp.asarray(
        -np.exp(RNG.standard_normal((b, t, h, p)) - 1).astype(np.float32)
    )
    u = jnp.asarray(RNG.standard_normal((h, p)).astype(np.float32) * 0.3)
    out = wkv6(r, k, v, lw, u)
    ref = wkv6_ref(r, k, v, lw, u)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5
    )


def test_wkv6_kernel_strong_decay():
    from repro.kernels.wkv6 import wkv6, wkv6_ref

    b, t, h, p = 2, 12, 1, 4
    r = jnp.asarray(RNG.standard_normal((b, t, h, p)).astype(np.float32))
    k = jnp.asarray(RNG.standard_normal((b, t, h, p)).astype(np.float32))
    v = jnp.asarray(RNG.standard_normal((b, t, h, p)).astype(np.float32))
    lw = jnp.full((b, t, h, p), -50.0, jnp.float32)
    u = jnp.zeros((h, p), jnp.float32)
    out = wkv6(r, k, v, lw, u)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(wkv6_ref(r, k, v, lw, u)), atol=1e-5
    )


# ---------------- dtype sweeps ----------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 3e-2)])
def test_fex_fused_dtypes(dtype, tol):
    """bf16 IO compares against the f32 oracle: the kernel accumulates
    its IIR state in f32 regardless of IO dtype (a bf16 reference scan
    is the *lossier* computation)."""
    coeffs = design_filterbank(16, 32000.0)
    x32 = jnp.asarray(RNG.standard_normal((2, 2048)).astype(np.float32) * 0.2)
    out = fex_fused(x32.astype(dtype), coeffs, 512).astype(jnp.float32)
    ref = fex_fused_ref(x32, coeffs, 512)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), rtol=tol, atol=tol
    )


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5), (jnp.bfloat16, 3e-2)])
def test_gru_sequence_dtypes(dtype, tol):
    b, t, i, h = 2, 8, 16, 48
    xs = jnp.asarray(RNG.standard_normal((b, t, i)).astype(np.float32)).astype(dtype)
    w = jnp.asarray(RNG.standard_normal((i, 3 * h)).astype(np.float32) * 0.2).astype(dtype)
    u = jnp.asarray(RNG.standard_normal((h, 3 * h)).astype(np.float32) * 0.2).astype(dtype)
    bi = jnp.zeros(3 * h, dtype)
    bh = jnp.zeros(3 * h, dtype)
    out = gru_sequence(xs, w, u, bi, bh).astype(jnp.float32)
    ref = jnp.moveaxis(
        gru_sequence_ref(jnp.moveaxis(xs, 1, 0), w, u, bi, bh,
                         jnp.zeros((b, h), dtype)), 0, 1
    ).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=tol)


@pytest.mark.parametrize("in_dtype", [jnp.int32, jnp.int16])
def test_intgemm_input_dtypes(in_dtype):
    x = jnp.asarray(RNG.integers(-8191, 8192, (4, 64)), in_dtype)
    w = jnp.asarray(RNG.integers(-128, 128, (64, 16)), jnp.int8)
    out = intgemm(x, w, dispatch="interpret")
    assert bool((out == intgemm_ref(x, w)).all())
