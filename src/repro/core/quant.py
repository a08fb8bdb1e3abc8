"""Fixed-point / quantization substrate matching the paper's datapath.

The IC uses (Section II / III-E):
  * 12-bit unsigned quantizer on the decimated FEx output (FV_Raw),
  * 10-bit logarithmic LUT output (FV_Log),
  * 14-bit signed activations in Q6.8 (6 integer + 8 fractional bits)
    for FV_Norm and all GRU activations,
  * 8-bit signed weights,
  * 24-bit accumulators in the 8 HPEs.

Training uses quantization-aware training (QAT) with straight-through
estimators; inference can run a bit-exact integer path (see intgemm
kernel) whose results the QAT fake-quant path matches by construction.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "QuantSpec",
    "ACT_Q6_8",
    "WEIGHT_INT8",
    "ACC_INT24",
    "BIAS_Q8_15",
    "ste_round",
    "fake_quant",
    "quantize_int",
    "dequantize_int",
    "quantize_unsigned",
    "log_compress_lut",
    "make_log_lut",
    "round_shift_even",
    "clip_act_codes",
    "sigmoid_lut_q68",
    "tanh_lut_q68",
    "lut_sigmoid_q68",
    "lut_tanh_q68",
    "rom_sigmoid",
    "rom_tanh",
]


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """A fixed-point format: `bits` total, `frac_bits` fractional, signed."""

    bits: int
    frac_bits: int
    signed: bool = True

    @property
    def scale(self) -> float:
        """LSB weight: value = code * 2**-frac_bits."""
        return 2.0 ** (-self.frac_bits)

    @property
    def qmin(self) -> int:
        return -(2 ** (self.bits - 1)) if self.signed else 0

    @property
    def qmax(self) -> int:
        return 2 ** (self.bits - 1) - 1 if self.signed else 2**self.bits - 1

    @property
    def max_value(self) -> float:
        return self.qmax * self.scale

    @property
    def min_value(self) -> float:
        return self.qmin * self.scale


# The paper's formats.
ACT_Q6_8 = QuantSpec(bits=14, frac_bits=8, signed=True)  # activations / FV_Norm
WEIGHT_INT8 = QuantSpec(bits=8, frac_bits=7, signed=True)  # weights in [-1, 1)
ACC_INT24 = QuantSpec(bits=24, frac_bits=16, signed=True)  # HPE accumulator
FV_RAW_U12 = QuantSpec(bits=12, frac_bits=0, signed=False)  # quantizer output
FV_LOG_U10 = QuantSpec(bits=10, frac_bits=0, signed=False)  # log LUT output
# Biases live pre-loaded in the HPE accumulator, at the accumulation
# scale of a Q6.8 activation x int8 weight product (frac = 8 + 7 = 15).
BIAS_Q8_15 = QuantSpec(bits=24, frac_bits=15, signed=True)


@jax.custom_jvp
def ste_round(x: jnp.ndarray) -> jnp.ndarray:
    """round-to-nearest-even with a straight-through gradient."""
    return jnp.round(x)


@ste_round.defjvp
def _ste_round_jvp(primals, tangents):
    (x,), (t,) = primals, tangents
    return jnp.round(x), t


def fake_quant(x: jnp.ndarray, spec: QuantSpec) -> jnp.ndarray:
    """Quantize-dequantize to `spec` on the float path (QAT forward).

    Saturates at the format bounds (the HPE accumulator and activation
    registers saturate rather than wrap) and uses STE for gradients.
    """
    inv = 2.0**spec.frac_bits
    q = ste_round(x * inv)
    q = jnp.clip(q, spec.qmin, spec.qmax)
    return q * spec.scale


def quantize_int(x: jnp.ndarray, spec: QuantSpec, dtype=jnp.int32) -> jnp.ndarray:
    """Float -> integer codes (saturating). Bit-exact integer path entry."""
    q = jnp.round(x * 2.0**spec.frac_bits)
    return jnp.clip(q, spec.qmin, spec.qmax).astype(dtype)


def dequantize_int(codes: jnp.ndarray, spec: QuantSpec) -> jnp.ndarray:
    return codes.astype(jnp.float32) * spec.scale


def quantize_unsigned(x: jnp.ndarray, bits: int, x_max: float) -> jnp.ndarray:
    """The FEx 12-bit unsigned quantizer: [0, x_max] -> integer codes.

    Mirrors the DeltaSigma-TDC + decimation output register width. Values
    are clipped (the TDC count register saturates).
    """
    levels = 2**bits - 1
    q = ste_round(jnp.clip(x, 0.0, x_max) / x_max * levels)
    return q  # float codes in [0, levels]; STE-differentiable


def make_log_lut(in_bits: int = 12, out_bits: int = 10) -> jnp.ndarray:
    """The 12-bit -> 10-bit logarithmic compression LUT (Section II).

    out = round((2^out_bits - 1) * log2(1 + v) / log2(2^in_bits)) — a
    monotone logarithmic companding curve covering the full input range,
    exactly representable as a 4096-entry ROM on the IC.
    """
    v = jnp.arange(2**in_bits, dtype=jnp.float32)
    out = jnp.round(
        (2.0**out_bits - 1.0) * jnp.log2(1.0 + v) / (in_bits * 1.0)
    )
    return out.astype(jnp.float32)


def log_compress_lut(codes: jnp.ndarray, in_bits: int = 12, out_bits: int = 10):
    """Differentiable (STE) logarithmic compression of integer codes.

    On hardware this is a ROM lookup; here we evaluate the closed form and
    round with STE so QAT can backprop through the FEx chain.
    """
    x = jnp.clip(codes, 0.0, 2.0**in_bits - 1.0)
    out = (2.0**out_bits - 1.0) * jnp.log2(1.0 + x) / (in_bits * 1.0)
    return ste_round(out)


# --------------------------------------------------------------------------
# Bit-exact integer inference substrate (the IC's datapath on codes).
#
# The contract with the QAT fake-quant path: every float op the QAT
# forward performs on grid values is exactly representable in float32
# for the network's magnitudes, so replaying it on integer codes with
# the same round-to-nearest-even rule is bit-identical (regression-
# tested in tests/test_classifier_int.py). Rescaling a frac-a x frac-b
# product (or a bias-augmented accumulator) back to Q6.8 is a single
# `round_shift_even`; sigmoid/tanh are ROM lookups over the 15-bit sum
# of two saturated Q6.8 addends, exactly as the IC's LUTs.
# --------------------------------------------------------------------------

def round_shift_even(codes: jnp.ndarray, shift: int) -> jnp.ndarray:
    """``round(codes / 2**shift)`` with ties-to-even, pure integer ops.

    Matches `jnp.round` (round-half-even) on the same rational values,
    which is what makes the integer path reproduce `fake_quant` bit for
    bit. `codes` must be a signed integer array; the arithmetic right
    shift floors for negatives, and the remainder test rounds the tie
    toward the even quotient.
    """
    if shift == 0:
        return codes
    half = 1 << (shift - 1)
    q = codes >> shift  # arithmetic shift: floor division
    r = codes - (q << shift)  # remainder in [0, 2**shift)
    round_up = (r > half) | ((r == half) & ((q & 1) == 1))
    return q + round_up.astype(q.dtype)


def clip_act_codes(codes: jnp.ndarray) -> jnp.ndarray:
    """Saturate integer codes to the Q6.8 activation register range."""
    return jnp.clip(codes, ACT_Q6_8.qmin, ACT_Q6_8.qmax)


# Domain of the sigmoid/tanh LUTs: the sum of two saturated Q6.8 codes
# (gate preactivations are i_gate + h_gate with both addends already
# clipped to the activation register), i.e. [2*qmin, 2*qmax].
_LUT_MIN = 2 * ACT_Q6_8.qmin
_LUT_MAX = 2 * ACT_Q6_8.qmax


def _host_rom(fn) -> np.ndarray:
    """``quantize_int(fn(code * 2^-8))`` over the LUT domain, evaluated
    on the host CPU backend.

    The TPU's float sigmoid/tanh differ from the CPU's in the last bit
    at a few grid points (2 sigmoid and 6 tanh entries of 32767 on a
    v5e), enough to flip a Q6.8 rounding. A ROM is one table, so it is
    built on one backend: the integer engine and the QAT gates
    (`rom_sigmoid`, `rom_tanh`) then serve the same codes on every
    platform.
    Built eagerly even when first requested under a trace (the cached
    table must be a constant, not a tracer of the enclosing scan/jit).
    """
    try:
        host = jax.devices("cpu")[0]
    except RuntimeError as e:
        raise RuntimeError(
            "the Q6.8 sigmoid/tanh ROMs are built on the host CPU "
            "backend, which this process does not have: leave "
            "JAX_PLATFORMS unset or include 'cpu' (e.g. "
            "JAX_PLATFORMS=tpu,cpu)"
        ) from e
    with jax.ensure_compile_time_eval(), jax.default_device(host):
        codes = jnp.arange(_LUT_MIN, _LUT_MAX + 1, dtype=jnp.int32)
        vals = fn(codes.astype(jnp.float32) * ACT_Q6_8.scale)
        return np.asarray(quantize_int(vals, ACT_Q6_8))


@functools.lru_cache(maxsize=None)
def sigmoid_lut_q68() -> np.ndarray:
    """Q6.8 sigmoid ROM over the summed-preactivation code domain.

    Entry ``i`` holds ``quantize_int(sigmoid((i + _LUT_MIN) * 2^-8))`` —
    the same float evaluation + round-half-even the QAT path performs,
    so lookup and fake-quant agree exactly on every representable input
    (on the CPU; see `_host_rom`).
    """
    return _host_rom(jax.nn.sigmoid)


@functools.lru_cache(maxsize=None)
def tanh_lut_q68() -> np.ndarray:
    """Q6.8 tanh ROM over the summed-preactivation code domain."""
    return _host_rom(jnp.tanh)


def lut_sigmoid_q68(codes: jnp.ndarray) -> jnp.ndarray:
    """Integer sigmoid: summed Q6.8 preactivation codes -> Q6.8 codes."""
    idx = jnp.clip(codes, _LUT_MIN, _LUT_MAX) - _LUT_MIN
    return jnp.take(sigmoid_lut_q68(), idx)


def lut_tanh_q68(codes: jnp.ndarray) -> jnp.ndarray:
    """Integer tanh: summed Q6.8 preactivation codes -> Q6.8 codes."""
    idx = jnp.clip(codes, _LUT_MIN, _LUT_MAX) - _LUT_MIN
    return jnp.take(tanh_lut_q68(), idx)


def _rom_act(fn, lut):
    """``fake_quant(fn(x), ACT_Q6_8)`` for Q6.8-grid ``x``, read from
    the host ROM so every backend returns the same value, with the
    gradient of the float expression (STE)."""

    @jax.custom_jvp
    def act(x):
        codes = jnp.round(x * 2.0**ACT_Q6_8.frac_bits).astype(jnp.int32)
        return dequantize_int(lut(codes), ACT_Q6_8)

    @act.defjvp
    def _jvp(primals, tangents):
        _, dy = jax.jvp(lambda v: fake_quant(fn(v), ACT_Q6_8), primals,
                        tangents)
        return act(*primals), dy

    return act


# The QAT gate activations. On the CPU they equal fake_quant(fn(x))
# bit for bit; a TPU's float sigmoid/tanh would round 8 grid points
# differently (see `_host_rom`), two of them next to zero.
rom_sigmoid = _rom_act(jax.nn.sigmoid, lut_sigmoid_q68)
rom_tanh = _rom_act(jnp.tanh, lut_tanh_q68)
