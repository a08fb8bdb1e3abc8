"""Bit-exact integer GRU-FC engine (the IC's digital classifier on codes).

This is the inference twin of `repro.core.gru`: the same 16 -> GRU(48)
-> GRU(48) -> FC(12) network, evaluated entirely on integer codes the
way the chip's 8 HPEs do (Sections II, III-E):

  * weights as int8 codes (frac 7, `quant.WEIGHT_INT8`),
  * activations / hidden state as Q6.8 codes (`quant.ACT_Q6_8`),
  * biases pre-loaded in the 24-bit accumulator at the product scale
    (frac 15, `quant.BIAS_Q8_15`),
  * matmuls through `repro.kernels.intgemm` (24-bit saturating
    accumulator; Pallas on TPU, exact jnp reference elsewhere),
  * sigmoid/tanh as Q6.8 ROM lookups (`quant.lut_sigmoid_q68` /
    `quant.lut_tanh_q68`) over the 15-bit summed-preactivation domain;
    the ROMs are built on the host CPU, and the device reads each
    as the thresholds of its unit steps (a compare-and-count, no gather),
  * every rescale a single round-to-nearest-even shift
    (`quant.round_shift_even`) plus Q6.8 saturation.

Bit-identity contract: for parameters produced by
`repro.serving.quantize.quantize_classifier` and inputs on the Q6.8
grid (which `KWSPipeline._postprocess` guarantees), the dequantized
outputs of `int_gru_classifier_forward` / `int_gru_classifier_step`
equal the QAT fake-quant path of `repro.core.gru` bit for bit — the
contract promised in `repro.core.quant`'s docstring and regression-
tested in tests/test_classifier_int.py. The one documented edge: the
integer path saturates the matmul accumulator at 24 bits before the
bias add, which the float path (clipping only at Q6.8) cannot see; it
binds only for |x . w| >= 256, far outside the network's Q6.8 range.

Everything here is pure jnp on integer arrays, so the engine scans,
vmaps, and fuses into the serving tick exactly like the float path.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.gru import GRUConfig
from repro.kernels.intgemm import intgemm

__all__ = [
    "QuantizedClassifier",
    "int_gru_cell",
    "int_gru_layer",
    "int_gru_classifier_forward",
    "int_gru_classifier_step",
    "int_init_states",
    "quantize_acts",
    "dequantize_acts",
]

# Rescale shifts fixed by the paper's formats: an act (frac 8) x weight
# (frac 7) accumulator carries frac 15 -> Q6.8 needs >> 7; an act x act
# product carries frac 16 -> Q6.8 needs >> 8. 1.0 in Q6.8 is 1 << 8.
_ACC_SHIFT = quant.WEIGHT_INT8.frac_bits
_ACT_SHIFT = quant.ACT_Q6_8.frac_bits
_ONE_Q68 = 1 << quant.ACT_Q6_8.frac_bits


@dataclasses.dataclass(frozen=True)
class QuantizedClassifier:
    """All classifier parameters as integer codes, as one pytree.

    gru  — per-layer dicts {w_i (I, 3H) int8, w_h (H, 3H) int8,
           b_i (3H,) int32 frac-15, b_h (3H,) int32 frac-15}.
    fc_w — (H, K) int8 weight codes.
    fc_b — (K,) int32 bias codes, frac-15.

    The scales are the paper's fixed per-tensor formats (weights 2^-7,
    biases 2^-15, activations 2^-8) and travel as class-level structure
    rather than leaves, so the pytree crosses jit/donation boundaries
    as plain integer buffers. Built by
    `repro.serving.quantize.quantize_classifier`.
    """

    gru: Tuple[Dict[str, jnp.ndarray], ...]
    fc_w: jnp.ndarray
    fc_b: jnp.ndarray


jax.tree_util.register_dataclass(
    QuantizedClassifier,
    data_fields=["gru", "fc_w", "fc_b"],
    meta_fields=[],
)


def quantize_acts(x: jnp.ndarray) -> jnp.ndarray:
    """Float activations -> Q6.8 codes (exact for on-grid inputs)."""
    return quant.quantize_int(x, quant.ACT_Q6_8)


def dequantize_acts(codes: jnp.ndarray) -> jnp.ndarray:
    """Q6.8 codes -> float32 (exact: code * 2^-8)."""
    return quant.dequantize_int(codes, quant.ACT_Q6_8)


def _accum(x_codes: jnp.ndarray, w_codes: jnp.ndarray,
           b_codes: jnp.ndarray) -> jnp.ndarray:
    """x (B, K) Q6.8 @ w (K, N) int8 + bias (frac 15) -> Q6.8 codes."""
    acc = intgemm(x_codes, w_codes) + b_codes
    return quant.clip_act_codes(quant.round_shift_even(acc, _ACC_SHIFT))


def int_gru_cell(
    layer: Dict[str, jnp.ndarray],
    h: jnp.ndarray,
    x: jnp.ndarray,
    config: GRUConfig,
    scope: str = "kws_gru",
) -> jnp.ndarray:
    """One GRU step on codes: x (B, I), h (B, H) -> h' (B, H), int32.

    Its stages run under named scopes ``<scope>_gemm`` (the input and
    recurrent accumulations), ``<scope>_gates`` (the three ROM step counts)
    and ``<scope>_update`` (the state update)."""
    del config  # geometry is carried by the code arrays themselves
    with jax.named_scope(f"{scope}_gemm"):
        gi = _accum(x, layer["w_i"], layer["b_i"])  # (B, 3H)
        gh = _accum(h, layer["w_h"], layer["b_h"])
    i_r, i_z, i_n = jnp.split(gi, 3, axis=-1)
    h_r, h_z, h_n = jnp.split(gh, 3, axis=-1)
    with jax.named_scope(f"{scope}_gates"):
        r = quant.lut_sigmoid_q68(i_r + h_r)
        z = quant.lut_sigmoid_q68(i_z + h_z)
        rn = quant.clip_act_codes(
            quant.round_shift_even(r * h_n, _ACT_SHIFT))
        n = quant.lut_tanh_q68(i_n + rn)
    with jax.named_scope(f"{scope}_update"):
        h_new = quant.round_shift_even((_ONE_Q68 - z) * n + z * h,
                                       _ACT_SHIFT)
        return quant.clip_act_codes(h_new)


def int_gru_layer(
    layer: Dict[str, jnp.ndarray],
    xs: jnp.ndarray,
    config: GRUConfig,
    h0=None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """xs (B, T, I) codes -> (hs (B, T, H), h_T (B, H)) codes."""
    bsz = xs.shape[0]
    h = (
        jnp.zeros((bsz, config.hidden_dim), jnp.int32) if h0 is None else h0
    )

    def step(h, x_t):
        h_new = int_gru_cell(layer, h, x_t, config)
        return h_new, h_new

    h_t, hs = jax.lax.scan(step, h, jnp.moveaxis(xs, 1, 0))
    return jnp.moveaxis(hs, 0, 1), h_t


def int_gru_classifier_forward(
    qparams: QuantizedClassifier, fv_codes: jnp.ndarray, config: GRUConfig
) -> jnp.ndarray:
    """fv codes (B, T, C) -> per-frame logit codes (B, T, K), int32."""
    xs = fv_codes
    for layer in qparams.gru:
        xs, _ = int_gru_layer(layer, xs, config)
    b, t, h = xs.shape
    logits = _accum(
        xs.reshape(b * t, h), qparams.fc_w, qparams.fc_b
    )
    return logits.reshape(b, t, -1)


def int_gru_classifier_step(
    qparams: QuantizedClassifier,
    states: List[jnp.ndarray],
    fv_t: jnp.ndarray,
    config: GRUConfig,
) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Streaming step on codes: one frame (B, C) -> (states, (B, K))."""
    new_states = []
    x = fv_t
    for i, (layer, h) in enumerate(zip(qparams.gru, states)):
        h_new = int_gru_cell(layer, h, x, config, scope=f"kws_gru{i}")
        new_states.append(h_new)
        x = h_new
    with jax.named_scope("kws_head"):
        logits = _accum(x, qparams.fc_w, qparams.fc_b)
    return new_states, logits


def int_init_states(
    config: GRUConfig, batch: int, device=None
) -> List[jnp.ndarray]:
    """Per-layer int32 Q6.8 hidden-state codes; ``device`` as in
    `repro.core.gru.init_states`."""
    return [
        jnp.zeros((batch, config.hidden_dim), jnp.int32, device=device)
        for _ in range(config.num_layers)
    ]
