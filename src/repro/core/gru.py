"""GRU-FC classifier (paper Sections II, III-E).

Network: 16-in -> GRU(48) -> GRU(48) -> FC(12).  PyTorch gate convention
(the paper trains in PyTorch 1.8):

    r = sigmoid(W_ir x + b_ir + W_hr h + b_hr)
    z = sigmoid(W_iz x + b_iz + W_hz h + b_hz)
    n = tanh   (W_in x + b_in + r * (W_hn h + b_hn))
    h' = (1 - z) * n + z * h

Weight memory at 8 bits = ~24 KB, matching the IC's WMEM; QAT applies
8-bit weights / 14-bit (Q6.8) activations via `repro.core.quant`.

Three execution paths (selected via `KWSPipelineConfig.classifier` —
see `repro.core.classifier`):
  * float / QAT (this file) — training and the software-model numbers;
  * bit-exact integer engine (`repro.core.gru_int`) — int8 weight codes
    and Q6.8 activation codes through the saturating-int24 `intgemm`
    kernel, bit-identical to the QAT fake-quant forward;
  * weights-resident Pallas kernel (`repro.kernels.gru`) — the TPU
    analogue of the IC's 8-HPE accelerator, validated against this file.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant

__all__ = [
    "GRUConfig",
    "init_gru_classifier",
    "gru_cell",
    "gru_layer",
    "qdot",
    "fc_logits",
    "gru_classifier_forward",
    "gru_classifier_step",
    "classifier_macs",
    "classifier_param_bytes",
]


@dataclasses.dataclass(frozen=True)
class GRUConfig:
    input_dim: int = 16
    hidden_dim: int = 48
    num_layers: int = 2
    num_classes: int = 12
    quantized: bool = True  # QAT fake-quant on weights + activations

    @property
    def weight_spec(self) -> quant.QuantSpec:
        return quant.WEIGHT_INT8

    @property
    def act_spec(self) -> quant.QuantSpec:
        return quant.ACT_Q6_8


Params = Dict[str, Any]


def init_gru_classifier(key: jax.Array, config: GRUConfig) -> Params:
    """Uniform(-1/sqrt(H)) init, PyTorch-style."""
    h = config.hidden_dim
    params: Params = {"gru": [], "fc": {}}
    k = 1.0 / np.sqrt(h)
    for layer in range(config.num_layers):
        in_dim = config.input_dim if layer == 0 else h
        key, k1, k2, k3, k4 = jax.random.split(key, 5)
        params["gru"].append(
            {
                "w_i": jax.random.uniform(k1, (in_dim, 3 * h), jnp.float32, -k, k),
                "w_h": jax.random.uniform(k2, (h, 3 * h), jnp.float32, -k, k),
                "b_i": jax.random.uniform(k3, (3 * h,), jnp.float32, -k, k),
                "b_h": jax.random.uniform(k4, (3 * h,), jnp.float32, -k, k),
            }
        )
    key, k1, k2 = jax.random.split(key, 3)
    params["fc"] = {
        "w": jax.random.uniform(
            k1, (h, config.num_classes), jnp.float32, -k, k
        ),
        "b": jax.random.uniform(k2, (config.num_classes,), jnp.float32, -k, k),
    }
    return params


def _maybe_q(x: jnp.ndarray, spec: Optional[quant.QuantSpec]) -> jnp.ndarray:
    return quant.fake_quant(x, spec) if spec is not None else x


def qdot(x: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """``x @ w`` at float32 precision on every backend.

    At default precision a TPU multiplies float32 in one bfloat16 pass,
    which keeps 8 of a Q6.8 code's 14 significant bits. HIGHEST splits
    the operands into bfloat16 pieces that hold every Q6.8 x int8
    product exactly, as the CPU's float32 does, so the QAT forward stays
    replayable on integer codes (repro.core.gru_int) on a TPU too.
    """
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _gate_acts(aspec):
    """The gates' sigmoid and tanh: float, or on the Q6.8 grid the ROM
    lookups (`quant.rom_sigmoid`), which give every backend the codes
    the CPU computes."""
    if aspec is None:
        return jax.nn.sigmoid, jnp.tanh
    return quant.rom_sigmoid, quant.rom_tanh


def _layer_weights(layer: Params, wspec) -> Tuple[jnp.ndarray, ...]:
    # Biases are pre-loaded into the 24-bit HPE accumulator, which works
    # at the Q6.8 x int8 product scale (frac 15) — quantize them to that
    # grid whenever weights are quantized, so the QAT forward is exactly
    # replayable on integer codes (repro.core.gru_int).
    bspec = None if wspec is None else quant.BIAS_Q8_15
    return (
        _maybe_q(layer["w_i"], wspec),
        _maybe_q(layer["w_h"], wspec),
        _maybe_q(layer["b_i"], bspec),
        _maybe_q(layer["b_h"], bspec),
    )


def gru_cell(
    layer: Params,
    h: jnp.ndarray,
    x: jnp.ndarray,
    config: GRUConfig,
) -> jnp.ndarray:
    """One GRU step: x (B, I), h (B, H) -> h' (B, H)."""
    aspec = config.act_spec if config.quantized else None
    wspec = config.weight_spec if config.quantized else None
    w_i, w_h, b_i, b_h = _layer_weights(layer, wspec)
    hdim = h.shape[-1]

    gi = _maybe_q(qdot(x, w_i) + b_i, aspec)  # (B, 3H)
    gh = _maybe_q(qdot(h, w_h) + b_h, aspec)
    i_r, i_z, i_n = jnp.split(gi, 3, axis=-1)
    h_r, h_z, h_n = jnp.split(gh, 3, axis=-1)
    # Gate outputs are quantized BEFORE use: on the IC sigmoid/tanh are
    # Q6.8 ROM lookups, so every downstream consumer (the r * h_n
    # product and the convex h update) sees register values, never the
    # float intermediate. The QAT path reads the integer engine's ROMs,
    # which keeps it bit-replayable on integer codes (repro.core.gru_int)
    # on every backend.
    sigmoid, tanh = _gate_acts(aspec)
    r = sigmoid(i_r + h_r)
    z = sigmoid(i_z + h_z)
    n = tanh(i_n + _maybe_q(r * h_n, aspec))
    h_new = (1.0 - z) * n + z * h
    return _maybe_q(h_new, aspec)


def gru_layer(
    layer: Params, xs: jnp.ndarray, config: GRUConfig, h0=None
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """xs (B, T, I) -> (hs (B, T, H), h_T (B, H))."""
    bsz = xs.shape[0]
    h = (
        jnp.zeros((bsz, config.hidden_dim), xs.dtype) if h0 is None else h0
    )

    def step(h, x_t):
        h_new = gru_cell(layer, h, x_t, config)
        return h_new, h_new

    h_t, hs = jax.lax.scan(step, h, jnp.moveaxis(xs, 1, 0))
    return jnp.moveaxis(hs, 0, 1), h_t


def fc_logits(params: Params, x: jnp.ndarray, config: GRUConfig):
    """The dense FC head on the last axis: (..., H) -> (..., K).

    The single definition shared by the batch forward, the streaming
    step, and the ΔGRU float engine (`repro.core.gru_delta`) — the
    θ=0 bit-identity target lives in exactly one place, mirroring how
    the code domain shares `gru_int._accum`.
    """
    wspec = config.weight_spec if config.quantized else None
    aspec = config.act_spec if config.quantized else None
    bspec = None if wspec is None else quant.BIAS_Q8_15
    w = _maybe_q(params["fc"]["w"], wspec)
    return _maybe_q(qdot(x, w) + _maybe_q(params["fc"]["b"], bspec), aspec)


def gru_classifier_forward(
    params: Params, fv: jnp.ndarray, config: GRUConfig
) -> jnp.ndarray:
    """fv (B, T, C) -> logits (B, T, num_classes) — per-frame scores.

    The IC streams an FV every 16 ms and the detected class is the most
    active output at the end of the sample (Section IV); callers take
    logits[:, -1] for classification.
    """
    xs = fv
    for layer in params["gru"]:
        xs, _ = gru_layer(layer, xs, config)
    return fc_logits(params, xs, config)


def gru_classifier_step(
    params: Params,
    states: List[jnp.ndarray],
    fv_t: jnp.ndarray,
    config: GRUConfig,
) -> Tuple[List[jnp.ndarray], jnp.ndarray]:
    """Streaming step: one frame fv_t (B, C) -> (new states, logits (B, K)).

    This is the serving path — state-resident, one FV per 16 ms frame,
    mirroring the accelerator's operation in Fig. 4.
    """
    new_states = []
    x = fv_t
    for layer, h in zip(params["gru"], states):
        h_new = gru_cell(layer, h, x, config)
        new_states.append(h_new)
        x = h_new
    return new_states, fc_logits(params, x, config)


def init_states(
    config: GRUConfig, batch: int, device=None
) -> List[jnp.ndarray]:
    """Per-layer hidden states; ``device`` (Device or Sharding) places
    each buffer at creation — sharded servers pass a stream-axis
    NamedSharding so no oversized single-device zeros is ever built."""
    return [
        jnp.zeros((batch, config.hidden_dim), jnp.float32, device=device)
        for _ in range(config.num_layers)
    ]


def classifier_macs(config: GRUConfig) -> int:
    """MAC count per frame — drives the latency model (Section III-E).

    Paper check: 2x48 GRU + FC over 16 inputs = 24,204 weights; at 8 HPEs
    and 250 kHz this yields the reported 12.4 ms latency (see energy.py).
    """
    macs = 0
    h = config.hidden_dim
    for layer in range(config.num_layers):
        in_dim = config.input_dim if layer == 0 else h
        macs += 3 * h * (in_dim + h) + 2 * 3 * h  # matmuls + two bias adds
    macs += config.num_classes * h + config.num_classes
    return macs


def classifier_param_bytes(config: GRUConfig, bits: int = 8) -> int:
    h = config.hidden_dim
    n = 0
    for layer in range(config.num_layers):
        in_dim = config.input_dim if layer == 0 else h
        n += 3 * h * (in_dim + h) + 2 * 3 * h
    n += config.num_classes * h + config.num_classes
    return n * bits // 8
