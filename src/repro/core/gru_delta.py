"""Temporal-sparsity delta-GRU inference engine (DeltaKWS-style ΔGRU).

Speech features are temporally redundant: consecutive 16 ms FV_Norm
frames (and the GRU hidden states they drive) change little, so most of
the accelerator's dense MAC work recomputes products it already knows.
DeltaKWS ("DeltaKWS: A 65nm 36nJ/Decision Bio-inspired Temporal-
Sparsity-Aware Digital Keyword Spotting IC", PAPERS.md) exploits this
with a ΔGRU: each layer remembers the last-TRANSMITTED input/state
vectors and the running matmul partial sums, and per timestep only the
components whose change exceeds a threshold θ fire a (delta · weight
column) update — everything else is skipped, cutting effective MACs
several-fold at near-iso accuracy.

This module is that engine for the paper's 16 -> GRU(48) -> GRU(48) ->
FC(12) classifier, in both arithmetic domains of the classifier
registry (`repro.core.classifier`):

  * the QAT fake-quant float domain (`delta_*` functions) — the delta
    sibling of `repro.core.gru`, registered as backend ``"delta"``;
  * the bit-exact integer code domain (`int_delta_*` functions, int8
    weights through the saturating-int24 `intgemm` kernel, Q6.8 ROM
    LUT nonlinearities) — the delta sibling of `repro.core.gru_int`,
    registered as backend ``"delta-int"``.

Per layer, the delta state carries:

  h        the true GRU hidden state (identical to the dense backends),
  x_ref    last-transmitted input memory  (what the columns of W_i saw),
  h_ref    last-transmitted state memory  (what the columns of W_h saw),
  acc_x    running partial sum Σ Δx · W_i   (bias NOT folded in, so a
  acc_h    running partial sum Σ Δh · W_h    zeroed slot is a valid
                                             fresh stream — the serving
                                             slot reset just zeroes),
  skipped  per-stream int32 count of delta-eligible weight COLUMNS
           skipped so far (a layer's column = 3H MACs; column units
           keep the counter ~4 days from int32 overflow at 16 ms
           ticks, and `effective_mac_fraction` converts exactly),
  total    per-stream int32 count of delta-eligible columns offered.

Per step, with θ in Q6.8 code units (`DeltaConfig`):

  Δx = x - x_ref;  fire = |Δx| > θ_x;  Δx[~fire] = 0
  x_ref += Δx;     acc_x += Δx · W_i          (only fired columns count)
  gi = quantize(acc_x + b_i)                  (the dense path's
                                               quantize(x·W_i + b_i))
  ... and the same for Δh against h_ref / acc_h / W_h; the gates, the
  r·h_n product and the convex h update are EXACTLY the dense cell's.

Bit-identity contract (regression-tested in tests/test_gru_delta.py):
at θ = 0 only exactly-unchanged components are skipped, so the partial
sums telescope — acc_x ≡ x · W_i and acc_h ≡ h · W_h on the nose — and
the engine is BIT-identical to its dense base backend ("qat" for the
float domain, "integer" for the code domain) for the full forward, the
streaming step, the fused serving tick, the lax.scan replay, and the
sharded multi-device server. The arithmetic argument is the same one
the QAT/integer identity already rests on (`repro.core.quant`): every
value lives on a fixed-point grid (Q6.8 inputs/states, frac-15 partial
sums) whose in-range sums and products are exact in both int32 and
float32, so adding increments in a different order changes nothing.

The skipped/total counters count DELTA-ELIGIBLE work only — the GRU
matmul lanes a ΔGRU can skip (each skipped input component is a
3H-wide weight column a sparse datapath need not read). Every lowering
here still computes the dense Δ·W product: the skipped columns are
counted, not saved. Bias adds and the dense FC head are excluded
from the counters; `effective_mac_fraction` converts columns to MACs
per layer and folds the always-dense FC back in, so the reported
fraction covers the whole classifier.

Everything is pure jnp on fixed-size arrays, so the engine scans,
vmaps, shards over the ("stream",) serving mesh, and fuses into the
serving tick exactly like the dense backends.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.gru import (
    GRUConfig, _gate_acts, _layer_weights, _maybe_q, fc_logits, qdot,
)
from repro.core.gru_int import (
    _ACC_SHIFT,
    _ACT_SHIFT,
    _ONE_Q68,
    _accum,
    QuantizedClassifier,
)
from repro.kernels.intgemm import intgemm

__all__ = [
    "DeltaConfig",
    "delta_init_states",
    "delta_gru_cell",
    "delta_classifier_step",
    "delta_classifier_forward",
    "int_delta_init_states",
    "int_delta_gru_cell",
    "int_delta_classifier_step",
    "int_delta_classifier_forward",
    "delta_eligible_macs_per_frame",
    "dense_fc_macs_per_frame",
    "effective_mac_fraction",
    "is_delta_states",
]


@dataclasses.dataclass(frozen=True)
class DeltaConfig:
    """ΔGRU thresholds, in the FV_Norm/state value domain (Q6.8 units).

    ``theta_x`` / ``theta_h`` apply to every layer's input / hidden
    deltas; ``per_layer`` overrides both per layer as a tuple of
    (theta_x, theta_h) pairs (length must equal ``gru.num_layers``).
    θ = 0 (the default) skips only exactly-unchanged components and is
    bit-identical to the dense base backend.

    Thresholds are snapped to the Q6.8 grid (`code_thresholds`) so the
    float- and code-domain engines fire identically: a delta fires when
    ``|Δ| > θ`` with both sides on the grid.
    """

    theta_x: float = 0.0
    theta_h: float = 0.0
    per_layer: Optional[Tuple[Tuple[float, float], ...]] = None

    def __post_init__(self):
        thetas = [self.theta_x, self.theta_h]
        if self.per_layer is not None:
            # normalize to nested tuples so the config stays hashable
            object.__setattr__(
                self,
                "per_layer",
                tuple((float(tx), float(th)) for tx, th in self.per_layer),
            )
            thetas += [t for pair in self.per_layer for t in pair]
        if any(t < 0 for t in thetas):
            raise ValueError(f"delta thresholds must be >= 0; got {self}")

    def code_thresholds(self, num_layers: int) -> Tuple[Tuple[int, int], ...]:
        """Per-layer (θ_x, θ_h) in integer Q6.8 code units."""
        if self.per_layer is not None:
            if len(self.per_layer) != num_layers:
                raise ValueError(
                    f"DeltaConfig.per_layer has {len(self.per_layer)} "
                    f"entries for {num_layers} GRU layers"
                )
            pairs = self.per_layer
        else:
            pairs = ((self.theta_x, self.theta_h),) * num_layers
        scale = 2.0 ** quant.ACT_Q6_8.frac_bits
        return tuple(
            (int(round(tx * scale)), int(round(th * scale)))
            for tx, th in pairs
        )


def _layer_dims(config: GRUConfig) -> List[Tuple[int, int]]:
    h = config.hidden_dim
    return [
        (config.input_dim if layer == 0 else h, h)
        for layer in range(config.num_layers)
    ]


def delta_eligible_macs_per_frame(config: GRUConfig) -> int:
    """MACs per frame a ΔGRU can skip: the GRU matmul lanes (each input/
    state component drives a 3H-wide weight column)."""
    return sum(3 * h * (i + h) for i, h in _layer_dims(config))


def dense_fc_macs_per_frame(config: GRUConfig) -> int:
    """The always-dense FC head's MACs per frame (never delta-skipped)."""
    return config.num_classes * config.hidden_dim


def _zeros_state(config, batch, dtype, device) -> List[Dict[str, jnp.ndarray]]:
    states = []
    for in_dim, h in _layer_dims(config):
        z = lambda shape, dt: jnp.zeros(shape, dt, device=device)  # noqa: E731
        states.append(
            {
                "h": z((batch, h), dtype),
                "x_ref": z((batch, in_dim), dtype),
                "h_ref": z((batch, h), dtype),
                "acc_x": z((batch, 3 * h), dtype),
                "acc_h": z((batch, 3 * h), dtype),
                "skipped": z((batch,), jnp.int32),
                "total": z((batch,), jnp.int32),
            }
        )
    return states


def delta_init_states(
    config: GRUConfig, batch: int, device=None
) -> List[Dict[str, jnp.ndarray]]:
    """Float-domain per-layer delta state (all-zeros IS the fresh state:
    empty memories, empty partial sums, zero counters — which is why the
    serving slot reset can just zero a slot's slices)."""
    return _zeros_state(config, batch, jnp.float32, device)


def int_delta_init_states(
    config: GRUConfig, batch: int, device=None
) -> List[Dict[str, jnp.ndarray]]:
    """Code-domain per-layer delta state (int32 Q6.8 / frac-15 codes)."""
    return _zeros_state(config, batch, jnp.int32, device)


def is_delta_states(states: Any) -> bool:
    """True when ``states`` is a delta-backend state list/tuple (the
    serving layer uses this to expose sparsity telemetry)."""
    return (
        isinstance(states, (list, tuple))
        and len(states) > 0
        and isinstance(states[0], dict)
        and "skipped" in states[0]
    )


def _count_macs(st, fire_x, fire_h):
    """Update the per-stream skipped/total counters for one step.

    Every input/state component offers one 3H-wide weight column; a
    non-fired component skips it entirely. The counters tick in COLUMN
    units (each column = 3H MACs — `effective_mac_fraction` converts,
    and the 3H factor cancels inside a layer anyway): a layer offers
    I+H <= 96 columns per frame, so an int32 counter lasts ~2^31/96
    frames ~= 4 days of continuous 16 ms ticks before overflow, vs
    under an hour if it ticked in raw MACs. Counters reset with the
    slot (`open_stream`).
    """
    in_dim = fire_x.shape[-1]
    h = fire_h.shape[-1]
    fired = fire_x.sum(-1, dtype=jnp.int32) + fire_h.sum(-1, dtype=jnp.int32)
    skipped = st["skipped"] + (in_dim + h - fired)
    total = st["total"] + jnp.int32(in_dim + h)
    return skipped, total


# --------------------------------------------------------------------------
# float (QAT fake-quant) domain — the delta sibling of repro.core.gru
# --------------------------------------------------------------------------

def delta_gru_cell(
    layer: Dict[str, jnp.ndarray],
    st: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    config: GRUConfig,
    thetas: Tuple[int, int],
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """One ΔGRU step, QAT float domain: x (B, I) -> (new state, h' (B, H)).

    The gate math after the partial sums is verbatim `gru.gru_cell`
    (quantized gate outputs, ROM-faithful ordering); only the way the
    two matmul preactivations are produced differs — incrementally from
    the thresholded deltas instead of densely from x and h. The Δ·W
    products are dense `gru.qdot` calls: a skipped column is a zero
    row of Δ, counted in ``skipped`` but still multiplied.
    """
    aspec = config.act_spec
    w_i, w_h, b_i, b_h = _layer_weights(layer, config.weight_spec)
    tx, th = thetas
    scale = quant.ACT_Q6_8.scale

    dx = x - st["x_ref"]
    fire_x = jnp.abs(dx) > tx * scale
    dx = jnp.where(fire_x, dx, 0.0)
    x_ref = st["x_ref"] + dx
    acc_x = st["acc_x"] + qdot(dx, w_i)

    dh = st["h"] - st["h_ref"]
    fire_h = jnp.abs(dh) > th * scale
    dh = jnp.where(fire_h, dh, 0.0)
    h_ref = st["h_ref"] + dh
    acc_h = st["acc_h"] + qdot(dh, w_h)

    gi = _maybe_q(acc_x + b_i, aspec)  # (B, 3H)
    gh = _maybe_q(acc_h + b_h, aspec)
    i_r, i_z, i_n = jnp.split(gi, 3, axis=-1)
    h_r, h_z, h_n = jnp.split(gh, 3, axis=-1)
    sigmoid, tanh = _gate_acts(aspec)
    r = sigmoid(i_r + h_r)
    z = sigmoid(i_z + h_z)
    n = tanh(i_n + _maybe_q(r * h_n, aspec))
    h_new = _maybe_q((1.0 - z) * n + z * st["h"], aspec)

    skipped, total = _count_macs(st, fire_x, fire_h)
    new_st = {
        "h": h_new, "x_ref": x_ref, "h_ref": h_ref,
        "acc_x": acc_x, "acc_h": acc_h,
        "skipped": skipped, "total": total,
    }
    return new_st, h_new


def _fc_logits(params, x, config):
    """The dense FC head — delegated to `gru.fc_logits` (the θ=0
    bit-identity target lives in one place), with the quantized specs
    forced on: the delta engine is always quantized, like the gate
    math in `delta_gru_cell` which ignores ``config.quantized``.
    """
    if not config.quantized:
        config = dataclasses.replace(config, quantized=True)
    return fc_logits(params, x, config)


def delta_classifier_step(
    params: Dict[str, Any],
    states: List[Dict[str, jnp.ndarray]],
    fv_t: jnp.ndarray,
    config: GRUConfig,
    thetas: Tuple[Tuple[int, int], ...],
) -> Tuple[List[Dict[str, jnp.ndarray]], jnp.ndarray]:
    """Streaming ΔGRU step: one frame (B, C) -> (new states, (B, K)).

    The input is snapped to the Q6.8 grid first (a no-op for pipeline-
    produced frames, which already live on it): the delta memories MUST
    stay on the grid or the partial sums stop telescoping exactly —
    and it keeps "delta" and "delta-int" in bit-agreement for any
    input, mirroring the integer backend's entry quantization.
    """
    new_states = []
    x = quant.fake_quant(fv_t, config.act_spec)
    for layer, st, t in zip(params["gru"], states, thetas):
        st, x = delta_gru_cell(layer, st, x, config, t)
        new_states.append(st)
    return new_states, _fc_logits(params, x, config)


def delta_classifier_forward(
    params: Dict[str, Any],
    fv: jnp.ndarray,
    config: GRUConfig,
    thetas: Tuple[Tuple[int, int], ...],
    return_states: bool = False,
):
    """fv (B, T, C) -> per-frame logits (B, T, K), scanned over frames.

    ``return_states`` additionally returns the final per-layer delta
    states (whose counters give the sweep's effective-MAC fraction via
    `effective_mac_fraction`).
    """
    states = delta_init_states(config, fv.shape[0])

    def step(states, x_t):
        states, logits = delta_classifier_step(
            params, states, x_t, config, thetas
        )
        return states, logits

    states, logits = jax.lax.scan(step, states, jnp.moveaxis(fv, 1, 0))
    logits = jnp.moveaxis(logits, 0, 1)
    return (logits, states) if return_states else logits


# --------------------------------------------------------------------------
# integer code domain — the delta sibling of repro.core.gru_int
# --------------------------------------------------------------------------

def int_delta_gru_cell(
    layer: Dict[str, jnp.ndarray],
    st: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    config: GRUConfig,
    thetas: Tuple[int, int],
) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
    """One ΔGRU step on codes: x (B, I) int32 Q6.8 -> (state, h' codes).

    Gate math after the partial sums is verbatim `gru_int.int_gru_cell`;
    the frac-15 partial sums live in the persistent int32 accumulators
    (the DeltaKWS per-neuron partial-sum memory) instead of being
    recomputed densely. The Δ·W products go through the dense
    saturating-int24 `intgemm` kernel: a skipped column is a zero row
    of Δ, counted in ``skipped`` but still multiplied.
    """
    del config  # geometry is carried by the code arrays themselves
    tx, th = thetas

    dx = x - st["x_ref"]
    fire_x = jnp.abs(dx) > tx
    dx = jnp.where(fire_x, dx, 0)
    x_ref = st["x_ref"] + dx
    acc_x = st["acc_x"] + intgemm(dx, layer["w_i"])

    dh = st["h"] - st["h_ref"]
    fire_h = jnp.abs(dh) > th
    dh = jnp.where(fire_h, dh, 0)
    h_ref = st["h_ref"] + dh
    acc_h = st["acc_h"] + intgemm(dh, layer["w_h"])

    gi = quant.clip_act_codes(
        quant.round_shift_even(acc_x + layer["b_i"], _ACC_SHIFT)
    )
    gh = quant.clip_act_codes(
        quant.round_shift_even(acc_h + layer["b_h"], _ACC_SHIFT)
    )
    i_r, i_z, i_n = jnp.split(gi, 3, axis=-1)
    h_r, h_z, h_n = jnp.split(gh, 3, axis=-1)
    r = quant.lut_sigmoid_q68(i_r + h_r)
    z = quant.lut_sigmoid_q68(i_z + h_z)
    rn = quant.clip_act_codes(quant.round_shift_even(r * h_n, _ACT_SHIFT))
    n = quant.lut_tanh_q68(i_n + rn)
    h_new = quant.clip_act_codes(
        quant.round_shift_even((_ONE_Q68 - z) * n + z * st["h"], _ACT_SHIFT)
    )

    skipped, total = _count_macs(st, fire_x, fire_h)
    new_st = {
        "h": h_new, "x_ref": x_ref, "h_ref": h_ref,
        "acc_x": acc_x, "acc_h": acc_h,
        "skipped": skipped, "total": total,
    }
    return new_st, h_new


def _int_fc_logits(qparams: QuantizedClassifier, x: jnp.ndarray):
    # the dense FC head, verbatim the integer engine's accumulate path
    # (shared so the bit-identity target can never drift from here)
    return _accum(x, qparams.fc_w, qparams.fc_b)


def int_delta_classifier_step(
    qparams: QuantizedClassifier,
    states: List[Dict[str, jnp.ndarray]],
    fv_t: jnp.ndarray,
    config: GRUConfig,
    thetas: Tuple[Tuple[int, int], ...],
) -> Tuple[List[Dict[str, jnp.ndarray]], jnp.ndarray]:
    """Streaming ΔGRU step on codes: one frame (B, C) -> (states, (B, K))."""
    new_states = []
    x = fv_t
    for layer, st, t in zip(qparams.gru, states, thetas):
        st, x = int_delta_gru_cell(layer, st, x, config, t)
        new_states.append(st)
    return new_states, _int_fc_logits(qparams, x)


def int_delta_classifier_forward(
    qparams: QuantizedClassifier,
    fv_codes: jnp.ndarray,
    config: GRUConfig,
    thetas: Tuple[Tuple[int, int], ...],
    return_states: bool = False,
):
    """fv codes (B, T, C) -> per-frame logit codes (B, T, K), scanned."""
    states = int_delta_init_states(config, fv_codes.shape[0])

    def step(states, x_t):
        states, logits = int_delta_classifier_step(
            qparams, states, x_t, config, thetas
        )
        return states, logits

    states, logits = jax.lax.scan(
        step, states, jnp.moveaxis(fv_codes, 1, 0)
    )
    logits = jnp.moveaxis(logits, 0, 1)
    return (logits, states) if return_states else logits


# --------------------------------------------------------------------------
# sparsity telemetry
# --------------------------------------------------------------------------

def effective_mac_fraction(
    states: List[Dict[str, jnp.ndarray]], config: GRUConfig
) -> jnp.ndarray:
    """Per-stream effective-MAC fraction in [0, 1] from the counters.

    executed / offered over the WHOLE classifier: the delta-eligible GRU
    counters (column units, converted to MACs per layer — a layer's
    column is 3H multiplies) plus the always-dense FC head (its
    per-frame cost is folded back in from the frame count the totals
    imply). Streams with no traffic yet report 1.0 (dense — nothing has
    been skipped).

    Feeds `repro.core.energy.AcceleratorModel(effective_mac_fraction=…)`
    to turn measured sparsity into DeltaKWS-style µW/latency predictions.
    """
    dims = _layer_dims(config)
    skipped = sum(
        st["skipped"].astype(jnp.float32) * (3 * h)
        for st, (_, h) in zip(states, dims)
    )
    total = sum(
        st["total"].astype(jnp.float32) * (3 * h)
        for st, (_, h) in zip(states, dims)
    )
    per_frame = float(delta_eligible_macs_per_frame(config))
    fc = float(dense_fc_macs_per_frame(config))
    n_frames = total / per_frame
    executed = total - skipped + n_frames * fc
    offered = total + n_frames * fc
    return jnp.where(total > 0, executed / jnp.maximum(offered, 1.0), 1.0)
