"""Cascaded always-on wake serving: a stage-1 detector gating the GRU.

The Sub-mW MCU KWS cascade (Cerutti et al., PAPERS.md): at serving
scale most always-on streams are silence, so a tiny first-stage
detector runs on every 16 ms feature frame and *wakes* the expensive
GRU classifier only on candidate speech. This module is the stage-1
subsystem the fused serving tick (`repro.serving.serve_loop`)
composes with ANY registered classifier backend (float / qat /
integer / delta / delta-int):

  * `CascadeConfig` — detector kind, wake/release thresholds
    (hysteresis), hangover frames, gated-tick score decay. Bound to a
    pipeline via `KWSPipelineConfig.cascade`.
  * `detector_scores` — per-frame nonnegative wake scores from the
    16-channel FV_Norm frame: an energy/VAD gate (`"energy"`) or a
    tiny trainable linear scorer (`"linear"`, a BNN-style single
    neuron fit by `fit_linear_detector`).
  * `init_state` / `gate_step` / `wake_rate` — the per-stream detector
    state machine (awake latch, hangover countdown, woken/ticks
    counters) that rides `ServerState` through donation, the jitted
    slot reset, and the ``("stream",)`` mesh like every other leaf.

Hard contract (tests/test_cascade.py, tests/test_serve_sharded.py):
both detectors produce scores >= 0, so ``wake_threshold=0``
(`CascadeConfig.always_on()`) opens the gate on every submitted tick
and the cascaded server is BIT-identical to the non-cascaded one for
every backend — the gate mask degenerates to the submitted mask and
the classifier arithmetic is untouched.

Like the ΔGRU engine, the gate is *modeled* sparsity on SPMD hardware:
the masked-out classifier work still executes under `jnp.where`, and
the energy story lives in `AcceleratorModel.duty_cycle`
(`repro.core.energy`), which composes the measured `srv.wake_rate`
with the ΔGRU `effective_mac_fraction` to predict IC µW
(benchmarks/fig_cascade_roc.py).

This module is deliberately free of serving/pipeline imports (jax
only) so `repro.core.pipeline` can host the config without a cycle.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = [
    "CascadeConfig",
    "DETECTORS",
    "detector_scores",
    "init_state",
    "gate_step",
    "wake_rate",
    "fit_linear_detector",
]

DETECTORS = ("energy", "linear")


@dataclasses.dataclass(frozen=True)
class CascadeConfig:
    """Stage-1 wake-gate configuration (hashable; closed over in jit).

    detector          "energy": mean over channels of relu(FV_Norm) —
                      silence-normalized features sit below the corpus
                      mean, so silence scores ~0 and speech positive.
                      "linear": sigmoid(fv @ linear_w + linear_b), a
                      trainable single-neuron scorer in [0, 1] (fit
                      with `fit_linear_detector`).
    wake_threshold    score >= wake_threshold turns the awake latch on.
                      Both detectors are nonnegative by construction,
                      so 0.0 means the gate is structurally always
                      open (`always_open`) — the bit-identity anchor.
    release_threshold score < release_threshold turns the latch off
                      (hysteresis band; must satisfy
                      0 <= release <= wake). None -> wake_threshold,
                      i.e. no band.
    hangover_frames   extra ticks the classifier keeps running after
                      the latch drops (VAD hangover: bridges short
                      intra-utterance pauses and lets the smoothed
                      posterior settle).
    score_decay       per-gated-tick multiplier on the smoothed
                      posterior of a stream the gate held asleep
                      (in [0, 1]; 1.0 = frozen hold). Decaying toward
                      zero ("silence") forgets a stale detection while
                      the classifier is not running.
    """

    detector: str = "energy"
    wake_threshold: float = 0.0
    release_threshold: Optional[float] = None
    hangover_frames: int = 0
    score_decay: float = 1.0
    # "linear" detector parameters — a weight per feature channel plus
    # a bias. Stored as a tuple of floats so the config stays hashable
    # (it is closed over statically by the fused tick's jit).
    linear_w: Optional[Tuple[float, ...]] = None
    linear_b: float = 0.0

    def __post_init__(self):
        if self.detector not in DETECTORS:
            raise ValueError(
                f"unknown cascade detector {self.detector!r}; "
                f"registered: {DETECTORS}"
            )
        if self.wake_threshold < 0.0:
            raise ValueError(
                "wake_threshold must be >= 0 (detector scores are "
                f"nonnegative); got {self.wake_threshold}"
            )
        if self.release_threshold is not None and not (
            0.0 <= self.release_threshold <= self.wake_threshold
        ):
            raise ValueError(
                "release_threshold must satisfy 0 <= release <= wake "
                f"({self.wake_threshold}); got {self.release_threshold}"
            )
        if self.hangover_frames < 0:
            raise ValueError(
                f"hangover_frames must be >= 0; got {self.hangover_frames}"
            )
        if not 0.0 <= self.score_decay <= 1.0:
            raise ValueError(
                f"score_decay must be in [0, 1]; got {self.score_decay}"
            )
        if self.detector == "linear":
            if self.linear_w is None:
                raise ValueError(
                    "detector='linear' needs linear_w (and linear_b); "
                    "fit them with cascade.fit_linear_detector"
                )
            object.__setattr__(
                self, "linear_w", tuple(float(w) for w in self.linear_w)
            )

    @classmethod
    def always_on(cls, **kwargs) -> "CascadeConfig":
        """A gate that is structurally always open (wake_threshold=0):
        the cascaded server is bit-identical to the plain one."""
        return cls(wake_threshold=0.0, **kwargs)

    @property
    def always_open(self) -> bool:
        """True when every submitted tick wakes the classifier: both
        detectors score >= 0, so threshold 0 never gates."""
        return self.wake_threshold <= 0.0

    @property
    def release(self) -> float:
        return (
            self.wake_threshold
            if self.release_threshold is None
            else self.release_threshold
        )


def detector_scores(fv: jnp.ndarray, config: CascadeConfig) -> jnp.ndarray:
    """Stage-1 wake scores for FV_Norm frames, shape (..., C) -> (...).

    Nonnegative for every input (the `always_open` contract):
      * "energy": mean(relu(fv)) over channels. FV_Norm is
        (x - mu) / sigma per channel, so silence — the bottom of the
        corpus log-energy range — is strongly negative and scores ~0,
        while speech lifts channels above the corpus mean.
      * "linear": sigmoid(fv @ w + b) in [0, 1].
    """
    if config.detector == "energy":
        return jnp.mean(jax.nn.relu(fv), axis=-1)
    w = jnp.asarray(config.linear_w, jnp.float32)
    b = jnp.float32(config.linear_b)
    z = jnp.matmul(fv, w, precision=jax.lax.Precision.HIGHEST)
    return jax.nn.sigmoid(z + b)


def init_state(batch: int, device=None) -> Dict[str, jnp.ndarray]:
    """Fresh per-stream detector state, all (batch,) leaves.

    All-zeros is the valid fresh state (asleep, no hangover, zero
    counters) — the invariant the jitted slot reset relies on
    (`_reset_slot` writes plain zeros into the reused slot's slice).

    awake  — the hysteresis latch (score crossed wake and has not yet
             dropped below release).
    hang   — remaining hangover ticks after the latch dropped.
    woken  — ticks the gate let the classifier advance (int32).
    ticks  — submitted ticks seen (int32; wraps after ~397 days of
             16 ms ticks, like the ΔGRU column counters).
    """
    z = dict(device=device) if device is not None else {}
    return {
        "awake": jnp.zeros((batch,), bool, **z),
        "hang": jnp.zeros((batch,), jnp.int32, **z),
        "woken": jnp.zeros((batch,), jnp.int32, **z),
        "ticks": jnp.zeros((batch,), jnp.int32, **z),
    }


def gate_step(
    state: Dict[str, jnp.ndarray],
    score: jnp.ndarray,
    config: CascadeConfig,
):
    """Advance the detector state machine one tick; return (state, gate).

    gate (bool, per stream) is True where the classifier runs this
    tick: the awake latch is on, or the hangover countdown is still
    draining. The caller applies its submitted mask on top (an idle
    stream's detector state must not advance — `masked_select`).
    """
    above = score >= config.wake_threshold
    below = score < config.release
    # hysteresis latch: set on wake crossing, hold until release
    # crossing (release == wake degenerates to awake = above)
    awake = jnp.logical_or(
        above, jnp.logical_and(state["awake"], jnp.logical_not(below))
    )
    gate = jnp.logical_or(awake, state["hang"] > 0)
    hang = jnp.where(
        awake,
        jnp.int32(config.hangover_frames),
        jnp.maximum(state["hang"] - 1, 0),
    )
    new_state = {
        "awake": awake,
        "hang": hang,
        "woken": state["woken"] + gate.astype(jnp.int32),
        "ticks": state["ticks"] + jnp.int32(1),
    }
    return new_state, gate


def wake_rate(state: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Fraction of submitted ticks the gate woke the classifier,
    per stream; 1.0 for slots that have seen no traffic (mirroring
    `srv.sparsity`: "no evidence" reads as dense)."""
    ticks = state["ticks"].astype(jnp.float32)
    woken = state["woken"].astype(jnp.float32)
    return jnp.where(state["ticks"] > 0, woken / jnp.maximum(ticks, 1.0), 1.0)


def fit_linear_detector(
    speech_fv,
    silence_fv,
    steps: int = 200,
    lr: float = 0.5,
) -> Tuple[Tuple[float, ...], float]:
    """Fit the "linear" detector: logistic regression speech-vs-silence
    on FV_Norm frames.

    speech_fv / silence_fv: (..., C) frame stacks (any leading shape);
    returns (linear_w tuple, linear_b) ready for `CascadeConfig`.
    Full-batch gradient descent — the model is C+1 scalars, so this is
    a few thousand FLOPs per step.
    """
    speech = jnp.asarray(speech_fv, jnp.float32)
    silence = jnp.asarray(silence_fv, jnp.float32)
    n_ch = speech.shape[-1]
    if silence.shape[-1] != n_ch:
        raise ValueError(
            f"channel mismatch: speech C={n_ch}, silence C={silence.shape[-1]}"
        )
    xs = jnp.concatenate(
        [speech.reshape(-1, n_ch), silence.reshape(-1, n_ch)]
    )
    ys = jnp.concatenate(
        [
            jnp.ones((speech.reshape(-1, n_ch).shape[0],), jnp.float32),
            jnp.zeros((silence.reshape(-1, n_ch).shape[0],), jnp.float32),
        ]
    )

    def loss(wb):
        w, b = wb
        z = xs @ w + b
        # binary cross-entropy on logits: softplus(z) - y*z
        return jnp.mean(jax.nn.softplus(z) - ys * z)

    grad = jax.jit(jax.grad(loss))
    w = jnp.zeros((n_ch,), jnp.float32)
    b = jnp.float32(0.0)
    for _ in range(steps):
        gw, gb = grad((w, b))
        w = w - lr * gw
        b = b - lr * gb
    return tuple(float(v) for v in np.asarray(w)), float(b)
