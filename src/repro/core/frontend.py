"""Pluggable feature-extraction frontends for the KWS pipeline.

The paper's core contribution is a *swappable* analog front-end: the
time-domain FEx (Section III) feeds the same GRU classifier that a
conventional voltage-domain FEx would. This module makes that axis a
first-class API: every way of turning raw audio into FV_Raw quantizer
codes is a `FeatureFrontend` registered under a string key (mirroring
`repro.models.registry`):

  "software"        — the differentiable Section II model
                      (`repro.core.fex`); used for QAT training and the
                      Fig. 2 ablation.
  "hardware"        — the Section III behavioral time-domain simulation
                      (`repro.core.tdfex`): VTC distortion/noise,
                      mismatched Rec-BPF, SRO DeltaSigma TDC, and the
                      beta/alpha calibration of Section III-F.
  "hardware-pallas" — the same signal chain with the TDC stage served by
                      the fused Pallas kernel (`repro.kernels.tdc`),
                      auto-dispatching pallas / interpret / reference
                      per backend and batch shape.

All per-frontend parameters travel in one `FrontendState` pytree (norm
stats, chip mismatch draw, beta/alpha calibration, filterbank coeffs) so
`KWSPipeline.features(audio, state)` is one call site for every path and
the state can cross `jax.jit` boundaries as a regular traced argument.

Streaming: each frontend also exposes a chunked step that consumes one
16 ms raw-audio hop per call and carries filter / phase state across
calls, so `StreamingKWSServer` can accept raw audio instead of
precomputed FV_Norm frames. The only deviation from the batch path is at
chunk edges: the 2x linear-interpolation oversampler needs one sample of
lookahead, which streaming replaces with edge replication (one internal
sample per 512-sample frame; well below one FV_Raw LSB for band-limited
audio).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import quant
from repro.core.fex import (
    FExNormStats,
    biquad_filterbank_frame_mean,
    biquad_filterbank_streaming,
    fex_frames,
    frame_average,
    oversample2x,
)
from repro.core.tdfex import (
    TDFExConfig,
    TDFExState,
    counts_to_fv_raw,
    design_mismatched_filterbank,
    draw_chip,
    sro_tdc,
    vtc,
)

__all__ = [
    "FrontendState",
    "FeatureFrontend",
    "register_frontend",
    "get_frontend",
    "available_frontends",
    "hardware_state",
    "masked_select",
    "SoftwareFrontend",
    "HardwareFrontend",
    "HardwarePallasFrontend",
]


def masked_select(mask: jnp.ndarray, new_tree: Any, old_tree: Any) -> Any:
    """Per-stream pytree select: leaves lead with the stream axis, and
    stream ``i`` takes ``new`` where ``mask[i]`` else keeps ``old``.

    This is how a batched streaming carry (or GRU state / score buffer)
    advances only for streams that submitted a frame this tick — the
    temporal-sparsity contract of frame-synchronous serving: an idle
    stream's state must be bit-identical before and after the tick.

    Sharding-transparent: when the leaves (and the mask) are sharded
    over their leading stream axis, the select is purely elementwise
    per slot, so SPMD partitioning inserts no collectives and the
    contract holds per shard — the broadcast below only ever expands
    replicated (non-stream) trailing dims.
    """
    mask = jnp.asarray(mask)

    def sel(new, old):
        m = jnp.expand_dims(mask, tuple(range(mask.ndim, new.ndim)))
        return jnp.where(m, new, old)

    return jax.tree_util.tree_map(sel, new_tree, old_tree)


# --------------------------------------------------------------------------
# State pytrees
# --------------------------------------------------------------------------

def _register_dataclass_pytree(cls, data_fields):
    """Make a frozen dataclass a jax pytree (all listed fields are leaves)."""
    jax.tree_util.register_dataclass(
        cls, data_fields=list(data_fields), meta_fields=[]
    )


# FExNormStats / TDFExState predate this module; register them here so a
# FrontendState holding them is itself a valid traced argument.
_register_dataclass_pytree(FExNormStats, ("mu", "sigma"))
_register_dataclass_pytree(TDFExState, ("gain_mismatch", "cf_mismatch"))


@dataclasses.dataclass(frozen=True)
class FrontendState:
    """Everything a frontend needs beyond the static config, as one pytree.

    norm_stats — mu/sigma of FV_Log over the training set (Section III-F);
                 required whenever the pipeline's ``use_norm`` is on.
    chip       — per-die mismatch realization (hardware frontends only).
    beta       — per-channel offset calibration: free-running SRO
                 counts/frame (Fig. 13's programmable subtractor).
    alpha      — per-channel gain calibration (Fig. 17a -> 17b).
    coeffs     — stacked (5, C) Rec-BPF biquad coefficients. Designed
                 once (including any cf mismatch) when the state is
                 built, because filter design is numpy-only and must not
                 run under a jit trace. None -> the nominal filterbank.

    Fields irrelevant to a given frontend stay None; None sub-trees are
    valid (empty) pytree nodes, so any FrontendState crosses jit.
    """

    norm_stats: Optional[FExNormStats] = None
    chip: Optional[TDFExState] = None
    beta: Optional[jnp.ndarray] = None
    alpha: Optional[jnp.ndarray] = None
    coeffs: Optional[jnp.ndarray] = None

    def with_norm_stats(self, norm_stats: Optional[FExNormStats]):
        return dataclasses.replace(self, norm_stats=norm_stats)


_register_dataclass_pytree(
    FrontendState, ("norm_stats", "chip", "beta", "alpha", "coeffs")
)


# --------------------------------------------------------------------------
# Protocol + registry
# --------------------------------------------------------------------------

class FeatureFrontend:
    """One feature path: raw audio -> FV_Raw quantizer codes.

    Implementations are stateless singletons (all run-time state lives in
    `FrontendState` / the streaming carry), so they are safe to close
    over in jit'd functions. Subclasses implement:

      init_state(cfg, key)            -> FrontendState (calibration etc.)
      raw_codes(audio, cfg, state, key) -> (B, F, C) FV_Raw codes
      streaming_init(cfg, batch, device=None)
                                      -> carry pytree (dict of arrays);
                                      ``device`` (Device or Sharding)
                                      places the buffers at creation —
                                      sharded servers pass a stream-axis
                                      NamedSharding
      streaming_step(chunk, cfg, state, carry, key)
                                      -> (carry, (B, C) FV_Raw frame)

    ``cfg`` is the `KWSPipelineConfig`; frontends read ``cfg.fex`` and
    ``cfg.tdfex_config`` from it. The shared FV_Raw -> FV_Norm
    post-processing (log LUT, normalizer, Q6.8) stays in the pipeline —
    it is the chip's digital back-end and identical for every frontend.
    """

    name: str = "?"
    #: True when raw_codes is differentiable end-to-end (QAT training).
    differentiable: bool = False

    def init_state(
        self,
        cfg,
        key: Optional[jax.Array] = None,
        norm_stats: Optional[FExNormStats] = None,
        **kwargs,
    ) -> FrontendState:
        raise NotImplementedError

    def raw_codes(
        self,
        audio: jnp.ndarray,
        cfg,
        state: FrontendState,
        key: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        raise NotImplementedError

    def streaming_init(
        self, cfg, batch: int, device: Any = None
    ) -> Dict[str, jnp.ndarray]:
        raise NotImplementedError

    def streaming_step(
        self,
        chunk: jnp.ndarray,
        cfg,
        state: FrontendState,
        carry: Dict[str, jnp.ndarray],
        key: Optional[jax.Array] = None,
    ) -> Tuple[Dict[str, jnp.ndarray], jnp.ndarray]:
        raise NotImplementedError


_REGISTRY: Dict[str, FeatureFrontend] = {}


def register_frontend(name: str):
    """Class decorator: instantiate + register under ``name``."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def get_frontend(name: str) -> FeatureFrontend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown frontend {name!r}; registered frontends: "
            f"{sorted(_REGISTRY)}"
        ) from None


def available_frontends() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


# --------------------------------------------------------------------------
# Shared streaming helpers
# --------------------------------------------------------------------------

def _chunk_to_internal(chunk: jnp.ndarray, fexc) -> jnp.ndarray:
    """One raw-audio hop (B, S @ fs_audio) -> internal rate (B, frame_len).

    Edge-replicated 2x oversampling (see module docstring for the
    one-sample boundary approximation vs the batch path).
    """
    if fexc.oversample == 2:
        chunk = oversample2x(chunk)
    return chunk


def _nominal_coeffs(cfg, state: FrontendState) -> jnp.ndarray:
    if state is not None and state.coeffs is not None:
        return state.coeffs
    if state is not None and state.chip is not None:
        # The chip's cf mismatch lives in the filterbank design, which is
        # numpy-only and cannot run under a jit trace — refusing here
        # beats silently simulating a mismatch-free filterbank.
        raise ValueError(
            "FrontendState has a chip (cf mismatch) but no designed "
            "coeffs; build the state via init_frontend_state / "
            "calibrate_state / hardware_state instead of by hand"
        )
    return cfg.fex.filterbank().stacked(dtype=jnp.float32)


def hardware_state(
    tdcfg: TDFExConfig,
    chip: Optional[TDFExState] = None,
    beta: Optional[jnp.ndarray] = None,
    alpha: Optional[jnp.ndarray] = None,
    norm_stats: Optional[FExNormStats] = None,
) -> FrontendState:
    """Assemble a hardware-frontend state, designing the (possibly
    mismatched) Rec-BPF coefficients once. beta/alpha default to the
    nominal offset / unity gain (an uncalibrated die)."""
    c = tdcfg.fex.num_channels
    if beta is None:
        beta = jnp.full((c,), tdcfg.beta_nominal, jnp.float32)
    if alpha is None:
        alpha = jnp.ones((c,), jnp.float32)
    return FrontendState(
        norm_stats=norm_stats,
        chip=chip,
        beta=jnp.asarray(beta),
        alpha=jnp.asarray(alpha),
        coeffs=design_mismatched_filterbank(tdcfg, chip).stacked(
            dtype=jnp.float32
        ),
    )


# --------------------------------------------------------------------------
# software — differentiable Section II model
# --------------------------------------------------------------------------

@register_frontend("software")
class SoftwareFrontend(FeatureFrontend):
    """Pure-jnp voltage-domain model: BPF -> |.| -> frame mean -> 12-bit
    quantizer (straight-through estimator), end-to-end differentiable."""

    differentiable = True

    def init_state(self, cfg, key=None, norm_stats=None, **kwargs):
        del key, kwargs  # nothing to calibrate in the ideal model
        return FrontendState(norm_stats=norm_stats)

    def raw_codes(self, audio, cfg, state, key=None):
        del key  # the software model is noiseless
        fexc = cfg.fex
        if state is not None and state.coeffs is not None:
            x = oversample2x(audio) if fexc.oversample == 2 else audio
            y, _ = biquad_filterbank_streaming(x, state.coeffs)
            frames = frame_average(jnp.abs(y), fexc.frame_len)
        else:
            frames = fex_frames(audio, fexc)
        return quant.quantize_unsigned(
            frames, fexc.quant_bits, fexc.quant_full_scale
        )

    def streaming_init(self, cfg, batch, device=None):
        c = cfg.fex.num_channels
        # distinct buffers per leaf: the serving tick donates the whole
        # carry, and a shared zeros buffer cannot be donated twice
        z = lambda: jnp.zeros(  # noqa: E731
            (batch, c), jnp.float32, device=device
        )
        return {"s1": z(), "s2": z()}

    def streaming_step(self, chunk, cfg, state, carry, key=None):
        del key
        fexc = cfg.fex
        x = _chunk_to_internal(chunk, fexc)
        # in-scan rectified mean: the serving tick's hot path never
        # materializes the (B, frame_len, C) filter output
        frame, (s1, s2) = biquad_filterbank_frame_mean(
            x, _nominal_coeffs(cfg, state), (carry["s1"], carry["s2"])
        )
        codes = quant.quantize_unsigned(
            frame, fexc.quant_bits, fexc.quant_full_scale
        )
        return {"s1": s1, "s2": s2}, codes


# --------------------------------------------------------------------------
# hardware — behavioral time-domain simulation (Section III)
# --------------------------------------------------------------------------

class _HardwareBase(FeatureFrontend):
    """Shared VTC -> Rec-BPF -> (TDC) -> beta/alpha signal chain; the TDC
    stage itself is provided by `_counts`."""

    def init_state(
        self,
        cfg,
        key=None,
        norm_stats=None,
        mismatch: bool = True,
        calibrate: bool = True,
        **kwargs,
    ):
        """Build a calibrated per-die state (Section III-F flow).

        key + mismatch=True draws a fresh chip (gain/cf mismatch);
        calibrate=True measures beta (zero input) and alpha (reference
        tones) exactly like the bench flow in `repro.core.calibration`.
        """
        del kwargs
        tdcfg = cfg.tdfex_config
        chip = None
        if key is not None and mismatch:
            key, k_chip = jax.random.split(key)
            chip = draw_chip(k_chip, tdcfg)
        beta = alpha = None  # hardware_state defaults: uncalibrated die
        if calibrate:
            from repro.core.calibration import calibrate_chip

            beta, alpha = calibrate_chip(tdcfg, chip, key=key)
        return hardware_state(
            tdcfg, chip, beta=beta, alpha=alpha, norm_stats=norm_stats
        )

    # --- TDC stage, overridden by the Pallas variant ---
    def _counts(self, rect, tdcfg, chip, key):
        return sro_tdc(rect, tdcfg, chip, key)

    def _calibration(self, tdcfg, state: FrontendState):
        if state is None or state.beta is None:
            beta = jnp.float32(tdcfg.beta_nominal)
        else:
            beta = state.beta
        alpha = (
            jnp.float32(1.0)
            if state is None or state.alpha is None
            else state.alpha
        )
        return beta, alpha

    def raw_codes(self, audio, cfg, state, key=None):
        tdcfg = cfg.tdfex_config
        if key is not None:
            k_vtc, k_tdc = jax.random.split(key)
        else:
            k_vtc = k_tdc = None
        duty = vtc(audio, tdcfg, k_vtc)
        y, _ = biquad_filterbank_streaming(
            duty, _nominal_coeffs(cfg, state)
        )
        rect = jnp.abs(y)
        chip = state.chip if state is not None else None
        counts = self._counts(rect, tdcfg, chip, k_tdc)
        beta, alpha = self._calibration(tdcfg, state)
        return counts_to_fv_raw(counts, tdcfg, beta, alpha)

    def streaming_init(self, cfg, batch, device=None):
        c = cfg.fex.num_channels
        # distinct buffers per leaf (donation-safe, see SoftwareFrontend)
        z = lambda: jnp.zeros(  # noqa: E731
            (batch, c), jnp.float32, device=device
        )
        # r: fractional phase carry of the 15-phase counter (counts);
        # j: the previous frame-edge phase jitter (counts), so keyed
        # streaming reproduces the batch path's SRO phase noise.
        return {"s1": z(), "s2": z(), "r": z(), "j": z()}

    def streaming_step(self, chunk, cfg, state, carry, key=None):
        tdcfg = cfg.tdfex_config
        if key is not None:
            k_vtc, k_jit = jax.random.split(key)
        else:
            k_vtc = k_jit = None
        duty = vtc(chunk, tdcfg, k_vtc)
        y, (s1, s2) = biquad_filterbank_streaming(
            duty, _nominal_coeffs(cfg, state), (carry["s1"], carry["s2"])
        )
        rect = jnp.abs(y)  # (B, frame_len, C)
        gain = 1.0
        if state is not None and state.chip is not None:
            gain = 1.0 + state.chip.gain_mismatch
        f_inst = jnp.maximum(
            (tdcfg.f_free_hz + tdcfg.k_sro_hz * rect) * gain, 0.0
        )
        # The per-tick floor increments telescope within a frame, so one
        # hop needs only the summed phase and the fractional carry r:
        # counts = floor(r + sum(P f dt)); r' = frac(...). ZOH over the
        # os TDC ticks per sample contributes a factor of os.
        delta = (
            tdcfg.n_phases
            * tdcfg.tdc_oversample
            / tdcfg.f_tdc
            * f_inst.sum(axis=-2)
        )  # (B, C)
        # SRO phase jitter: in the batch path only the jitter at the two
        # frame-edge ticks survives the telescoping, so one draw per
        # frame (scaled to counts) reproduces its per-frame statistics.
        j = carry["j"]
        if k_jit is not None and tdcfg.phase_noise_rms > 0:
            j = tdcfg.n_phases * tdcfg.phase_noise_rms * jax.random.normal(
                k_jit, delta.shape, delta.dtype
            )
        tot = carry["r"] + delta + (j - carry["j"])
        counts = jnp.floor(tot)
        r = tot - counts
        beta, alpha = self._calibration(tdcfg, state)
        codes = counts_to_fv_raw(
            counts[:, None, :], tdcfg, beta, alpha
        )[:, 0, :]
        return {"s1": s1, "s2": s2, "r": r, "j": j}, codes


@register_frontend("hardware")
class HardwareFrontend(_HardwareBase):
    """Behavioral chip simulation with the jnp cumsum/floor TDC."""


@register_frontend("hardware-pallas")
class HardwarePallasFrontend(_HardwareBase):
    """Same signal chain, TDC served by the fused Pallas kernel
    (`repro.kernels.tdc`), auto-dispatching pallas / interpret /
    reference per backend and batch shape. SRO phase jitter
    (``phase_noise_rms``) is not modeled inside the kernel."""

    def _counts(self, rect, tdcfg, chip, key):
        del key  # kernel path is deterministic
        from repro.kernels.tdc import tdc_counts

        return tdc_counts(rect, tdcfg, chip)
