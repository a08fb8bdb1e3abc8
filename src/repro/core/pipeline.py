"""End-to-end KWS pipeline assembly (Fig. 3): FEx -> classifier.

Both stages are pluggable, string-keyed backends:

  * `KWSPipelineConfig.frontend` names a registered
    `repro.core.frontend.FeatureFrontend` ("software", "hardware",
    "hardware-pallas" — see that module);
  * `KWSPipelineConfig.classifier` names a registered
    `repro.core.classifier.ClassifierBackend` ("float", "qat",
    "integer", "delta", "delta-int") — None resolves from
    ``gru.quantized``. The "integer" backend runs the bit-exact
    int8/Q6.8 engine of `repro.core.gru_int`; `prepare_params`
    converts float training params to its code pytree, and every
    classifier entry point below accepts either form. The ΔGRU
    backends ("delta"/"delta-int", `repro.core.gru_delta`) take their
    thresholds from `KWSPipelineConfig.delta` (bound to the backend at
    pipeline construction via `ClassifierBackend.with_config`).

Every feature entry point routes through the frontend:

  features(audio, state)                batch audio -> (FV_Norm, FV_Raw)
  record_features(audio, state)         batched numpy recording of
                                        FV_Raw (the Section III-F flow:
                                        the paper records features from
                                        the chip once, then trains)
  predict(params, audio, state)         features + GRU + argmax
  streaming_features_step(carry, chunk) one 16 ms raw-audio hop ->
                                        one FV_Norm frame per stream
  streaming_step(params, states, fv_t)  one GRU step per 16 ms frame

All frontend-side parameters (norm stats, chip mismatch, beta/alpha
calibration, filterbank coefficients) live in one `FrontendState`
pytree, built by `init_frontend_state` / `repro.core.calibration` and
passed to the calls above (or bound at construction time); loose
``beta``/``alpha``/``norm_stats`` positional arguments are gone.

The FV_Raw -> FV_Norm post-processing (log LUT, (x-mu)/sigma, Q6.8) is
the chip's digital back-end and is shared by every frontend
(`features_from_raw`). The classifier is always trained on features
*recorded from the chosen frontend*, exactly as the paper records FV_Raw
from the chip for its training set.
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import quant
from repro.core.classifier import (
    ClassifierBackend,
    get_classifier,
    resolve_classifier_key,
)
from repro.core.fex import FExConfig, FExNormStats
from repro.core.frontend import (
    FeatureFrontend,
    FrontendState,
    get_frontend,
)
from repro.core.gru import GRUConfig, init_gru_classifier
from repro.core.gru_delta import DeltaConfig
from repro.core.tdfex import TDFExConfig, TDFExState
from repro.serving.cascade import CascadeConfig

__all__ = [
    "KWSPipelineConfig",
    "KWSPipeline",
    "record_features_hardware",
]


@dataclasses.dataclass(frozen=True)
class KWSPipelineConfig:
    frontend: str = "software"  # registered FeatureFrontend key
    fex: FExConfig = dataclasses.field(default_factory=FExConfig)
    gru: GRUConfig = dataclasses.field(default_factory=GRUConfig)
    # Hardware-sim parameters for the "hardware*" frontends. None ->
    # TDFExConfig built around `fex` (the paper's nominal chip).
    tdfex: Optional[TDFExConfig] = None
    use_log: bool = True
    use_norm: bool = True
    # Registered ClassifierBackend key ("float" / "qat" / "integer" /
    # "delta" / "delta-int"); None resolves from gru.quantized ("qat"
    # when True else "float"), preserving the pre-registry behavior.
    classifier: Optional[str] = None
    # ΔGRU thresholds for the "delta"/"delta-int" backends
    # (`repro.core.gru_delta.DeltaConfig`; θ per layer). None -> θ=0,
    # which is bit-identical to the dense base backend. Ignored by the
    # dense backends.
    delta: Optional["DeltaConfig"] = None
    # Stage-1 wake cascade for the serving tick
    # (`repro.serving.cascade.CascadeConfig`): an always-on detector on
    # the feature frame gates the classifier per stream. None -> no
    # gate (the always-dense tick); `CascadeConfig.always_on()` is
    # bit-identical to None for every backend. Consumed only by the
    # serving layer (`StreamingKWSServer`) — batch `features`/`logits`
    # calls ignore it.
    cascade: Optional["CascadeConfig"] = None

    def __post_init__(self):
        # The pipeline post-processes (and shapes chunks) with `fex`
        # while the hardware frontends generate features with
        # `tdfex.fex`; a disagreement would surface as silently wrong
        # FV_Norm far from the misconfiguration.
        if self.tdfex is not None and self.tdfex.fex != self.fex:
            raise ValueError(
                "KWSPipelineConfig.fex and KWSPipelineConfig.tdfex.fex "
                "disagree; pass tdfex=TDFExConfig(fex=your_fex, ...)"
            )

    @property
    def tdfex_config(self) -> TDFExConfig:
        if self.tdfex is not None:
            return self.tdfex
        return TDFExConfig(fex=self.fex)

    @property
    def classifier_key(self) -> str:
        return resolve_classifier_key(self.classifier, self.gru)


class KWSPipeline:
    """Stateless-functional pipeline with convenience wrappers.

    A `FrontendState` may be bound at construction (used as the default
    for every call) or passed per call; methods never mutate it.
    """

    def __init__(
        self,
        config: KWSPipelineConfig,
        state: Optional[FrontendState] = None,
        norm_stats: Optional[FExNormStats] = None,
    ):
        self.config = config
        self.frontend: FeatureFrontend = get_frontend(config.frontend)
        # with_config binds pipeline-level backend parameters (the ΔGRU
        # thresholds of config.delta); dense backends return the
        # registry singleton unchanged.
        self.classifier: ClassifierBackend = get_classifier(
            config.classifier_key
        ).with_config(config)
        if state is None:
            state = FrontendState()
        if norm_stats is not None:
            state = state.with_norm_stats(norm_stats)
        self.state = state
        # memo for prepare_params: (params object, mesh, prepared
        # pytree). The strong reference to the keys object keeps its
        # id() from being recycled while the entry is alive.
        self._prepared: Optional[Tuple[Any, Any, Any]] = None

    @property
    def norm_stats(self) -> Optional[FExNormStats]:
        return self.state.norm_stats

    def _resolve(self, state: Optional[FrontendState]) -> FrontendState:
        return self.state if state is None else state

    # ---------- frontend state ----------

    def init_frontend_state(
        self, key: Optional[jax.Array] = None, **kwargs
    ) -> FrontendState:
        """Build this frontend's state (chip draw + beta/alpha calibration
        for the hardware paths; a no-op shell for "software"). Any bound
        norm_stats are carried over unless overridden via kwargs."""
        kwargs.setdefault("norm_stats", self.state.norm_stats)
        return self.frontend.init_state(self.config, key=key, **kwargs)

    def with_state(self, state: FrontendState) -> "KWSPipeline":
        """A copy of this pipeline with ``state`` bound as the default."""
        return KWSPipeline(self.config, state=state)

    # ---------- feature extraction ----------

    @functools.partial(jax.jit, static_argnums=(0,))
    def _features_jit(self, audio, state, key):
        fv_raw = self.frontend.raw_codes(audio, self.config, state, key=key)
        return self._postprocess(fv_raw, state), fv_raw

    def features(
        self,
        audio: jnp.ndarray,
        state: Optional[FrontendState] = None,
        key: Optional[jax.Array] = None,
    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """audio (B, T) -> (fv_norm (B, F, C), fv_raw codes), via the
        configured frontend. One call site for all registered paths."""
        return self._features_jit(audio, self._resolve(state), key)

    def features_software(self, audio: jnp.ndarray):
        """Deprecated alias kept for the pre-registry API; equivalent to
        `features` on a ``frontend="software"`` pipeline."""
        warnings.warn(
            "KWSPipeline.features_software is deprecated; use "
            "KWSPipeline.features (works for any cfg.frontend) — see "
            "the migration table in CHANGES.md",
            DeprecationWarning,
            stacklevel=2,
        )
        if self.config.frontend != "software":
            raise ValueError(
                "features_software on a "
                f"frontend={self.config.frontend!r} pipeline; "
                "use features()"
            )
        return self.features(audio)

    def _postprocess(self, fv_raw, state: FrontendState) -> jnp.ndarray:
        """FV_Raw codes -> FV_Norm: the chip's digital back-end (log ROM,
        read under the ``kws_log_rom`` scope; normalizer; Q6.8
        saturation), shared by every frontend."""
        x = fv_raw
        if self.config.use_log:
            x = quant.log_compress_lut(
                x, self.config.fex.quant_bits, self.config.fex.log_bits
            )
        if self.config.use_norm:
            if state.norm_stats is None:
                raise ValueError("use_norm requires fitted norm_stats")
            x = (x - state.norm_stats.mu) / state.norm_stats.sigma
        else:
            in_bits = (
                self.config.fex.log_bits
                if self.config.use_log
                else self.config.fex.quant_bits
            )
            x = x * 2.0 ** -(in_bits - 5)
        return quant.fake_quant(x, quant.ACT_Q6_8)

    def features_from_raw(
        self, fv_raw: jnp.ndarray, state: Optional[FrontendState] = None
    ) -> jnp.ndarray:
        """Post-processing only: recorded FV_Raw codes -> FV_Norm."""
        return self._postprocess(fv_raw, self._resolve(state))

    def record_features(
        self,
        audio: np.ndarray,
        state: Optional[FrontendState] = None,
        key: Optional[jax.Array] = None,
        batch_size: int = 64,
    ) -> np.ndarray:
        """Record FV_Raw codes in host-memory batches (Section III-F).

        Works for any frontend; the hardware paths consume ``key`` for
        their per-record noise draw (VTC noise / SRO jitter)."""
        state = self._resolve(state)
        fn = jax.jit(
            lambda a, k: self.frontend.raw_codes(
                a, self.config, state, key=k
            )
        )
        outs = []
        n = audio.shape[0]
        for i in range(0, n, batch_size):
            chunk = jnp.asarray(audio[i : i + batch_size])
            k = None
            if key is not None:
                key, k = jax.random.split(key)
            outs.append(np.asarray(fn(chunk, k)))
        return np.concatenate(outs, axis=0)

    # ---------- classifier ----------

    def init_params(self, key: jax.Array) -> Dict[str, Any]:
        """Float training params (QAT trains in float; the configured
        backend converts via `prepare_params` at inference time)."""
        return init_gru_classifier(key, self.config.gru)

    def prepare_params(self, params, mesh=None):
        """Float training params -> whatever the configured backend
        consumes (e.g. `QuantizedClassifier` integer codes for
        ``classifier="integer"``). Idempotent: already-prepared params
        pass through, so every public entry point below can call it.
        The last conversion is memoized by parameter identity, so
        per-frame callers (`streaming_step`) don't re-quantize the
        whole parameter pytree every 16 ms tick.

        ``mesh`` (a serving `stream_mesh`) places the prepared pytree
        replicated across every mesh device — weights are resident on
        each shard of a stream-parallel server, never re-transferred
        per tick."""
        if (
            self._prepared is not None
            and self._prepared[0] is params
            and self._prepared[1] is mesh
        ):
            return self._prepared[2]
        prepared = self.classifier.prepare(params, self.config.gru)
        if mesh is not None:
            from repro.distributed.sharding import replicated_shardings

            prepared = jax.device_put(
                prepared, replicated_shardings(prepared, mesh)
            )
        self._prepared = (params, mesh, prepared)
        return prepared

    @functools.partial(jax.jit, static_argnums=(0,))
    def _logits_jit(self, params, fv_norm: jnp.ndarray) -> jnp.ndarray:
        all_logits = self.classifier.forward(
            params, fv_norm, self.config.gru
        )
        return all_logits[:, -1, :]

    def logits(self, params, fv_norm: jnp.ndarray) -> jnp.ndarray:
        """(B, F, C) -> final-frame logits (B, K), via the configured
        classifier backend."""
        return self._logits_jit(self.prepare_params(params), fv_norm)

    @functools.partial(jax.jit, static_argnums=(0,))
    def _logits_all_jit(self, params, fv_norm: jnp.ndarray) -> jnp.ndarray:
        return self.classifier.forward(params, fv_norm, self.config.gru)

    def logits_all_frames(self, params, fv_norm: jnp.ndarray) -> jnp.ndarray:
        return self._logits_all_jit(self.prepare_params(params), fv_norm)

    def predict(
        self,
        params,
        audio: jnp.ndarray,
        state: Optional[FrontendState] = None,
        key: Optional[jax.Array] = None,
    ) -> jnp.ndarray:
        fv_norm, _ = self.features(audio, state, key)
        return jnp.argmax(self.logits(params, fv_norm), axis=-1)

    # ---------- streaming serving ----------

    @property
    def chunk_samples(self) -> int:
        """Raw-audio samples per 16 ms streaming hop (at fs_audio)."""
        fexc = self.config.fex
        return int(round(fexc.fs_audio * fexc.frame_shift_ms / 1000.0))

    def streaming_init(self, batch: int, mesh=None):
        """Classifier (GRU) state for a batch of streams — float32 for
        the float/qat backends, int32 Q6.8 codes for "integer".

        ``mesh`` (a serving `stream_mesh`) creates the state buffers
        already sharded over their leading stream axis — no oversized
        single-device allocation, no post-hoc reshard."""
        return self.classifier.init_states(
            self.config.gru, batch, device=self._stream_sharding(mesh)
        )

    @functools.partial(jax.jit, static_argnums=(0,))
    def _streaming_step_jit(self, params, states, fv_t: jnp.ndarray):
        return self.classifier.step(params, states, fv_t, self.config.gru)

    def streaming_step(self, params, states, fv_t: jnp.ndarray):
        """One 16 ms frame for a batch of streams -> (states, logits)."""
        return self._streaming_step_jit(
            self.prepare_params(params), states, fv_t
        )

    @staticmethod
    def _stream_sharding(mesh):
        """mesh -> NamedSharding over the leading stream axis of a
        (batch, channels) state buffer; None stays None (default
        single-device placement)."""
        if mesh is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec

        from repro.distributed.sharding import STREAM_AXIS

        return NamedSharding(mesh, PartitionSpec(STREAM_AXIS, None))

    def streaming_features_init(self, batch: int, mesh=None):
        """Frontend carry (filter / SRO phase state) for batch streams;
        ``mesh`` shards the carry over its stream axis (see
        `streaming_init`)."""
        return self.frontend.streaming_init(
            self.config, batch, device=self._stream_sharding(mesh)
        )

    def streaming_features_apply(
        self,
        carry,
        chunk: jnp.ndarray,
        state: FrontendState,
        key: Optional[jax.Array] = None,
    ):
        """Pure (unjitted) body of `streaming_features_step`: one raw
        hop (B, chunk_samples) -> (carry, fv_norm (B, C)). Safe to call
        from inside a larger jit — the fused serving tick
        (`repro.serving.serve_loop`) inlines it so frontend + classifier
        + smoothing compile as one program."""
        carry, fv_raw = self.frontend.streaming_step(
            chunk, self.config, state, carry, key=key
        )
        fv_norm = self._postprocess(fv_raw[:, None, :], state)[:, 0, :]
        return carry, fv_norm

    def streaming_logits_apply(self, params, states, fv_t: jnp.ndarray):
        """Pure (unjitted) body of `streaming_step`, for fusing callers.

        ``params`` must already be backend-shaped (`prepare_params`);
        the fused serving tick prepares once at server construction."""
        return self.classifier.step(params, states, fv_t, self.config.gru)

    @functools.partial(jax.jit, static_argnums=(0,))
    def _sfeatures_jit(self, carry, chunk, state, key):
        carry, fv_raw = self.frontend.streaming_step(
            chunk, self.config, state, carry, key=key
        )
        fv_norm = self._postprocess(fv_raw[:, None, :], state)[:, 0, :]
        return carry, fv_norm, fv_raw

    def streaming_features_step(
        self,
        carry,
        chunk: jnp.ndarray,
        state: Optional[FrontendState] = None,
        key: Optional[jax.Array] = None,
    ):
        """One raw-audio hop (B, chunk_samples) -> (carry, fv_norm (B, C)).

        Feed consecutive 16 ms hops; the carry holds per-stream filter
        and SRO-phase state so the concatenated stream matches the batch
        `features` path (up to the documented chunk-edge approximation
        of the 2x oversampler)."""
        carry, fv_norm, _ = self._sfeatures_jit(
            carry, chunk, self._resolve(state), key
        )
        return carry, fv_norm


def record_features_hardware(
    audio: np.ndarray,
    tdcfg: TDFExConfig,
    chip: Optional[TDFExState],
    beta: jnp.ndarray,
    alpha: jnp.ndarray,
    key: Optional[jax.Array] = None,
    batch_size: int = 64,
) -> np.ndarray:
    """Deprecated shim for the pre-registry API: record FV_Raw from the
    hardware sim. Use ``KWSPipeline(KWSPipelineConfig(frontend="hardware",
    ...)).record_features(audio, state)`` instead."""
    warnings.warn(
        "record_features_hardware is deprecated; use "
        'KWSPipeline(KWSPipelineConfig(frontend="hardware", tdfex=...), '
        "state=hardware_state(...)).record_features(audio, key=...) — "
        "see the migration table in CHANGES.md",
        DeprecationWarning,
        stacklevel=2,
    )
    from repro.core.frontend import hardware_state

    cfg = KWSPipelineConfig(
        frontend="hardware", fex=tdcfg.fex, tdfex=tdcfg
    )
    state = hardware_state(tdcfg, chip, beta=beta, alpha=alpha)
    return KWSPipeline(cfg).record_features(
        audio, state, key=key, batch_size=batch_size
    )
