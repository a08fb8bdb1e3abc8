"""Pluggable classifier backends for the KWS pipeline.

The paper's classifier is an integer machine — 8-bit weight memory,
Q6.8 activations, 24-bit accumulators — trained in float with QAT.
This module makes that execution axis a first-class API, exactly
mirroring `repro.core.frontend`: every way of evaluating the GRU-FC
network is a `ClassifierBackend` registered under a string key,
selected via `KWSPipelineConfig.classifier`:

  "float"   — plain float32 forward (no fake-quant); the ablation /
              debugging path.
  "qat"     — the quantization-aware fake-quant forward of
              `repro.core.gru` (8-bit weights, Q6.8 activations via
              straight-through estimators); training and the default
              inference path.
  "integer" — the bit-exact integer engine of `repro.core.gru_int`:
              parameters as int8/int32 codes
              (`repro.serving.quantize.quantize_classifier`), matmuls
              through the saturating-int24 `intgemm` kernel,
              sigmoid/tanh as Q6.8 LUTs. Bit-identical to "qat" on the
              same parameters (tests/test_classifier_int.py) while
              keeping weights WMEM-resident — the serving path.
  "delta"   — the temporal-sparsity ΔGRU engine of
              `repro.core.gru_delta` in the QAT float domain: per-layer
              last-transmitted input/state memories + partial-sum
              accumulators, thresholded deltas (θ per layer via
              `KWSPipelineConfig.delta`, a `gru_delta.DeltaConfig`),
              per-stream skipped/total MAC counters. θ=0 is
              BIT-identical to "qat" (tests/test_gru_delta.py).
  "delta-int" — the same ΔGRU engine layered on the "integer" codes
              (int8 weights through `intgemm`, int32 Q6.8 state and
              frac-15 accumulators). θ=0 is BIT-identical to "integer".

The backend boundary speaks float FV_Norm frames in and float logits
out for every backend, so softmax / smoothing / argmax downstream are
backend-agnostic; the integer backend converts at the boundary (exact
in both directions: inputs arrive on the Q6.8 grid from the pipeline's
post-processing, and logit codes dequantize to exact float32).

Hidden state is backend-owned: `init_states` returns float32 leaves
for "float"/"qat" and int32 code leaves for "integer", and the fused
serving tick (`repro.serving.tick.ServerState`) carries whichever
it is through donation without caring.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp

from repro.core.gru import (
    GRUConfig,
    gru_classifier_forward,
    gru_classifier_step,
    init_states,
)

__all__ = [
    "ClassifierBackend",
    "register_classifier",
    "get_classifier",
    "available_classifiers",
    "resolve_classifier_key",
    "FloatClassifier",
    "QATClassifier",
    "IntegerClassifier",
    "DeltaClassifier",
    "DeltaIntClassifier",
]


class ClassifierBackend:
    """One execution path of the GRU-FC classifier.

    Implementations are stateless singletons (all run-time state lives
    in the params pytree and the per-stream hidden states), safe to
    close over in jit'd functions. Subclasses implement:

      prepare(params, cfg)        float training params -> the pytree
                                  this backend consumes (idempotent:
                                  already-prepared params pass through)
      init_states(cfg, batch, device=None)
                                  per-layer hidden state leaves;
                                  ``device`` (a Device or Sharding)
                                  places them at creation — sharded
                                  servers pass a stream-axis
                                  NamedSharding
      forward(params, fv, cfg)    (B, T, C) float FV_Norm ->
                                  (B, T, K) float logits
      step(params, states, fv_t, cfg)
                                  one frame (B, C) ->
                                  (new states, (B, K) float logits)

    ``cfg`` is the `GRUConfig`. The quantization mode is the backend's
    identity, so each backend forces its own ``cfg.quantized`` and the
    flag a caller set on the config is ignored here (the pipeline
    resolves the default backend FROM that flag instead).
    """

    name: str = "?"
    #: True when forward is differentiable (training-capable).
    differentiable: bool = False

    def prepare(self, params: Any, cfg: GRUConfig) -> Any:
        return params

    def with_config(self, pipeline_config: Any) -> "ClassifierBackend":
        """Hook for backends parameterized by pipeline-level config
        beyond the `GRUConfig` (the ΔGRU thresholds live on
        `KWSPipelineConfig.delta`). The registry hands out stateless
        singletons; a backend that needs per-pipeline configuration
        returns a configured copy here. Default: the singleton itself.
        """
        return self

    def init_states(
        self, cfg: GRUConfig, batch: int, device: Any = None
    ) -> List[jnp.ndarray]:
        raise NotImplementedError

    def forward(self, params, fv: jnp.ndarray, cfg: GRUConfig):
        raise NotImplementedError

    def step(self, params, states, fv_t: jnp.ndarray, cfg: GRUConfig):
        raise NotImplementedError


_REGISTRY: Dict[str, ClassifierBackend] = {}


def register_classifier(name: str):
    """Class decorator: instantiate + register under ``name``."""

    def deco(cls):
        cls.name = name
        _REGISTRY[name] = cls()
        return cls

    return deco


def get_classifier(name: str) -> ClassifierBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown classifier {name!r}; registered classifiers: "
            f"{sorted(_REGISTRY)}"
        ) from None


def available_classifiers() -> Tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def resolve_classifier_key(
    classifier: Optional[str], gru: GRUConfig
) -> str:
    """None -> the backend the pre-registry pipeline implied: "qat"
    when ``gru.quantized`` else "float". Explicit keys win."""
    if classifier is not None:
        return classifier
    return "qat" if gru.quantized else "float"


# --------------------------------------------------------------------------
# float / qat — the repro.core.gru paths
# --------------------------------------------------------------------------

class _FloatBase(ClassifierBackend):
    """Shared float-forward plumbing; `_cfg` pins the fake-quant mode."""

    differentiable = True
    _quantized: bool = True

    def _cfg(self, cfg: GRUConfig) -> GRUConfig:
        if cfg.quantized == self._quantized:
            return cfg
        return dataclasses.replace(cfg, quantized=self._quantized)

    def init_states(self, cfg, batch, device=None):
        return init_states(cfg, batch, device=device)

    def forward(self, params, fv, cfg):
        return gru_classifier_forward(params, fv, self._cfg(cfg))

    def step(self, params, states, fv_t, cfg):
        return gru_classifier_step(params, states, fv_t, self._cfg(cfg))


@register_classifier("float")
class FloatClassifier(_FloatBase):
    """Plain float32 forward — no fake-quant anywhere."""

    _quantized = False


@register_classifier("qat")
class QATClassifier(_FloatBase):
    """QAT fake-quant forward (8-bit weights, Q6.8 activations, STE)."""

    _quantized = True


# --------------------------------------------------------------------------
# integer — the bit-exact code engine
# --------------------------------------------------------------------------

@register_classifier("integer")
class IntegerClassifier(ClassifierBackend):
    """Bit-exact integer engine over `QuantizedClassifier` codes.

    `prepare` quantizes float params once (idempotent); `forward`/
    `step` quantize the float FV_Norm input to Q6.8 codes at entry
    (exact for pipeline-produced frames, which are already on the
    grid) and dequantize logit codes to float at exit (always exact).
    Hidden states are int32 Q6.8 code buffers.
    """

    differentiable = False

    def prepare(self, params, cfg):
        from repro.core.gru_int import QuantizedClassifier

        if isinstance(params, QuantizedClassifier):
            return params
        from repro.serving.quantize import quantize_classifier

        return quantize_classifier(params, cfg)

    def init_states(self, cfg, batch, device=None):
        from repro.core.gru_int import int_init_states

        return int_init_states(cfg, batch, device=device)

    def forward(self, params, fv, cfg):
        from repro.core import gru_int

        self._check_prepared(params)
        codes = gru_int.int_gru_classifier_forward(
            params, gru_int.quantize_acts(fv), cfg
        )
        return gru_int.dequantize_acts(codes)

    def step(self, params, states, fv_t, cfg):
        from repro.core import gru_int

        self._check_prepared(params)
        states, codes = gru_int.int_gru_classifier_step(
            params, states, gru_int.quantize_acts(fv_t), cfg
        )
        return states, gru_int.dequantize_acts(codes)

    @staticmethod
    def _check_prepared(params):
        from repro.core.gru_int import QuantizedClassifier

        if not isinstance(params, QuantizedClassifier):
            raise TypeError(
                "integer classifier needs QuantizedClassifier params; "
                "call pipeline.prepare_params(params) (or "
                "repro.serving.quantize.quantize_classifier) first"
            )


# --------------------------------------------------------------------------
# delta / delta-int — the temporal-sparsity ΔGRU engine
# --------------------------------------------------------------------------

class _DeltaBase(ClassifierBackend):
    """Shared ΔGRU plumbing; subclasses pick the arithmetic domain.

    Instances carry their `gru_delta.DeltaConfig` (the registry
    singleton holds the θ=0 default); `with_config` returns a copy
    bound to `KWSPipelineConfig.delta`. The per-layer state dicts
    (memories, accumulators, skipped/total MAC counters) thread through
    `init_states`, `ServerState` donation, `masked_select`, the jitted
    slot reset, and the stream mesh exactly like the dense backends'
    hidden-state leaves — the serving tick never special-cases them.
    """

    differentiable = False

    def __init__(self, delta=None):
        from repro.core.gru_delta import DeltaConfig

        self.delta = DeltaConfig() if delta is None else delta

    def with_config(self, pipeline_config):
        delta = getattr(pipeline_config, "delta", None)
        if delta is None or delta == self.delta:
            return self
        return type(self)(delta)

    def _thetas(self, cfg: GRUConfig):
        return self.delta.code_thresholds(cfg.num_layers)


@register_classifier("delta")
class DeltaClassifier(_DeltaBase):
    """ΔGRU in the QAT fake-quant float domain (θ=0 ≡ "qat" bit for
    bit). Params stay float (like "qat"); state leaves are float32
    grid values plus int32 MAC counters."""

    def init_states(self, cfg, batch, device=None):
        from repro.core.gru_delta import delta_init_states

        return delta_init_states(cfg, batch, device=device)

    def forward(self, params, fv, cfg):
        from repro.core.gru_delta import delta_classifier_forward

        return delta_classifier_forward(params, fv, cfg, self._thetas(cfg))

    def step(self, params, states, fv_t, cfg):
        from repro.core.gru_delta import delta_classifier_step

        return delta_classifier_step(
            params, states, fv_t, cfg, self._thetas(cfg)
        )


@register_classifier("delta-int")
class DeltaIntClassifier(_DeltaBase):
    """ΔGRU on the "integer" backend's codes (θ=0 ≡ "integer" bit for
    bit): int8 weight codes through `intgemm`, int32 Q6.8 state and
    frac-15 accumulator codes, float FV_Norm/logits at the boundary
    exactly like `IntegerClassifier`."""

    def prepare(self, params, cfg):
        return IntegerClassifier.prepare(self, params, cfg)

    def init_states(self, cfg, batch, device=None):
        from repro.core.gru_delta import int_delta_init_states

        return int_delta_init_states(cfg, batch, device=device)

    def forward(self, params, fv, cfg):
        from repro.core import gru_int
        from repro.core.gru_delta import int_delta_classifier_forward

        IntegerClassifier._check_prepared(params)
        codes = int_delta_classifier_forward(
            params, gru_int.quantize_acts(fv), cfg, self._thetas(cfg)
        )
        return gru_int.dequantize_acts(codes)

    def step(self, params, states, fv_t, cfg):
        from repro.core import gru_int
        from repro.core.gru_delta import int_delta_classifier_step

        IntegerClassifier._check_prepared(params)
        states, codes = int_delta_classifier_step(
            params, states, gru_int.quantize_acts(fv_t), cfg,
            self._thetas(cfg),
        )
        return states, gru_int.dequantize_acts(codes)
