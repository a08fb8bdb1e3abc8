"""Reference ("xla") tier of the fused serving tick.

`tick_reference` is the single definition of the 16 ms serving-tick
math — frontend feature frame, stage-1 cascade wake gate, all GRU
layers through the pipeline's classifier backend, FC head, softmax,
exponential score smoothing, masked state advance. It used to live
inline in `repro.serving.serve_loop._fused_tick`; it was moved here
(pure code motion) so every dispatch tier of the tick kernel evaluates
the SAME function:

  * the "xla" / "reference" tier calls it directly (one fused XLA
    program, exactly the pre-kernel server);
  * the "pallas" / "interpret" tiers re-run it INSIDE the megakernel
    body on one stream block at a time (`repro.kernels.tick_fused.
    kernel`) — per-stream math has no cross-stream term anywhere, so
    block slicing is exact and the kernel inherits the tick's whole
    bit-identity story.

The state crossing this boundary is a plain 4-tuple ``(gru, carry,
scores, det)`` rather than the serving layer's `ServerState`
dataclass, so the kernel layer stays importable without the serving
module (no import cycle: serving imports kernels, never the reverse).

``step_fn`` overrides the classifier step (default:
``pipeline.streaming_logits_apply``); the megakernel passes the
gather-compacted ΔGRU step for the delta backends. It receives the
resolved per-stream wake mask as a fourth argument so a sparse step
can suppress the Δ·W work of streams whose new state is about to be
discarded by `masked_select` anyway — legal because ONLY values the
mask keeps reach the returned state, so any per-row value may differ
on masked-out rows without changing a single output bit.
"""

from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.frontend import masked_select
from repro.serving import cascade as cascade_lib

# (gru states tuple, frontend carry, smoothed scores, detector state)
TickState = Tuple[Any, Any, jnp.ndarray, Any]


def tick_reference(
    pipeline,
    raw_audio: bool,
    params,
    state: TickState,
    inp: jnp.ndarray,
    mask: jnp.ndarray,
    frontend_state,
    smoothing,
    step_fn: Optional[Callable] = None,
) -> Tuple[TickState, jnp.ndarray, jnp.ndarray]:
    """One fully fused serving tick on explicit state leaves.

    inp is a raw-audio slab (N, chunk_samples) when ``raw_audio`` else
    an FV_Norm slab (N, C); mask (N,) bool marks slots that submitted
    this tick. Frontend carry, GRU states, and smoothed scores advance
    ONLY under the mask — an idle slot's slice of every buffer is
    returned bit-identical (jnp.where keeps the old value), so a
    stream skipping a tick resumes from its own contiguous state.

    With a cascade (`pipeline.config.cascade`, a static branch) the
    stage-1 detector scores the feature frame and its gate narrows the
    mask the classifier/scores advance under: a submitted-but-gated
    stream's GRU state holds frozen (and its posterior optionally
    decays toward silence), while the frontend carry and the detector
    state still advance under the plain submitted mask — the stage-1
    gate is always-on and consumes every frame, only the classifier
    sleeps. An always-open gate makes ``wake == mask`` elementwise, so
    the tick is bit-identical to the non-cascaded program.

    Each stage runs under a `jax.named_scope`, so its device ops carry
    the scope in their HLO metadata (``op_name``): ``kws_frontend``
    (raw audio only; its log ROM read is ``kws_frontend/kws_log_rom``,
    `repro.core.quant.log_compress_lut`), ``kws_cascade``,
    ``kws_classifier`` — inside which the integer classifier names
    ``kws_gru{l}_gemm`` / ``kws_gru{l}_gates`` / ``kws_gru{l}_update``
    and ``kws_head``
    (`repro.core.gru_int.int_gru_classifier_step`) — and
    ``kws_smooth`` (softmax, smoothing, masked selects, argmax).
    Scopes change metadata only, never a value.

    Returns ``((gru, carry, scores, det), scores, top)``.
    """
    gru_in, carry_in, scores_in, det_in = state
    if raw_audio:
        with jax.named_scope("kws_frontend"):
            new_carry, fv = pipeline.streaming_features_apply(
                carry_in, inp, frontend_state
            )
            carry = masked_select(mask, new_carry, carry_in)
    else:
        carry = carry_in
        fv = inp
    casc = pipeline.config.cascade
    if casc is not None:
        with jax.named_scope("kws_cascade"):
            score = cascade_lib.detector_scores(fv, casc)
            new_det, gate = cascade_lib.gate_step(det_in, score, casc)
            det = masked_select(mask, new_det, det_in)
            wake = jnp.logical_and(mask, gate)
    else:
        det = det_in
        wake = mask
    with jax.named_scope("kws_classifier"):
        if step_fn is None:
            new_gru, logits = pipeline.streaming_logits_apply(
                params, list(gru_in), fv
            )
        else:
            new_gru, logits = step_fn(params, list(gru_in), fv, wake)
    with jax.named_scope("kws_smooth"):
        gru = tuple(masked_select(wake, tuple(new_gru), tuple(gru_in)))
        probs = jax.nn.softmax(logits, axis=-1)
        smoothed = smoothing * scores_in + (1.0 - smoothing) * probs
        scores = masked_select(wake, smoothed, scores_in)
        if casc is not None and casc.score_decay != 1.0:
            # submitted but gated: decay the stale posterior toward zero
            # ("silence") while the classifier sleeps
            gated = jnp.logical_and(mask, jnp.logical_not(wake))
            scores = masked_select(gated, casc.score_decay * scores_in,
                                   scores)
        top = jnp.argmax(scores, axis=-1)
    return (gru, carry, scores, det), scores, top
