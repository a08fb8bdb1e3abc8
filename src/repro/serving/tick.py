"""The 16 ms serving tick: one definition, traced into every program.

`serve_tick` is the whole per-tick math of `StreamingKWSServer` —
frontend feature frame, stage-1 cascade wake gate, every GRU layer
through the pipeline's classifier backend, FC head, softmax,
exponential score smoothing, masked state advance — on one
`ServerState`. The live tick, the scanned replay and the sharded
server all trace this function (`repro.serving.serve_loop._fused_tick`);
on a stream mesh `over_stream_shards` runs it once per shard-local
slab.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.frontend import masked_select
from repro.distributed.sharding import STREAM_AXIS
from repro.serving import cascade as cascade_lib


@dataclasses.dataclass(frozen=True)
class ServerState:
    """All per-slot device state of a `StreamingKWSServer`, as one pytree.

    gru    — per-layer classifier state, owned by the backend: a
             (max_streams, H) float32 hidden state per layer for
             float/qat, int32 Q6.8 codes for "integer", and for the
             ΔGRU backends a per-layer dict {h, x_ref, h_ref, acc_x,
             acc_h, skipped, total} of (max_streams, ...) leaves
             (masking, donation, slot resets, and the stream mesh are
             structure- and dtype-agnostic; all-zeros is every
             backend's valid fresh state).
    carry  — frontend streaming carry (filter / SRO-phase state), a dict
             of (max_streams, ...) arrays from `streaming_features_init`.
    scores — exponentially smoothed posteriors, (max_streams, K).
    det    — stage-1 wake-gate state for a cascaded pipeline
             (`repro.serving.cascade.init_state`: per-stream awake
             latch, hangover countdown, woken/ticks counters, all
             (max_streams,) leaves; all-zeros is the valid fresh
             state). None when `pipeline.config.cascade` is None —
             a None leaf vanishes from the pytree, so a non-cascaded
             server keeps the exact pre-cascade state structure and
             device programs.

    The pytree crosses jit as a single donated argument: every tick
    consumes the old state buffers and writes the new ones in place
    (donation), so steady-state serving allocates nothing per tick.
    """

    gru: Tuple[jnp.ndarray, ...]
    carry: Any
    scores: jnp.ndarray
    det: Any = None


jax.tree_util.register_dataclass(
    ServerState,
    data_fields=["gru", "carry", "scores", "det"],
    meta_fields=[],
)


def serve_tick(
    pipeline,
    raw_audio: bool,
    params,
    state: ServerState,
    inp: jnp.ndarray,
    mask: jnp.ndarray,
    frontend_state,
    smoothing,
) -> Tuple[ServerState, jnp.ndarray, jnp.ndarray]:
    """One serving tick on every slot of ``state``.

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

    Returns ``(new_state, scores, top)``.
    """
    if raw_audio:
        with jax.named_scope("kws_frontend"):
            new_carry, fv = pipeline.streaming_features_apply(
                state.carry, inp, frontend_state
            )
            carry = masked_select(mask, new_carry, state.carry)
    else:
        carry = state.carry
        fv = inp
    casc = pipeline.config.cascade
    if casc is not None:
        with jax.named_scope("kws_cascade"):
            score = cascade_lib.detector_scores(fv, casc)
            new_det, gate = cascade_lib.gate_step(state.det, score, casc)
            det = masked_select(mask, new_det, state.det)
            wake = jnp.logical_and(mask, gate)
    else:
        det = state.det
        wake = mask
    with jax.named_scope("kws_classifier"):
        new_gru, logits = pipeline.streaming_logits_apply(
            params, list(state.gru), fv
        )
    with jax.named_scope("kws_smooth"):
        gru = tuple(masked_select(wake, tuple(new_gru), tuple(state.gru)))
        probs = jax.nn.softmax(logits, axis=-1)
        smoothed = smoothing * state.scores + (1.0 - smoothing) * probs
        scores = masked_select(wake, smoothed, state.scores)
        if casc is not None and casc.score_decay != 1.0:
            # submitted but gated: decay the stale posterior toward zero
            # ("silence") while the classifier sleeps
            gated = jnp.logical_and(mask, jnp.logical_not(wake))
            scores = masked_select(gated, casc.score_decay * state.scores,
                                   scores)
        top = jnp.argmax(scores, axis=-1)
    new_state = ServerState(gru=gru, carry=carry, scores=scores, det=det)
    return new_state, scores, top


def over_stream_shards(fn, mesh):
    """Run a tick ``fn(params, state, inp, mask, frontend_state,
    smoothing)`` once per shard-local slab of the ``("stream",)`` mesh.

    Slots are computationally independent (no collective anywhere in
    the tick), so each device runs the single-device tick on its own
    rows. It also keeps every Pallas call shard-local: GSPMD cannot
    partition a Mosaic kernel, and the tick holds the intgemm calls.
    ``mesh=None`` returns ``fn`` unchanged.
    """
    if mesh is None:
        return fn
    slab, rep = P(STREAM_AXIS), P()
    sharded = jax.shard_map(
        fn, mesh=mesh,
        in_specs=(rep, slab, slab, slab, rep, rep),
        out_specs=(slab, slab, slab),
        check_vma=False,
    )

    def call(params, state, inp, mask, frontend_state, smoothing):
        return sharded(params, state, inp, mask, frontend_state,
                       jnp.asarray(smoothing, jnp.float32))

    return call
