"""Serving paths.

LM side: `lower_prefill` / `lower_decode_step` build the pjit'd serving
programs the dry-run compiles (batch of requests, KV cache / recurrent
state sharded per distributed/sharding.py).

KWS side: `StreamingKWSServer` — the deployment shape of the paper's
chip: N concurrent audio streams, one tick per 16 ms frame, a batched
weights-resident GRU step, per-stream argmax + exponential score
smoothing. Each tick accepts, per stream, EITHER a precomputed FV_Norm
frame (C,) OR a raw 16 ms audio hop (`pipeline.chunk_samples` samples at
fs_audio); raw audio is pushed through the pipeline's registered
`FeatureFrontend` (software / hardware-sim / Pallas TDC) with per-stream
filter + SRO-phase carry, so the server is end-to-end audio-in,
posteriors-out. The GRU step itself runs through the pipeline's
registered `ClassifierBackend` (float / qat / integer / delta /
delta-int): with ``classifier="integer"`` the tick consumes int8
weight codes and int32 Q6.8 hidden-state codes — the IC's
WMEM-resident arithmetic, bit-identical to the QAT path; with the
ΔGRU backends ("delta"/"delta-int", `repro.core.gru_delta`) each
slot's state additionally carries last-transmitted memories, partial-
sum accumulators, and skipped/total MAC counters, and the server
exposes the measured temporal sparsity as `srv.sparsity` (per-stream
effective-MAC fraction). Orthogonally, a cascaded pipeline
(`KWSPipelineConfig.cascade`, `repro.serving.cascade`) puts a stage-1
always-on wake detector inside the same tick: an energy/linear gate
on the feature frame wakes the full classifier only on candidate
speech (frozen-state hold + optional score decay while gated), with
the measured duty cycle exposed as `srv.wake_rate`; an always-open
gate (`CascadeConfig.always_on()`) is bit-identical to no cascade for
every backend. This is the serve-side example driver
(examples/serve_streaming.py).

The whole per-tick device program is ONE fused jit (`_fused_tick`):
frontend feature extraction, the batched GRU step, softmax, and
exponential score smoothing run back-to-back on-device over donated
state buffers, under a per-stream submitted mask. State — GRU hidden
states, frontend carry, smoothed scores — lives in a single
`ServerState` pytree; an idle stream's slice of every buffer is
bit-identical across a tick it did not submit to (temporal sparsity,
the DeltaKWS deployment contract). `open_stream`/`close_stream` recycle
slots from a free list, zeroing only the reused slot, and
`StreamingKWSServer.run` replays buffered audio through a `lax.scan`
over the same tick body for offline-throughput serving.

Stream-parallel sharding: slots are computationally independent (no
cross-slot reduction anywhere in the tick), so the slot axis shards
block-wise over a 1-D ``("stream",)`` device mesh
(`repro.distributed.sharding.stream_mesh`). With ``devices=N`` (or an
explicit ``mesh=``) every `ServerState` leaf, input slab, and submitted
mask carries a `NamedSharding` over its slot axis while classifier
params and frontend calibration replicate; the fused tick, the scanned
replay, and the jitted slot reset each lower to one SPMD program with
the sharded state donated across calls. Slot assignment doubles as
device placement, handled by `repro.serving.autoscale.StreamRouter`
(round-robin fill keeps shards balanced). Per-slot math is unchanged by
the partition, so sharded serving is BIT-identical to the single-device
server (tests/test_serve_sharded.py proves it on an emulated CPU mesh).
With one visible device the server falls back to exactly the
pre-sharding single-device program.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.kernels.tick_fused import (
    mosaic_unsupported,
    over_stream_shards,
    tick_fused,
    tick_reference,
)
from repro.serving import cascade as cascade_lib

from repro.distributed.sharding import (
    STREAM_AXIS,
    ShardingRules,
    batch_specs,
    cache_specs,
    make_mesh_context,
    named,
    param_specs,
    replicated_shardings,
    stream_mesh,
    stream_shardings,
    surviving_devices,
)
from repro.models.registry import get_backbone
from repro.serving.autoscale import StreamRouter
from repro.serving.ingress import TickHandle, pack_result
from repro.serving.metrics import MetricsRegistry, span

Pytree = Any


def serve_batch_shape(arch_cfg, shape_spec):
    """ShapeDtypeStructs for one serve step of the given input shape."""
    b = shape_spec.global_batch
    if arch_cfg.frontend == "embedding":
        return {
            "embeddings": jax.ShapeDtypeStruct(
                (b, 1, arch_cfg.d_model), arch_cfg.activation_dtype
            )
        }
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}


def prefill_batch_shape(arch_cfg, shape_spec):
    b, s = shape_spec.global_batch, shape_spec.seq_len
    if arch_cfg.frontend == "embedding":
        return {
            "embeddings": jax.ShapeDtypeStruct(
                (b, s, arch_cfg.d_model), arch_cfg.activation_dtype
            )
        }
    return {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32)}


def lower_decode_step(arch_cfg, rules: ShardingRules, shape_spec):
    """Abstract lower of one decode step at (batch, cache_len) scale."""
    backbone = get_backbone(arch_cfg)
    mesh_ctx = make_mesh_context(rules)
    b, s = shape_spec.global_batch, shape_spec.seq_len
    params_shape = jax.eval_shape(
        lambda k: backbone.init_params(k, arch_cfg, mesh_ctx),
        jax.random.PRNGKey(0),
    )
    if getattr(arch_cfg, "serve_quant", False):
        from repro.models.moe_quant import quantize_expert_shapes

        params_shape = quantize_expert_shapes(params_shape)
    cache_shape = jax.eval_shape(
        lambda: backbone.init_cache(arch_cfg, b, s, mesh_ctx)
    )
    batch_shape = serve_batch_shape(arch_cfg, shape_spec)
    pspecs = param_specs(params_shape, rules)
    cspecs = cache_specs(cache_shape, rules, b)
    bspecs = batch_specs(batch_shape, rules)

    def step(params, cache, cache_len, batch):
        return backbone.decode_step(
            params, cache, cache_len, batch, arch_cfg, mesh_ctx
        )

    # the updated cache keeps the input cache's sharding (donated buffers)
    out_cache_shape = jax.eval_shape(
        step,
        params_shape,
        cache_shape,
        jax.ShapeDtypeStruct((), jnp.int32),
        batch_shape,
    )[1]
    out_cspecs = cache_specs(out_cache_shape, rules, b)
    with rules.mesh:
        lowered = jax.jit(
            step,
            in_shardings=(
                named(pspecs, rules.mesh),
                named(cspecs, rules.mesh),
                None,
                named(bspecs, rules.mesh),
            ),
            out_shardings=(None, named(out_cspecs, rules.mesh)),
            donate_argnums=(1,),
        ).lower(
            params_shape,
            cache_shape,
            jax.ShapeDtypeStruct((), jnp.int32),
            batch_shape,
        )
    return lowered, params_shape, cache_shape


def lower_prefill(arch_cfg, rules: ShardingRules, shape_spec):
    backbone = get_backbone(arch_cfg)
    mesh_ctx = make_mesh_context(rules)
    b, s = shape_spec.global_batch, shape_spec.seq_len
    params_shape = jax.eval_shape(
        lambda k: backbone.init_params(k, arch_cfg, mesh_ctx),
        jax.random.PRNGKey(0),
    )
    batch_shape = prefill_batch_shape(arch_cfg, shape_spec)
    pspecs = param_specs(params_shape, rules)
    bspecs = batch_specs(batch_shape, rules)

    def step(params, batch):
        return backbone.prefill(params, batch, arch_cfg, mesh_ctx)

    out_cache_shape = jax.eval_shape(step, params_shape, batch_shape)[1]
    out_cspecs = cache_specs(out_cache_shape, rules, b)
    with rules.mesh:
        lowered = jax.jit(
            step,
            in_shardings=(
                named(pspecs, rules.mesh),
                named(bspecs, rules.mesh),
            ),
            out_shardings=(None, named(out_cspecs, rules.mesh)),
        ).lower(params_shape, batch_shape)
    return lowered, params_shape


# --------------------------------------------------------------------------
# Streaming KWS serving (the paper's own deployment shape)
# --------------------------------------------------------------------------

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


# kept importable for API compatibility with the pre-fused server
@dataclasses.dataclass
class StreamState:
    stream_id: int
    scores: Optional[np.ndarray] = None  # smoothed class scores


# tick_impl -> the kernel layer's dispatch tier (ISSUE: the serving API
# speaks deployment names, the kernel layer speaks tiers)
_TICK_IMPLS = ("auto", "xla", "fused-pallas", "fused-interpret")
_TICK_DISPATCH = {
    "xla": "xla", "fused-pallas": "pallas", "fused-interpret": "interpret",
}


def _fused_tick(pipeline, raw_audio, params, state: ServerState, inp,
                mask, frontend_state, smoothing, *, tick_impl="xla",
                mesh=None):
    """One fully fused serving tick, traced as a single device program.

    The tick MATH — frontend feature frame, stage-1 cascade wake gate,
    classifier step, softmax, smoothing, masked state advance — lives
    in `repro.kernels.tick_fused.tick_reference` (moved there verbatim
    so the megakernel can re-run it per stream block); this wrapper
    owns only the `ServerState` packing and the implementation choice:

      tick_impl="xla"             one fused XLA program (what "auto"
                                  picks; exactly the pre-kernel tick)
      tick_impl="fused-pallas"    the whole tick as ONE `pallas_call`
                                  over stream blocks with the ΔGRU
                                  gather path (TPU)
      tick_impl="fused-interpret" the same megakernel body under the
                                  Pallas interpreter (CPU-testable)

    All three are bit-identical for every classifier backend (tests/
    test_tick_fused.py). With a stream ``mesh`` every choice runs once
    per shard-local slab under a `shard_map` (`over_stream_shards`):
    GSPMD cannot partition a `pallas_call`, and the xla tick holds the
    intgemm kernels on TPU.
    """
    state4 = (state.gru, state.carry, state.scores, state.det)
    if tick_impl == "xla":
        tick = over_stream_shards(
            functools.partial(tick_reference, pipeline, raw_audio), mesh
        )
        (gru, carry, scores, det), out_scores, top = tick(
            params, state4, inp, mask, frontend_state, smoothing,
        )
    else:
        (gru, carry, scores, det), out_scores, top = tick_fused(
            pipeline, raw_audio, params, state4, inp, mask,
            frontend_state, smoothing,
            dispatch=_TICK_DISPATCH[tick_impl], mesh=mesh,
        )
    return (
        ServerState(gru=gru, carry=carry, scores=scores, det=det),
        out_scores,
        top,
    )


def _tick(pipeline, raw_audio, params, state: ServerState, inp, mask,
          frontend_state, smoothing, *, tick_impl="xla", mesh=None):
    """The live tick's device program: `_fused_tick`, with its (scores,
    top) packed into the one lane-dense buffer the host fetches
    (`repro.serving.ingress.pack_result`). The buffer is a fresh value
    that matches no donated state leaf, so nothing aliases it and a
    handle holding it outlives every later tick's donation."""
    state, scores, top = _fused_tick(
        pipeline, raw_audio, params, state, inp, mask, frontend_state,
        smoothing, tick_impl=tick_impl, mesh=mesh,
    )
    return state, pack_result(scores, top)


def _reset_slot(state: ServerState, slot) -> ServerState:
    """Zero one slot's slice of every state buffer (slot is traced, so
    open/close never recompiles). The zero is written in each leaf's
    own dtype — the cascade's awake latch is a bool leaf, and scatter
    of a literal int into bool is deprecated."""
    return jax.tree_util.tree_map(
        lambda t: t.at[slot].set(jnp.zeros((), t.dtype)), state
    )


class StreamingKWSServer:
    """Batched frame-synchronous KWS over N concurrent audio streams.

    Each frame tick: callers push, per active stream, either one FV_Norm
    (C,) or one raw 16 ms audio hop (`pipeline.chunk_samples` samples at
    fs_audio) — the kinds may not be mixed within one tick. The whole
    tick is one jit-compiled program over donated `ServerState` buffers:
    frontend (for raw audio, with per-stream filter/SRO carry), ONE
    batched GRU step for all slots (the accelerator's Fig. 4 timing,
    vectorized across streams), softmax, and exponential score smoothing
    — no per-stream Python loop, no host-side numpy math. Streams that
    did not submit a frame this tick are masked out of every state
    update (frontend carry, GRU hidden state, scores).

    Slot lifecycle: `open_stream` takes a slot from the router's free
    list and zeroes only that slot's slices; `close_stream` returns it.
    `step` drives one live tick from a {stream_id: frame} dict; `run`
    replays pre-buffered audio through a `lax.scan` over the same tick
    body.

    Live ingress comes in two cadences: `step_batch` (synchronous —
    dispatch, then block on the score fetch) and `step_batch_async`
    (non-blocking — returns a `TickHandle` whose scores materialize
    later, so tick N-1's results are fetched while tick N runs). The
    double-buffered staging and micro-batch coalescing around the async
    path live in `repro.serving.ingress`; both cadences drive the same
    device program and are bit-identical.

    Sharding: ``devices=N`` (first N visible devices) or an explicit
    ``mesh=`` (a 1-D `stream_mesh`) shards the slot axis of every state
    buffer, slab, and mask over the mesh and replicates the params —
    one SPMD program per tick, bit-identical to the single-device
    server. ``devices=None`` with a single visible device (and a
    size-1 mesh) falls back to the pre-sharding single-device path.

    Tick implementation: ``tick_impl=`` selects how the per-tick device
    program is built — ``"xla"`` (one fused XLA program, the historical
    tick), ``"fused-pallas"`` (the whole tick as ONE Pallas megakernel
    over stream blocks with the ΔGRU gather path — temporal sparsity
    becomes wall-clock speed), ``"fused-interpret"`` (the megakernel
    under the Pallas interpreter, for CPU CI), or ``"auto"`` (default:
    xla on every platform — Mosaic cannot lower the megakernel for most
    pipelines, see `repro.kernels.tick_fused.mosaic_unsupported`). The
    resolved choice and its kernel dispatch tier are exposed as
    `srv.tick_impl` / `srv.tick_dispatch`.

    Observability: ``metrics=`` takes a
    `repro.serving.metrics.MetricsRegistry` (or ``True`` for a fresh
    default one, exposed as `srv.metrics`) and instruments the server:
    tick dispatch / fetch latency histograms keyed on the 16 ms budget,
    tick / retrace / compile counters, occupancy gauges, and a
    structured journal event for every compile, shape-keyed retrace,
    resize, and shard-loss recovery. Everything is host-side clock
    reads around the existing calls — the device operands, programs,
    and dispatch order are untouched, so a metrics-enabled server is
    BIT-identical to a metrics-off one (tests/test_metrics.py).
    `srv.metrics_snapshot()` rolls the registry plus the server-level
    telemetry (`sparsity` / `wake_rate` means over open slots — host
    reads of existing counters, taken at snapshot time, never on the
    tick path) into one JSON-able dict. Retrace/compile counts are
    tracked even with metrics off (`srv.retrace_count` /
    `srv.compile_count`): a "retrace" is the first dispatch of a
    (program, operand-shape) pair since the programs were last rebuilt
    — exactly the ticks that pay jax's trace+compile cost, e.g. the
    first tick after a `resize` to a not-yet-seen capacity (resizing
    BACK to a seen capacity hits jax's cache and counts nothing).
    Every dispatch also writes ``kws.server.*`` spans into the
    profiler's trace (`repro.serving.metrics.span`; free when no
    profiler runs), tagged ``tick=srv.dispatch_seq`` at dispatch.
    """

    def __init__(self, pipeline, params, max_streams: int = 256,
                 smoothing: float = 0.7, state=None, mesh=None,
                 devices: Optional[int] = None, tick_impl: str = "auto",
                 metrics=None):
        if mesh is not None and devices is not None:
            raise ValueError("pass mesh= or devices=, not both")
        if tick_impl not in _TICK_IMPLS:
            raise ValueError(
                f"tick_impl must be one of {_TICK_IMPLS}; got "
                f"{tick_impl!r}"
            )
        if tick_impl == "auto":
            # the fused-XLA tick is the one program that compiles for
            # every backend and both input kinds on every platform; the
            # megakernel stays an explicit choice (see
            # `repro.kernels.tick_fused.mosaic_unsupported`)
            tick_impl = "xla"
        if tick_impl == "fused-pallas":
            # FV_Norm is the megakernel's widest reach; a pipeline it
            # cannot serve there it cannot serve at all (raw-audio ticks
            # are refused per tick by `tick_fused`)
            reason = mosaic_unsupported(pipeline, raw_audio=False)
            if reason is not None:
                raise ValueError(
                    f"tick_impl='fused-pallas' cannot serve this "
                    f"pipeline: {reason}; use tick_impl='xla'"
                )
        self.tick_impl = tick_impl
        # the kernel dispatch tier the ticks will actually run
        # ("xla" = no pallas_call at all) — benchmarks record this
        self.tick_dispatch = _TICK_DISPATCH[tick_impl]
        if mesh is None and devices is not None:
            # stream_mesh is the single count-vs-visible validator; the
            # size-1 fallback below then strips a one-device mesh
            mesh = stream_mesh(devices)
        if mesh is not None and mesh.devices.size == 1:
            mesh = None  # single-device fallback: no SPMD plumbing
        if mesh is not None and mesh.axis_names != (STREAM_AXIS,):
            raise ValueError(
                f"server mesh must be 1-D with axis named "
                f"{STREAM_AXIS!r} (see stream_mesh); got "
                f"{mesh.axis_names}"
            )
        self.mesh = mesh
        self.n_devices = int(mesh.devices.size) if mesh is not None else 1
        if max_streams % self.n_devices != 0:
            raise ValueError(
                f"max_streams={max_streams} must divide over "
                f"{self.n_devices} devices"
            )
        # `_is_raw` dispatches on the trailing dim alone, so a geometry
        # where a raw hop and an FV_Norm frame have the SAME width would
        # silently route every tick down the raw-audio path. The paper's
        # geometry (256-sample hops, 16 channels) never collides; any
        # config that does is rejected here, at construction, instead of
        # misclassifying ticks at serve time.
        if pipeline.chunk_samples == pipeline.config.fex.num_channels:
            raise ValueError(
                "ambiguous serving geometry: chunk_samples == "
                f"fex.num_channels == {pipeline.chunk_samples}, so raw "
                "audio hops and FV_Norm frames are indistinguishable by "
                "width; change fex.fs_audio / frame_shift_ms / "
                "num_channels so the two differ"
            )
        self.pipeline = pipeline
        # Backend-shape the params once (e.g. classifier="integer"
        # quantizes to the int8/int32 `QuantizedClassifier` here, so
        # every tick runs on weight codes); float/qat pass through.
        # On a mesh the codes are placed replicated across every device.
        self.params = pipeline.prepare_params(params, mesh=mesh)
        self.max_streams = max_streams
        self.smoothing = smoothing
        # frontend state (norm stats / calibration); default = the
        # pipeline's bound state. Replicated on the mesh.
        self.frontend_state = (
            pipeline.state if state is None else state
        )
        if mesh is not None:
            self.frontend_state = jax.device_put(
                self.frontend_state,
                replicated_shardings(self.frontend_state, mesh),
            )
        scores_sharding = (
            None if mesh is None
            else NamedSharding(mesh, P(STREAM_AXIS, None))
        )
        # stage-1 detector state only when the pipeline carries a
        # cascade — None keeps the pre-cascade pytree structure (and
        # device programs) for plain servers
        det = None
        if pipeline.config.cascade is not None:
            det = cascade_lib.init_state(
                max_streams,
                device=(
                    None if mesh is None
                    else NamedSharding(mesh, P(STREAM_AXIS))
                ),
            )
        self.state = ServerState(
            gru=tuple(pipeline.streaming_init(max_streams, mesh=mesh)),
            carry=pipeline.streaming_features_init(max_streams, mesh=mesh),
            scores=jnp.zeros(
                (max_streams, pipeline.config.gru.num_classes),
                jnp.float32,
                device=scores_sharding,
            ),
            det=det,
        )
        self.active: Dict[int, int] = {}  # stream_id -> slot
        # slot allocation = device placement on a mesh; the router's
        # round-robin fill keeps per-shard load balanced (and reduces
        # to the lowest-free-slot order of the pre-sharding free list
        # when n_shards == 1)
        self.router = StreamRouter(max_streams, self.n_devices)
        # retrace/compile accounting is always on (it is two ints and a
        # set — the benchmarks' exact compile-tick exclusion needs it
        # with metrics off too); the registry mirrors are optional
        self._retraces = 0
        self._compiles = 0
        self._tick_shapes: set = set()
        # device dispatches so far: the next one's ``tick`` number
        self.dispatch_seq = 0
        # metrics: True -> fresh default registry, an existing
        # MetricsRegistry -> shared, any falsy value (None/False) -> off
        if metrics is True:
            metrics = MetricsRegistry()
        elif not metrics:
            metrics = None
        self.metrics: Optional[MetricsRegistry] = metrics
        self._clock = time.perf_counter if metrics is None else metrics.clock
        self._m_dispatch = self._m_fetch = self._m_tick = self._m_d2h = None
        if metrics is not None:
            self._m_ticks = metrics.counter(
                "kws_serve_ticks_total",
                "fused serving ticks dispatched (scanned windows count "
                "each scanned tick)",
            )
            self._m_retraces = metrics.counter(
                "kws_serve_retraces_total",
                "dispatches that traced+compiled a new (program, "
                "operand shape) — the ticks that pay jit cost",
            )
            self._m_compiles = metrics.counter(
                "kws_serve_compile_programs_total",
                "full program rebuilds (construction and mesh changes)",
            )
            self._m_dispatch = metrics.histogram(
                "kws_serve_tick_dispatch_ms",
                "host time to dispatch one tick (or one coalesced "
                "window) — slab handoff to handle return, fetch "
                "excluded",
            )
            self._m_fetch = metrics.histogram(
                "kws_serve_tick_fetch_ms",
                "host time blocked in TickHandle.result() fetching "
                "scores to host",
            )
            self._m_d2h = metrics.counter(
                "kws_serve_d2h_transfers_total",
                "device-to-host transfers issued by tick fetches: one "
                "packed result per dispatch",
            )
            self._m_tick = metrics.histogram(
                "kws_serve_tick_ms",
                "synchronous step_batch wall time (dispatch + fetch)",
            )
            self._m_open = metrics.gauge(
                "kws_serve_open_streams", "streams currently open"
            )
            self._m_cap = metrics.gauge(
                "kws_serve_capacity", "stream-slot capacity"
            )
            self._m_occ = metrics.gauge(
                "kws_serve_occupancy", "open streams / capacity"
            )
        self._update_occupancy_gauges()
        self._compile_programs()

    def _compile_programs(self):
        """(Re)build the jitted device programs for the current mesh.

        One compiled program per input kind; pipeline is closed over
        (static), state buffers are donated. On a mesh every jit gets
        explicit in/out shardings so each lowers to one SPMD program
        over the ("stream",) axis with the state donated in place.

        Called at construction and again only when the MESH changes
        (`recover_shard_loss`): the in/out NamedShardings name the mesh
        object, so a new mesh needs new jit wrappers. A capacity
        `resize` on an unchanged mesh deliberately does NOT come here —
        NamedShardings are shape-agnostic and `ServerState`'s pytree
        structure is capacity-independent, so the existing wrappers
        simply retrace at the new slot-axis shape (jax's own shape-
        keyed cache) and toggling between capacities reuses already-
        compiled programs instead of rebuilding them every resize.
        """
        # new wrappers mean every previously seen operand shape will
        # trace+compile again — reset the retrace tracking to match
        self._tick_shapes.clear()
        self._compiles += 1
        if self.metrics is not None:
            self._m_compiles.inc()
            self.metrics.journal.append(
                "compile_programs",
                n_devices=self.n_devices,
                max_streams=self.max_streams,
                tick_impl=self.tick_impl,
            )
        mesh, pipeline = self.mesh, self.pipeline
        if mesh is None:
            jit_kw = dict(donate_argnums=(1,))
            tick_kw = run_kw = jit_kw
            reset_kw = dict(donate_argnums=(0,))
        else:
            st_sh = stream_shardings(self.state, mesh)
            rep = lambda t: replicated_shardings(t, mesh)  # noqa: E731
            row = NamedSharding(mesh, P(STREAM_AXIS, None))
            vec = NamedSharding(mesh, P(STREAM_AXIS))
            seq_row = NamedSharding(mesh, P(None, STREAM_AXIS, None))
            seq_vec = NamedSharding(mesh, P(None, STREAM_AXIS))
            # the packed result, (K + 1, N) per tick: stream axis minor
            packed = NamedSharding(mesh, P(None, STREAM_AXIS))
            seq_packed = NamedSharding(mesh, P(None, None, STREAM_AXIS))
            scalar = NamedSharding(mesh, P())
            tick_kw = dict(
                donate_argnums=(1,),
                in_shardings=(
                    rep(self.params), st_sh, row, vec,
                    rep(self.frontend_state), scalar,
                ),
                out_shardings=(st_sh, packed),
            )
            run_kw = dict(
                donate_argnums=(1,),
                in_shardings=(
                    rep(self.params), st_sh, seq_row, seq_vec,
                    rep(self.frontend_state), scalar,
                ),
                out_shardings=(st_sh, seq_packed),
            )
            reset_kw = dict(
                donate_argnums=(0,),
                in_shardings=(st_sh, scalar),
                out_shardings=st_sh,
            )
        impl_kw = dict(tick_impl=self.tick_impl, mesh=mesh)
        self._tick_audio = jax.jit(
            functools.partial(_tick, pipeline, True, **impl_kw),
            **tick_kw,
        )
        self._tick_fv = jax.jit(
            functools.partial(_tick, pipeline, False, **impl_kw),
            **tick_kw,
        )
        self._reset = jax.jit(_reset_slot, **reset_kw)
        self._run_audio = jax.jit(
            functools.partial(_run_scan, pipeline, True, **impl_kw),
            **run_kw,
        )
        self._run_fv = jax.jit(
            functools.partial(_run_scan, pipeline, False, **impl_kw),
            **run_kw,
        )

    # ---- observability ----

    @property
    def retrace_count(self) -> int:
        """Dispatches so far that traced+compiled a new (program,
        operand shape) pair — i.e. the ticks that paid jit cost. The
        first tick after construction counts (it compiles), as does
        the first tick after a `resize` to a capacity this program set
        has not served yet; a resize back to a seen capacity hits
        jax's shape-keyed cache and does not. Rebuilt programs
        (`_compile_programs`) reset the seen-shape tracking, so the
        first post-recovery tick counts again. Tracked with metrics
        off too — `benchmarks/churn_load.py` keys its exact
        compile-tick exclusion on this."""
        return self._retraces

    @property
    def compile_count(self) -> int:
        """Full program rebuilds so far (1 after construction; +1 per
        mesh change, i.e. `recover_shard_loss`)."""
        return self._compiles

    def _note_dispatch(self, program: str, shape) -> bool:
        """Record one dispatch of `program` at `shape`; True when it is
        a retrace: the first (program, shape) since the last
        `_compile_programs` (jax traces+compiles under this very
        call)."""
        key = (program, tuple(int(d) for d in shape))
        if key in self._tick_shapes:
            return False
        self._tick_shapes.add(key)
        self._retraces += 1
        if self.metrics is not None:
            self._m_retraces.inc()
            self.metrics.journal.append(
                "retrace", program=program, shape=list(key[1]),
                max_streams=self.max_streams,
            )
        return True

    def _update_occupancy_gauges(self) -> None:
        if self.metrics is None:
            return
        n = len(self.active)
        self._m_open.set(n)
        self._m_cap.set(self.max_streams)
        self._m_occ.set(n / self.max_streams if self.max_streams else 0.0)

    def metrics_snapshot(self) -> Dict[str, Any]:
        """One JSON-able dict of everything observable about the server.

        ``server`` block: identity (tick_impl / dispatch tier / mesh
        size), capacity and occupancy, retrace/compile counts, and the
        per-backend telemetry rollups — mean `sparsity` (ΔGRU
        effective-MAC fraction) and `wake_rate` (cascade duty cycle)
        over the OPEN slots (None with no streams open). Those two
        read device state (a host sync), which is fine here: snapshots
        happen off the tick path. With `metrics=` enabled the registry
        snapshot (counters / gauges / histograms with percentiles,
        journal, trace span rollups) is merged in; with metrics off
        only the server block is returned.

        `json.dumps(srv.metrics_snapshot())` always succeeds and
        round-trips equal (tests/test_metrics.py).
        """
        slots = sorted(self.active.values())
        server: Dict[str, Any] = {
            "tick_impl": self.tick_impl,
            "tick_dispatch": self.tick_dispatch,
            "n_devices": self.n_devices,
            "max_streams": self.max_streams,
            "open_streams": len(self.active),
            "occupancy": (
                len(self.active) / self.max_streams
                if self.max_streams else 0.0
            ),
            "retraces": self._retraces,
            "compiles": self._compiles,
            "sparsity_mean": (
                float(np.mean(self.sparsity[slots])) if slots else None
            ),
            "wake_rate_mean": (
                float(np.mean(self.wake_rate[slots])) if slots else None
            ),
        }
        snap: Dict[str, Any] = {"server": server}
        if self.metrics is not None:
            snap.update(self.metrics.snapshot())
        return snap

    # ---- compatibility views of the fused state ----

    @property
    def states(self) -> List[jnp.ndarray]:
        """Per-layer GRU hidden states (pre-fused API name)."""
        return list(self.state.gru)

    @property
    def feat_carry(self):
        """Frontend streaming carry (pre-fused API name)."""
        return self.state.carry

    @property
    def scores(self) -> np.ndarray:
        """Smoothed per-slot posteriors as a host array.

        An owned copy, not a view: `np.asarray` of a CPU device buffer
        can be zero-copy, and the buffer it would alias is donated to
        the next tick — a view could silently mutate under the caller
        (see `step_batch`). The authoritative copy lives in
        `self.state.scores`."""
        return np.array(self.state.scores)

    @property
    def sparsity(self) -> np.ndarray:
        """Per-slot effective-MAC fraction, (max_streams,) float32.

        For the ΔGRU backends ("delta"/"delta-int") this reads the
        skipped/total MAC counters the tick accumulates per stream
        (executed / offered over the whole classifier, always-dense FC
        included — see `repro.core.gru_delta.effective_mac_fraction`):
        1.0 means fully dense, 0.1 means the stream's traffic let the
        engine skip 90 % of the eligible work. Counters reset with the
        slot on `open_stream`, advance only under the submitted mask
        (an idle tick changes nothing), and ride `ServerState` through
        donation and the stream mesh like every other leaf, so the
        telemetry is exact for live ticks, slab ingress, and the
        scanned replay alike. Dense backends report all-ones — the
        fraction is an invariant 1.0 there, so callers can sweep
        backends without special-casing.

        An owned host copy, like `scores` (never a view of a
        donation-bound buffer).
        """
        from repro.core.gru_delta import (
            effective_mac_fraction,
            is_delta_states,
        )

        if is_delta_states(self.state.gru):
            return np.array(
                effective_mac_fraction(
                    list(self.state.gru), self.pipeline.config.gru
                ),
                dtype=np.float32,
            )
        return np.ones((self.max_streams,), np.float32)

    @property
    def wake_rate(self) -> np.ndarray:
        """Per-slot stage-1 wake rate, (max_streams,) float32.

        For a cascaded pipeline (`pipeline.config.cascade`) this reads
        the detector's woken/ticks counters the tick accumulates per
        stream: the fraction of a stream's submitted ticks on which
        the gate let the classifier advance (1.0 = always woken, 0.0 =
        the stream never crossed the wake threshold). The mean over
        active slots is the classifier duty cycle — it plugs straight
        into `AcceleratorModel(duty_cycle=...)` to predict gated IC
        µW, composing with the ΔGRU `srv.sparsity` (which, for a
        cascaded delta server, measures sparsity *within* the woken
        ticks — the two factors multiply).

        Same telemetry contract as `sparsity`: counters reset with the
        slot on `open_stream`, advance only under the submitted mask,
        freeze while the stream idles, ride donation and the stream
        mesh, and are placement-independent. Slots with no traffic —
        and every slot of a non-cascaded server — report 1.0, so
        callers can sweep configurations without special-casing.

        An owned host copy, like `scores` (never a view of a
        donation-bound buffer).
        """
        if self.state.det is None:
            return np.ones((self.max_streams,), np.float32)
        return np.array(
            cascade_lib.wake_rate(self.state.det), dtype=np.float32
        )

    # ---- slot lifecycle ----

    def open_stream(self, stream_id: int):
        if stream_id in self.active:
            raise ValueError(f"stream {stream_id} already open")
        slot = self.router.acquire()  # raises RuntimeError at capacity
        self.active[stream_id] = slot
        # zero only the reused slot — concurrent streams' slices and the
        # free slots' garbage are untouched (they are masked anyway).
        # The slot index is traced (and replicated on a mesh), so
        # open/close never recompiles and works across shard boundaries.
        self.state = self._reset(self.state, jnp.int32(slot))
        self._update_occupancy_gauges()

    def close_stream(self, stream_id: int):
        # validate before touching the router: a raw KeyError from
        # active.pop leaked bookkeeping internals for double-closes and
        # never-opened ids
        if stream_id not in self.active:
            raise ValueError(f"stream {stream_id} not open")
        slot = self.active.pop(stream_id)
        self.router.release(slot)
        self._update_occupancy_gauges()

    # ---- elastic capacity: live resize & shard-loss recovery ----

    def _host_state(self) -> ServerState:
        """Owned host copies of every state leaf. `np.array` both
        forces the copy (a zero-copy view would alias buffers the next
        tick donates) and blocks until any in-flight tick that writes
        them has executed — a resize never tears a tick."""
        return jax.tree.map(lambda t: np.array(t), self.state)

    def _relay_state(self, host_state: ServerState, new_max: int,
                     src, dst) -> ServerState:
        """Re-lay host state onto a new capacity: per-leaf zeros at
        `new_max` slots with old rows `src` copied BITWISE to new rows
        `dst` (numpy fancy indexing — no arithmetic touches the data,
        which is what makes survivors array-equal, not just close, in
        every dtype: float32 scores, int32 Q6.8 codes, bool latches,
        ΔGRU accumulators)."""
        src = np.asarray(src, np.int64)
        dst = np.asarray(dst, np.int64)

        def relay(leaf):
            out = np.zeros((new_max,) + leaf.shape[1:], leaf.dtype)
            out[dst] = leaf[src]
            return out

        return jax.tree.map(relay, host_state)

    def _place_state(self, host_state: ServerState) -> ServerState:
        """Put a host-side state onto the device(s) in the server's
        canonical layout (slot axis block-sharded over the mesh)."""
        if self.mesh is None:
            return jax.device_put(host_state)
        return jax.device_put(
            host_state, stream_shardings(host_state, self.mesh)
        )

    def resize(self, new_max_streams: int) -> None:
        """Grow or shrink the stream-slot capacity live.

        Every `ServerState` leaf is re-laid onto the new capacity:
        open streams' per-slot slices are copied bitwise (host-side
        fancy indexing, then `device_put` back onto the ``("stream",)``
        block layout), stream ids keep serving through the move, and
        the `StreamRouter` re-places the survivors in ascending
        old-slot order (`StreamRouter.remap` — deterministic, so the
        new placement is balanced and oracle-predictable). Surviving
        streams are BIT-identical to an un-resized server afterwards —
        all five classifier backends, cascaded detector state, ΔGRU
        counters, async handles in flight (a handle's packed result
        is no state leaf) — proven in tests/test_serve_sharded.py.

        The mesh is unchanged, so no device program is rebuilt; the
        existing jits retrace at the new slot-axis shape and previously
        compiled capacities are reused from jax's cache (grow then
        shrink back costs zero new compiles).

        The new capacity must divide over the mesh (whole per-shard
        blocks) and hold every open stream; shrinking below the open
        count raises before any state moves. Callers holding a
        `PipelinedIngress` must `drain()` it around a resize — its
        staged slabs are capacity-shaped (it reallocates on next
        `stage()`; see `repro.serving.ingress`).
        """
        if new_max_streams < 1:
            raise ValueError(
                f"new_max_streams must be >= 1, got {new_max_streams}"
            )
        if new_max_streams % self.n_devices != 0:
            raise ValueError(
                f"new_max_streams={new_max_streams} must divide over "
                f"{self.n_devices} devices"
            )
        if len(self.active) > new_max_streams:
            raise RuntimeError(
                f"cannot shrink to {new_max_streams} slots with "
                f"{len(self.active)} stream(s) open"
            )
        if new_max_streams == self.max_streams:
            return
        occupied = sorted(self.active.values())
        router, mapping = StreamRouter.remap(
            occupied, new_max_streams, self.n_devices
        )
        host = self._host_state()
        new_host = self._relay_state(
            host, new_max_streams, occupied,
            [mapping[s] for s in occupied],
        )
        self.state = self._place_state(new_host)
        self.active = {
            sid: mapping[slot] for sid, slot in self.active.items()
        }
        self.router = router
        old_max, self.max_streams = self.max_streams, new_max_streams
        if self.metrics is not None:
            self.metrics.journal.append(
                "resize", from_streams=old_max,
                to_streams=new_max_streams,
                open_streams=len(self.active),
                n_devices=self.n_devices,
            )
        self._update_occupancy_gauges()

    def recover_shard_loss(self, lost_shard: int) -> Dict[str, Any]:
        """Shrink-reshard after losing one shard's device.

        The recovery control flow of `repro.distributed.fault_tolerance`
        wired into serving: the lost device's slot block is gone, so

          1. every OTHER shard's per-slot state is gathered to host
             (bitwise — healthy streams must come out unchanged),
          2. `ElasticMeshManager` rebuilds a smaller ``("stream",)``
             mesh from the surviving devices (power-of-two shrink, as
             for the training mesh; one survivor -> the single-device
             fallback, no mesh),
          3. capacity is rounded UP to whole per-shard blocks of the
             new mesh (survivors never stop fitting),
          4. survivors are remapped (ascending old-slot order) and
             their state re-laid bitwise onto the new layout,
          5. params / frontend calibration are re-replicated and the
             jitted programs REBUILT — unlike `resize`, the mesh
             changed, and the programs' NamedShardings name it,
          6. the lost shard's streams are reopened under their own
             stream ids on fresh zeroed slots (their state died with
             the device; the caller replays or resumes their audio).

        Returns a summary dict: ``lost_shard``, ``n_devices`` /
        ``max_streams`` (after), ``reopened`` (stream ids that lost
        state), ``survivors`` (stream ids bit-preserved).
        """
        if self.mesh is None:
            raise ValueError(
                "single-device server has no shards to lose"
            )
        if not 0 <= lost_shard < self.n_devices:
            raise ValueError(
                f"lost_shard {lost_shard} outside "
                f"[0, {self.n_devices})"
            )
        from repro.distributed.fault_tolerance import ElasticMeshManager
        from repro.serving.autoscale import shard_of_slot

        # gather BEFORE the mesh shrinks: in this simulation the host
        # can still read every shard; only the lost block's rows are
        # treated as gone (never copied into the new layout)
        host = self._host_state()
        healthy = surviving_devices(self.mesh, lost_shard)
        manager = ElasticMeshManager(
            make_mesh=lambda n: stream_mesh(healthy[:n]),
            initial_data_size=self.n_devices,
        )
        new_mesh = manager.shrink(1)
        new_n = manager.data_size
        if new_n == 1:
            new_mesh = None  # single-device fallback, like __init__
        new_max = -(-self.max_streams // new_n) * new_n
        survivors = {
            sid: slot for sid, slot in self.active.items()
            if shard_of_slot(slot, self.max_streams, self.n_devices)
            != lost_shard
        }
        affected = sorted(
            (slot, sid) for sid, slot in self.active.items()
            if sid not in survivors
        )
        occupied = sorted(survivors.values())
        router, mapping = StreamRouter.remap(occupied, new_max, new_n)
        new_host = self._relay_state(
            host, new_max, occupied, [mapping[s] for s in occupied]
        )
        old_devices, old_max = self.n_devices, self.max_streams
        self.mesh = new_mesh
        self.n_devices = new_n
        self.max_streams = new_max
        # replicated operands follow the mesh; state takes the new
        # block layout; programs rebuild against the new shardings
        if new_mesh is not None:
            self.params = jax.device_put(
                self.params, replicated_shardings(self.params, new_mesh)
            )
            self.frontend_state = jax.device_put(
                self.frontend_state,
                replicated_shardings(self.frontend_state, new_mesh),
            )
        else:
            to_default = lambda t: jax.device_put(np.asarray(t))  # noqa: E731
            self.params = jax.tree.map(to_default, self.params)
            self.frontend_state = jax.tree.map(
                to_default, self.frontend_state
            )
        self.state = self._place_state(new_host)
        self.active = {
            sid: mapping[slot] for sid, slot in survivors.items()
        }
        self.router = router
        self._compile_programs()
        # reopen the lost streams: same ids, fresh zeroed slots (old
        # slot order keeps the reopening deterministic for the oracle)
        reopened = []
        for _old_slot, sid in affected:
            slot = self.router.acquire()
            self.active[sid] = slot
            self.state = self._reset(self.state, jnp.int32(slot))
            reopened.append(sid)
        if self.metrics is not None:
            self.metrics.journal.append(
                "shard_loss",
                lost_shard=lost_shard,
                from_devices=old_devices, to_devices=new_n,
                from_streams=old_max, to_streams=new_max,
                reopened=list(reopened),
                survivors=sorted(survivors),
            )
        self._update_occupancy_gauges()
        return {
            "lost_shard": lost_shard,
            "n_devices": new_n,
            "max_streams": new_max,
            "reopened": reopened,
            "survivors": sorted(survivors),
        }

    # ---- serving ----

    def _require_open(self, stream_ids) -> None:
        """Reject ticks naming unopened streams BEFORE any slab or
        state mutation — a bad tick must leave the server bit-unchanged
        (the pre-validation code KeyError'd out of `_slab` mid-build)."""
        unknown = [sid for sid in stream_ids if sid not in self.active]
        if unknown:
            raise ValueError(
                f"stream(s) {sorted(unknown)} not open"
            )

    def _is_raw(self, dim: int) -> bool:
        """The single kind-dispatch site: True for raw audio hops, False
        for FV_Norm frames, canonical error otherwise. (The two widths
        never collide for the paper's geometry.)"""
        if dim == self.pipeline.chunk_samples:
            return True
        if dim == self.pipeline.config.fex.num_channels:
            return False
        raise ValueError(
            "per-stream input must be an FV_Norm frame "
            f"({self.pipeline.config.fex.num_channels},) or a raw audio "
            f"hop ({self.pipeline.chunk_samples},); got trailing dim {dim}"
        )

    def _slab(self, frames: Dict[int, np.ndarray]):
        """{sid: frame} -> (dense slab, mask) host-side; kind validation
        happens downstream in `step_batch`."""
        self._require_open(frames)
        dims = {int(np.shape(f)[-1]) for f in frames.values()}
        if len(dims) > 1:
            raise ValueError(
                "all frames in one tick must be the same kind; got "
                f"trailing dims {sorted(dims)}"
            )
        dim = dims.pop()
        slab = np.zeros((self.max_streams, dim), np.float32)
        mask = np.zeros((self.max_streams,), bool)
        for sid, frame in frames.items():
            slot = self.active[sid]
            slab[slot] = frame
            mask[slot] = True
        return slab, mask

    def step_batch(self, slab, mask):
        """Pre-batched tick: the high-throughput ingress path.

        slab: (max_streams, S) raw audio hops or (max_streams, C) FV_Norm
        frames, slot-major (rows for unsubmitted slots are ignored);
        mask: (max_streams,) bool, True where the slot submitted. Callers
        that already maintain slot-major buffers (a socket ingress, the
        load generator) skip `step`'s per-stream dict assembly entirely —
        the tick is one device dispatch plus one result fetch.

        Returns (scores (max_streams, K), top (max_streams,)) as host
        arrays; rows of unsubmitted slots hold their previous values.
        Both are views of one owned host array (never of a
        donation-bound buffer): this is `step_batch_async` fetched
        immediately.
        """
        with span("kws.server.step", self._m_tick, self._clock,
                  tick=self.dispatch_seq):
            return self.step_batch_async(slab, mask).result()

    def step_batch_async(self, slab, mask) -> TickHandle:
        """Non-blocking tick: dispatch and return a deferred handle.

        Same operands and same device program as `step_batch`, but the
        host is NOT blocked on the device-to-host score fetch — the
        returned `TickHandle` materializes (scores, top) on its first
        `result()` call. Dispatching tick N+1 before fetching tick N's
        handle overlaps host slab staging with device execution (the
        async ingress path: `repro.serving.ingress.PipelinedIngress`
        does the buffer discipline, `TickCoalescer` the sub-window
        arrival merging), which is what closes the live-vs-scan
        throughput gap.

        The handle holds the tick's packed result (`_tick`), a fresh
        buffer no `ServerState` leaf aliases, whose copy to the host
        starts at dispatch; it survives any number of later ticks
        donating the state — fetch it as late as you like.
        The state trajectory is bit-identical to the synchronous
        `step_batch` sequence: async moves only WHEN the host reads the
        results, never what the device computes.

        Host buffers go straight into the jit call — an explicit
        `jnp.asarray` staging hop here measured ~0.35 ms/tick extra on
        a single-core host, most of the live-vs-scan dispatch gap.
        """
        raw = self._is_raw(int(np.shape(slab)[-1]))
        fn = self._tick_audio if raw else self._tick_fv
        return self._dispatch(fn, "tick_audio" if raw else "tick_fv",
                              slab, mask, 1)

    def step(self, frames: Dict[int, np.ndarray]) -> Dict[int, dict]:
        """frames: stream_id -> FV_Norm (C,) or raw audio hop (S,).

        One 16 ms tick. Inputs are raw audio when their trailing dim is
        `pipeline.chunk_samples` (e.g. 256 @ 16 kHz), FV_Norm when it is
        `fex.num_channels` (e.g. 16) — the two never collide for the
        paper's geometry. An empty dict is a no-op tick: no device call,
        no state change."""
        if not frames:
            return {}
        slab, mask = self._slab(frames)
        scores, top = self.step_batch(slab, mask)
        out = {}
        for sid in frames:
            slot = self.active[sid]
            out[sid] = {"probs": scores[slot], "top": int(top[slot])}
        return out

    def run_batch(self, slab, mask):
        """Offline replay of pre-batched tick slabs, as one device program.

        slab: (n_ticks, max_streams, S) raw audio hops or
        (n_ticks, max_streams, C) FV_Norm frames; mask: (n_ticks,
        max_streams) bool, True where the slot submitted that tick. The
        whole replay is a `lax.scan` over the fused tick body with the
        `ServerState` donated across ticks — the pre-refactor path could
        not be scanned at all, since its per-tick numpy smoothing forced
        a host round-trip every 16 ms. Compiles once per (n_ticks, kind).

        Returns (scores_seq (n_ticks, N, K), tops (n_ticks, N)) as host
        arrays and advances the server state by n_ticks. Both are views
        of one owned host array, never of donation-bound buffers: this
        is `run_batch_async` fetched immediately.
        """
        return self.run_batch_async(slab, mask).result()

    def run_batch_async(self, slab, mask) -> TickHandle:
        """Non-blocking window dispatch: `run_batch` returning a handle.

        Scan-replays a (window, max_streams, S|C) slab of consecutive
        ticks as ONE device program (state donated across ticks inside
        the scan) and returns immediately; the handle's `result()` is
        (scores_seq (window, N, K), tops (window, N)). Because the scan
        body is the very `_fused_tick` the live path jits, the state
        trajectory and every per-tick score row are bit-identical to
        `window` sequential `step_batch` calls — which is what lets the
        async ingress amortize the per-dispatch host cost over a whole
        window (`PipelinedIngress(window=K)`) without touching the
        correctness story. Same packed, prefetched result as
        `step_batch_async`, with a leading window axis.
        """
        raw = self._is_raw(int(np.shape(slab)[-1]))
        fn = self._run_audio if raw else self._run_fv
        return self._dispatch(fn, "run_audio" if raw else "run_fv",
                              slab, mask, int(np.shape(slab)[0]))

    def _dispatch(self, fn, program: str, slab, mask,
                  n_ticks: int) -> TickHandle:
        """Enqueue one device call of `fn` (a tick or a scanned window)
        and start its packed result's copy to the host, under the
        dispatch spans; returns the handle, which carries the
        dispatch's number."""
        k = self.dispatch_seq
        self.dispatch_seq += 1
        compiles = self._note_dispatch(program, np.shape(slab))
        with span("kws.server.dispatch", self._m_dispatch, self._clock,
                  tick=k) as sp:
            with (span("kws.server.compile", tick=k) if compiles
                  else contextlib.nullcontext()):
                with span("kws.server.tick_call", tick=k):
                    self.state, packed = fn(
                        self.params, self.state, slab, mask,
                        self.frontend_state, self.smoothing,
                    )
            # the transfer begins as soon as the device finishes, not
            # when a fetch first asks for the result
            with span("kws.server.prefetch", tick=k):
                packed.copy_to_host_async()
        if self.metrics is not None:
            self._m_ticks.inc(n_ticks)
        return TickHandle(packed, tick=k, dispatched_at=sp.end,
                          fetch_hist=self._m_fetch, transfers=self._m_d2h,
                          clock=self._clock)

    def run(self, buffers: Dict[int, np.ndarray]) -> Dict[int, dict]:
        """Offline replay: buffered audio -> per-tick posteriors, scanned.

        buffers: stream_id -> raw audio (n_samples,) for streams that are
        already open; each is split into consecutive
        `pipeline.chunk_samples` hops (trailing remainder dropped).
        Streams may have different lengths — a stream is masked out of
        every tick past its own end, exactly as if it had stopped
        submitting to `step`.

        The whole replay is ONE device program: `lax.scan` over the fused
        tick body, state donated across ticks. Compiles once per
        (n_ticks, kind) shape. Returns, per stream,
        ``{"probs": (n_ticks_sid, K) smoothed posteriors trajectory,
        "top": final argmax}``, and advances the server state by the
        replayed ticks.
        """
        if not buffers:
            return {}
        self._require_open(buffers)
        hop = self.pipeline.chunk_samples
        ticks = {sid: len(np.asarray(b)) // hop for sid, b in buffers.items()}
        n_ticks = max(ticks.values())
        if n_ticks == 0:
            return {}
        slab = np.zeros((n_ticks, self.max_streams, hop), np.float32)
        mask = np.zeros((n_ticks, self.max_streams), bool)
        for sid, buf in buffers.items():
            slot = self.active[sid]
            t = ticks[sid]
            buf = np.asarray(buf, np.float32)[: t * hop]
            slab[:t, slot] = buf.reshape(t, hop)
            mask[:t, slot] = True
        scores_seq, tops = self.run_batch(slab, mask)  # (T, N, K), (T, N)
        out = {}
        for sid in buffers:
            slot = self.active[sid]
            t = ticks[sid]
            out[sid] = {
                "probs": scores_seq[:t, slot],
                "top": int(tops[t - 1, slot]) if t else None,
            }
        return out


def _run_scan(pipeline, raw_audio, params, state: ServerState, slab, mask,
              frontend_state, smoothing, *, tick_impl="xla", mesh=None):
    """lax.scan of the live tick over (n_ticks, N, S|C) buffered input.

    The scan body is the very `_tick` the live path jits — same
    tick_impl, so a fused-pallas server replays its megakernel inside
    the scan too (one kernel launch per scanned tick) — and returns
    its packed results stacked, (n_ticks, K + 1, N)."""

    def body(st, xs):
        x_t, m_t = xs
        return _tick(
            pipeline, raw_audio, params, st, x_t, m_t, frontend_state,
            smoothing, tick_impl=tick_impl, mesh=mesh,
        )

    return jax.lax.scan(body, state, (slab, mask))
