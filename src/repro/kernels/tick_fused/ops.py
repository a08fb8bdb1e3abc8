"""Public entry point for the fused serving-tick megakernel.

`tick_fused` picks one of three equivalent implementations per call,
through the shared `repro.kernels.dispatch` machinery (same convention
as `tdc` / `intgemm`):

  * ``pallas``    — the compiled Mosaic megakernel (TPU): the whole
                    tick as ONE `pallas_call` over stream blocks;
  * ``interpret`` — the same kernel body under the Pallas interpreter
                    (validates the megakernel — block slicing, operand
                    encoding, the ΔGRU gather path — on CPU CI);
  * ``reference`` — `tick_reference` directly: the plain fused-XLA
                    tick, exactly the pre-kernel server program.

Sharding: with a ``mesh=`` every tier is wrapped in a `shard_map` over
the ``("stream",)`` axis (`over_stream_shards`) — each device runs the
tick on its own slab (slots are computationally independent; there is
no collective anywhere in the tick). GSPMD cannot partition a
`pallas_call`, so this is also what keeps the megakernel, and the
intgemm kernels inside the xla tick, compilable on a mesh.

The expected call site is inside the serving layer's outer jit
(`repro.serving.serve_loop._fused_tick` with ``tick_impl=
"fused-pallas"|"fused-interpret"``), where the kernel call inlines
into the tick's single jaxpr; top-level calls (the identity tests)
simply trace eagerly.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_dispatch
from repro.kernels.tick_fused.kernel import tick_fused_pallas
from repro.kernels.tick_fused.ref import tick_reference


def resolve_tick_dispatch(
    dispatch: str = "auto",
    interpret: Optional[bool] = None,
) -> str:
    """Resolve 'auto' to a concrete tier for this backend.

    Off-TPU the interpreter re-traces the whole tick per stream block
    (correct but slow), so 'auto' picks the plain fused-XLA reference —
    the serving layer's ``tick_impl="auto"`` maps to the same choice.
    """
    return resolve_dispatch(dispatch, interpret, off_tpu="reference")


def mosaic_unsupported(pipeline, raw_audio: bool) -> Optional[str]:
    """Why Mosaic cannot lower the megakernel for this pipeline and
    input kind, or None where it can.

    The compiled tier replays the whole XLA tick inside one kernel body,
    so every primitive of the tick must have a Mosaic lowering. Compiled
    for a v5e chip (tests/test_tpu_compile.py), only the float backend
    on FV_Norm frames without a cascade passes; each other case
    is refused here, before anything is traced, with the first
    primitive Mosaic rejects.
    """
    if raw_audio:
        return (
            "raw-audio ticks replay the frontend's per-sample lax.scan "
            "inside the kernel, and Mosaic does not lower a scan with "
            "extensive operands"
        )
    if pipeline.config.cascade is not None:
        return (
            "the cascade's bool wake latch needs an int8 -> bool "
            "truncation, which Mosaic does not lower"
        )
    name = pipeline.config.classifier_key
    if name in ("qat", "integer", "delta-int"):
        return (
            f"the {name!r} backend's sigmoid/tanh ROM lookup is a 1-D "
            "gather (jnp.take), and Mosaic lowers only 2-D gathers"
        )
    if name == "delta":
        return (
            "the ΔGRU gather path compacts firing columns with cumsum, "
            "which Mosaic does not lower"
        )
    return None


def over_stream_shards(fn, mesh):
    """Run a tick ``fn(params, state, inp, mask, frontend_state,
    smoothing)`` once per shard-local slab of the ``("stream",)`` mesh.

    Slots are computationally independent (no collective anywhere in
    the tick), so each device runs the single-device tick on its own
    rows. It also keeps every Pallas call shard-local: GSPMD cannot
    partition a Mosaic kernel, neither the megakernel nor the intgemm
    calls inside the xla tick. ``mesh=None`` returns ``fn`` unchanged.
    """
    if mesh is None:
        return fn
    from jax.sharding import PartitionSpec as P

    from repro.distributed.sharding import STREAM_AXIS

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


def tick_fused(
    pipeline,
    raw_audio: bool,
    params,
    state: Tuple[Any, Any, jnp.ndarray, Any],
    inp: jnp.ndarray,
    mask: jnp.ndarray,
    frontend_state,
    smoothing,
    *,
    dispatch: str = "auto",
    interpret: Optional[bool] = None,
    block_streams: Optional[int] = None,
    mesh=None,
) -> Tuple[Tuple[Any, Any, jnp.ndarray, Any], jnp.ndarray, jnp.ndarray]:
    """One fused serving tick; state is the ``(gru, carry, scores, det)``
    tuple of `tick_reference`. Returns ``(new_state, scores, top)``,
    bit-identical across all three tiers for every classifier backend.
    With a ``mesh`` every tier runs per shard (`over_stream_shards`).
    """
    state = (tuple(state[0]), state[1], state[2], state[3])
    path = resolve_tick_dispatch(dispatch, interpret)
    if path == "reference":
        call = functools.partial(tick_reference, pipeline, raw_audio)
    else:
        run_interpret = path == "interpret"
        if not run_interpret:
            reason = mosaic_unsupported(pipeline, raw_audio)
            if reason is not None:
                raise ValueError(
                    f"the compiled megakernel cannot serve this "
                    f"pipeline: {reason}; use the xla tick"
                )
        if block_streams is None:
            block_streams = 8 if run_interpret else 128
        call = functools.partial(
            tick_fused_pallas, pipeline, raw_audio,
            block_streams=block_streams, interpret=run_interpret,
        )
    return over_stream_shards(call, mesh)(
        params, state, inp, mask, frontend_state, smoothing
    )
