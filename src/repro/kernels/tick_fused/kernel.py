"""The fused serving-tick megakernel (pallas / interpret tiers).

One `pallas_call`, gridded over stream blocks, executes the ENTIRE
16 ms serving tick — frontend feature frame, stage-1 cascade wake
gate, every GRU layer, FC head, softmax, exponential smoothing, masked
state advance — as one device program per block. All per-stream state
(GRU hidden states, Δ reference memories, partial-sum accumulators,
frontend carry, detector latches, smoothed scores) is staged into VMEM
by the block specs and every intermediate (feature frame, gate
preactivations, logits, probabilities) lives and dies in registers/
VMEM inside the one kernel invocation: zero intermediate HBM
round-trips, which is the software inverse of the paper IC's
always-resident FEx→GRU→FC datapath.

The kernel body does not reimplement the tick: it re-runs the exact
`tick_reference` math (`repro.kernels.tick_fused.ref`) on one stream
block. Per-stream math has no cross-stream term anywhere in the tick
(the invariant the sharded==single suite already proves), so slicing
the stream axis into grid blocks is exact and the kernel is
bit-identical to the XLA tick by construction — the identity suites in
tests/test_tick_fused.py (+ the serve_sharded / gru_delta / cascade
extensions) pin it down to array equality.

Nested kernels: the classifier backends traced inside the body call
`intgemm` themselves, and a `pallas_call` cannot nest. The body traces
under `force_dispatch("reference")` (`repro.kernels.dispatch`), so
every nested kernel entry point resolves to its bit-identical pure-jnp
reference.

ΔGRU gather path: for the "delta"/"delta-int" backends the dense
``Δ @ W`` inside each cell is replaced (via the cells' ``matmul=``
hook) with a gather-only column update. The per-component fire mask is
already encoded in the thresholded Δ (zeros where not fired); the
block's union of firing columns is compacted with a cumsum prefix sum
into a dense index list and a `fori_loop` with a DYNAMIC trip count
issues one rank-1 ``Δ[:, i] · W[i]`` update per firing column. Work —
not just a counter — now scales with the fire count, so measured tick
latency drops toward the effective-MAC fraction (`srv.sparsity`)
instead of staying dense. Rows whose new state the tick's wake mask
will discard are zeroed out of the union first: an idle or gated
stream costs no columns. Bit-identity of the reordered accumulation
rests on the same fixed-point-grid argument as the θ=0 telescoping
guarantee (`repro.core.gru_delta`): every operand lives on a Q6.8 /
frac-15 grid whose in-range sums are exact in f32 and int32, so
summation order changes nothing; the integer domain additionally
applies `intgemm`'s final int24 saturation to the whole per-tick
contribution, exactly like `intgemm_ref`.
"""

from __future__ import annotations

import functools
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.dispatch import force_dispatch
from repro.kernels.intgemm.ref import INT24_MAX, INT24_MIN
from repro.kernels.tick_fused.ref import tick_reference

__all__ = [
    "gather_delta_matmul",
    "gather_delta_intgemm",
    "make_sparse_step",
    "tick_fused_pallas",
]


# --------------------------------------------------------------------------
# gather-only ΔGRU column update
# --------------------------------------------------------------------------

def _gather_contrib(d: jnp.ndarray, w: jnp.ndarray) -> jnp.ndarray:
    """Σ over firing columns i of outer(d[:, i], w[i]), gather-compacted.

    d (B, I) is a thresholded delta block (zeros where not fired); w is
    (I, N). Columns that fired for NO row in the block are skipped
    entirely: the block-union fire mask is prefix-summed into a compact
    index list and a dynamically-bounded `fori_loop` touches only the
    ``n_fired`` entries — the loop lowers to a while_loop whose trip
    count is the fire count, so the work (and on CPU tiers the wall
    clock) scales with sparsity. Equal to ``d @ w`` wherever that
    product is exact on its fixed-point grid (the ΔGRU regime): columns
    with d ≡ 0 contribute exact zeros, and in-range grid sums are
    order-independent.
    """
    bsz, in_dim = d.shape
    col = jnp.any(d != 0, axis=0)  # (I,) block-union fire mask
    n_fired = jnp.sum(col.astype(jnp.int32))
    # compact[j] = index of the j-th firing column (prefix-sum scatter;
    # non-firing columns scatter to index I and are dropped)
    pos = jnp.cumsum(col.astype(jnp.int32)) - 1
    compact = (
        jnp.zeros((in_dim,), jnp.int32)
        .at[jnp.where(col, pos, in_dim)]
        .set(jnp.arange(in_dim, dtype=jnp.int32), mode="drop")
    )

    def body(j, acc):
        i = compact[j]
        d_col = jax.lax.dynamic_slice_in_dim(d, i, 1, axis=1)  # (B, 1)
        w_row = jax.lax.dynamic_slice_in_dim(w, i, 1, axis=0)  # (1, N)
        return acc + d_col * w_row

    acc0 = jnp.zeros((bsz, w.shape[1]), jnp.result_type(d, w))
    return jax.lax.fori_loop(0, n_fired, body, acc0)


def _mask_rows(d: jnp.ndarray, row_mask: Optional[jnp.ndarray]):
    """Zero the delta rows of streams whose new state the tick's wake
    mask discards anyway (`masked_select` keeps the old value), so an
    idle or gated stream contributes no columns to the block union.
    Changes only discarded values — never an output bit."""
    if row_mask is None:
        return d
    return jnp.where(row_mask[:, None], d, jnp.zeros((), d.dtype))


def gather_delta_matmul(
    d: jnp.ndarray, w: jnp.ndarray, row_mask: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Float-domain gather Δ·W: drop-in for ``d @ w`` in
    `gru_delta.delta_gru_cell` (bit-identical on the QAT grids)."""
    return _gather_contrib(_mask_rows(d, row_mask), w)


def gather_delta_intgemm(
    d: jnp.ndarray, w: jnp.ndarray, row_mask: Optional[jnp.ndarray] = None
) -> jnp.ndarray:
    """Code-domain gather Δ·W: drop-in for ``intgemm(d, w)`` in
    `gru_delta.int_delta_gru_cell`.

    The int24 saturation is applied to the WHOLE per-tick contribution
    after the gather sum, exactly where `intgemm_ref` clips its full
    matmul result — int32 accumulation of the partial products is exact
    (products < 2^21, ≤ 96 terms), so the gather sum equals the dense
    matmul on the nose and the clip sees the identical value.
    """
    contrib = _gather_contrib(
        _mask_rows(d, row_mask).astype(jnp.int32), w.astype(jnp.int32)
    )
    return jnp.clip(contrib, INT24_MIN, INT24_MAX)


def make_sparse_step(pipeline):
    """A `tick_reference` ``step_fn`` with gather-compacted Δ·W updates
    for the delta backends, or None (dense step) for the others.

    Reuses the very `gru_delta` classifier step the XLA tick runs —
    thresholds, counters, gate math and all — overriding ONLY the
    ``matmul=`` hook, so the gather path can never drift from the
    bit-identity target.
    """
    backend = pipeline.classifier
    name = getattr(backend, "name", None)
    if name not in ("delta", "delta-int"):
        return None
    # lazy: gru_delta/gru_int import repro.kernels.intgemm, which runs
    # the kernels package init that imports this module last
    from repro.core import gru_delta, gru_int

    cfg = pipeline.config.gru
    thetas = backend.delta.code_thresholds(cfg.num_layers)

    if name == "delta":
        def step(params, states, fv, wake):
            return gru_delta.delta_classifier_step(
                params, states, fv, cfg, thetas,
                matmul=functools.partial(gather_delta_matmul, row_mask=wake),
            )
        return step

    def step(params, states, fv, wake):
        states, codes = gru_delta.int_delta_classifier_step(
            params, states, gru_int.quantize_acts(fv), cfg, thetas,
            matmul=functools.partial(gather_delta_intgemm, row_mask=wake),
        )
        return states, gru_int.dequantize_acts(codes)
    return step


# --------------------------------------------------------------------------
# pytree <-> kernel-operand encoding
# --------------------------------------------------------------------------
#
# pallas operands want >= 2-D arrays of non-bool dtype; the tick's
# pytrees carry (N,) bool masks, () scalars and (C,) calibration
# vectors. Each leaf is encoded at the wrapper boundary (bool -> int32,
# (N,) -> (N, 1) stream leaves, () -> (1, 1) / (C,) -> (1, C)
# replicated leaves) and decoded back inside the kernel body — both
# directions are exact, so the encoding is invisible to the math.

def _enc_stream(x):
    x = jnp.asarray(x)
    meta = (x.ndim, x.dtype)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    if x.ndim == 1:
        x = x[:, None]
    return x, meta


def _enc_rep(x):
    x = jnp.asarray(x)
    meta = (x.ndim, x.dtype)
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    if x.ndim == 0:
        x = x.reshape(1, 1)
    elif x.ndim == 1:
        x = x.reshape(1, -1)
    return x, meta


def _dec(x, meta):
    ndim, dtype = meta
    if ndim == 0:
        x = x.reshape(())
    elif ndim == 1:
        x = x.reshape(-1)
    if dtype == jnp.bool_:
        x = x.astype(jnp.bool_)
    return x


def _enc_out_val(x):
    if x.dtype == jnp.bool_:
        x = x.astype(jnp.int32)
    if x.ndim == 1:
        x = x[:, None]
    return x


def _stream_spec(shape2d, block):
    nd = len(shape2d)
    return pl.BlockSpec(
        (block,) + tuple(shape2d[1:]),
        lambda ib, _nd=nd: (ib,) + (0,) * (_nd - 1),
    )


def _rep_spec(shape2d):
    nd = len(shape2d)
    return pl.BlockSpec(
        tuple(shape2d), lambda ib, _nd=nd: (0,) * _nd
    )


# --------------------------------------------------------------------------
# the megakernel
# --------------------------------------------------------------------------

def tick_fused_pallas(
    pipeline,
    raw_audio: bool,
    params,
    state: Tuple[Any, Any, jnp.ndarray, Any],
    inp: jnp.ndarray,
    mask: jnp.ndarray,
    frontend_state,
    smoothing,
    *,
    block_streams: int = 8,
    interpret: bool = False,
):
    """One fused serving tick as a single `pallas_call` over stream blocks.

    Same contract as `tick_reference` (state is the ``(gru, carry,
    scores, det)`` 4-tuple): returns ``(new_state, scores, top)``,
    bit-identical for every classifier backend. The stream axis is
    zero-padded to a whole number of ``block_streams`` blocks (padded
    slots carry mask=False, so they are idle streams whose state
    provably does not advance) and sliced back afterwards.
    """
    state = (tuple(state[0]), state[1], state[2], state[3])
    n = mask.shape[0]
    sparse_step = make_sparse_step(pipeline)

    state_leaves, state_def = jax.tree_util.tree_flatten(state)
    s_leaves, s_def = jax.tree_util.tree_flatten((state, inp, mask))
    r_leaves, r_def = jax.tree_util.tree_flatten(
        (params, frontend_state, jnp.asarray(smoothing, jnp.float32))
    )
    enc_s = [_enc_stream(x) for x in s_leaves]
    enc_r = [_enc_rep(x) for x in r_leaves]
    s_arrs = [a for a, _ in enc_s]
    s_meta = [m for _, m in enc_s]
    r_arrs = [a for a, _ in enc_r]
    r_meta = [m for _, m in enc_r]

    def block_fn(s_vals, r_vals):
        (st, x_in, m_in) = jax.tree_util.tree_unflatten(s_def, s_vals)
        (pp, fs, sm) = jax.tree_util.tree_unflatten(r_def, r_vals)
        with force_dispatch("reference"):
            new_state, scores, top = tick_reference(
                pipeline, raw_audio, pp, st, x_in, m_in, fs, sm,
                step_fn=sparse_step,
            )
        return jax.tree_util.tree_leaves(new_state) + [scores, top]

    # Trace the tick once on one block. This both derives the output
    # geometry and LIFTS closed-over device arrays (filterbank
    # coefficients, LUTs — anything living on the pipeline object
    # rather than in params/frontend_state) out as jaxpr consts: a
    # pallas kernel body may not capture array constants, so they ride
    # along as extra replicated operands and the body replays the
    # jaxpr.
    s_structs = [
        jax.ShapeDtypeStruct((block_streams,) + tuple(x.shape[1:]), x.dtype)
        for x in s_leaves
    ]
    r_structs = [
        jax.ShapeDtypeStruct(tuple(x.shape), x.dtype) for x in r_leaves
    ]
    block_jaxpr = jax.make_jaxpr(block_fn)(s_structs, r_structs)
    consts = [jnp.asarray(c) for c in block_jaxpr.consts]
    out_structs = [
        jax.ShapeDtypeStruct(a.shape, a.dtype)
        for a in block_jaxpr.out_avals
    ]
    out_meta = [(len(o.shape), o.dtype) for o in out_structs]

    pad = (-n) % block_streams
    if pad:
        s_arrs = [
            jnp.concatenate(
                [a, jnp.zeros((pad,) + a.shape[1:], a.dtype)], axis=0
            )
            for a in s_arrs
        ]
    n_pad = n + pad

    out_shapes_2d = []
    for o in out_structs:
        shape = (n_pad,) + tuple(o.shape[1:])
        if len(o.shape) == 1:
            shape = (n_pad, 1)
        dtype = jnp.int32 if o.dtype == jnp.bool_ else o.dtype
        out_shapes_2d.append(jax.ShapeDtypeStruct(shape, dtype))

    enc_c = [_enc_rep(c) for c in consts]
    c_arrs = [a for a, _ in enc_c]
    c_meta = [m for _, m in enc_c]
    n_s, n_r, n_c = len(s_arrs), len(r_arrs), len(c_arrs)

    def kernel(*refs):
        in_refs, out_refs = refs[: n_s + n_r + n_c], refs[n_s + n_r + n_c:]
        s_vals = [
            _dec(r[...], m) for r, m in zip(in_refs[:n_s], s_meta)
        ]
        r_vals = [
            _dec(r[...], m)
            for r, m in zip(in_refs[n_s:n_s + n_r], r_meta)
        ]
        c_vals = [
            _dec(r[...], m)
            for r, m in zip(in_refs[n_s + n_r:], c_meta)
        ]
        outs = jax.core.eval_jaxpr(
            block_jaxpr.jaxpr, c_vals, *s_vals, *r_vals
        )
        for ref, val in zip(out_refs, outs):
            ref[...] = _enc_out_val(val)

    outs = pl.pallas_call(
        kernel,
        grid=(n_pad // block_streams,),
        in_specs=(
            [_stream_spec(a.shape, block_streams) for a in s_arrs]
            + [_rep_spec(a.shape) for a in r_arrs]
            + [_rep_spec(a.shape) for a in c_arrs]
        ),
        out_specs=[
            _stream_spec(o.shape, block_streams) for o in out_shapes_2d
        ],
        out_shape=out_shapes_2d,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(*s_arrs, *r_arrs, *c_arrs)

    out_vals = [
        _dec(o[:n], m) for o, m in zip(outs, out_meta)
    ]
    n_state = len(state_leaves)
    new_state = jax.tree_util.tree_unflatten(state_def, out_vals[:n_state])
    scores, top = out_vals[n_state], out_vals[n_state + 1]
    return new_state, scores, top
