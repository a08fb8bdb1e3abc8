"""Async double-buffered ingress: bit-identity + staging discipline.

The pipelined serving path (`repro.serving.ingress`) must be a pure
latency transformation: `step_batch_async` / `run_batch_async` dispatch
the SAME jitted programs on the SAME operands in the SAME order as the
synchronous `step_batch` sequence — only the host-side fetch moves
later in time. This suite proves it with `np.testing.assert_array_equal`
(never allclose) for every classifier backend ("float" / "qat" /
"integer" / "delta" / "delta-int"), with the stage-1 cascade enabled
(always-open and a real threshold), and on the 8-emulated-device
("stream",) mesh (tests/conftest.py forces the platform), including:

  * deferred handles fetched arbitrarily late — after further ticks
    donated the `ServerState` buffers the raw tick outputs alias, and
    after slot resets (`open_stream`) rewrote state in place;
  * `PipelinedIngress` buffer discipline: ping-pong reuse only after
    the consuming dispatch retired, FIFO retirement order, stage/commit
    protocol errors, and the `window` coalescing path (full and
    partial windows) against the per-tick reference;
  * `TickCoalescer` semantics: deadline / tick-full / second-frame
    flushes under an injected clock, kind and lifecycle validation,
    and slot mapping captured at dispatch time;
  * a lifecycle-oracle hypothesis harness interleaving open/close with
    in-flight async ticks: a stream's scores depend only on its own
    submitted frames, never on when handles were fetched.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.fex import fit_norm_stats
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.kernels.tick_fused import tick_reference
from repro.serving.cascade import CascadeConfig
from repro.serving.ingress import (
    CoalescedTick,
    PipelinedIngress,
    TickCoalescer,
    TickHandle,
    pack_result,
)
from repro.serving.serve_loop import StreamingKWSServer

from _hypothesis_compat import given, settings, st

N_DEV = len(jax.devices())
MESH_DEV = (
    max(d for d in (2, 4, 8) if d <= min(8, N_DEV)) if N_DEV >= 2 else 1
)
MAX_STREAMS = 8
CLASSIFIERS = ("float", "qat", "integer", "delta", "delta-int")


@pytest.fixture(scope="module")
def norm_stats():
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(audio)
    return fit_norm_stats(quant.log_compress_lut(raw, 12, 10))


@pytest.fixture(scope="module", params=CLASSIFIERS)
def backend(request, norm_stats):
    """(pipeline, params) per classifier backend, built once."""
    pipe = KWSPipeline(
        KWSPipelineConfig(classifier=request.param), norm_stats=norm_stats
    )
    return pipe, pipe.init_params(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def qat_server(norm_stats):
    """A single qat server for the ingress-discipline tests (state is
    fully reset per test via close+open, like the sharded suite)."""
    pipe = KWSPipeline(
        KWSPipelineConfig(classifier="qat"), norm_stats=norm_stats
    )
    params = pipe.init_params(jax.random.PRNGKey(3))
    return pipe, StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)


def _reset(srv, n_open=MAX_STREAMS):
    for sid in list(srv.active):
        srv.close_stream(sid)
    for sid in range(n_open):
        srv.open_stream(sid)


def _state_leaves(srv):
    return [
        np.asarray(leaf).copy()
        for leaf in jax.tree_util.tree_leaves(srv.state)
    ]


def _assert_views_of_one_owned_array(scores, top):
    """A fetched (scores, top): views of one host array that owns its
    data — never of a device buffer a later tick could donate."""
    assert scores.base is not None and scores.base is top.base
    assert scores.base.flags["OWNDATA"]


def _assert_states_identical(a, b):
    la, lb = _state_leaves(a), _state_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)


def _ticks(pipe, n, kind="fv", seed=0, n_streams=MAX_STREAMS):
    """n random (slab, mask) tick operands with partial masks."""
    rng = np.random.default_rng(seed)
    dim = (
        pipe.chunk_samples if kind == "audio"
        else pipe.config.fex.num_channels
    )
    out = []
    for _ in range(n):
        slab = rng.standard_normal(
            (n_streams, dim)
        ).astype(np.float32) * 0.05
        mask = rng.random(n_streams) > 0.25
        out.append((slab, mask))
    return out


def _drive_async_vs_sync(pipe, params, ticks, devices=1,
                         max_streams=MAX_STREAMS):
    """Dispatch every tick async (fetching nothing), then fetch all
    handles; replay the same ticks synchronously on a twin server.
    Returns (async_srv, sync_srv, async_results, sync_results)."""
    a = StreamingKWSServer(
        pipe, params, max_streams=max_streams, devices=devices
    )
    b = StreamingKWSServer(pipe, params, max_streams=max_streams)
    for sid in range(max_streams):
        a.open_stream(sid)
        b.open_stream(sid)
    handles = [a.step_batch_async(slab, mask) for slab, mask in ticks]
    got = [h.result() for h in handles]
    ref = [b.step_batch(slab, mask) for slab, mask in ticks]
    return a, b, got, ref


# --------------------------------------------------------------------------
# step_batch_async bit-identity (every backend, both kinds, deferred)
# --------------------------------------------------------------------------

def test_async_bit_identical_all_backends(backend):
    """All handles fetched AFTER the last dispatch: every tick's scores
    and top, and the final state, bit-match the synchronous sequence —
    for fv and raw-audio ticks alike."""
    pipe, params = backend
    ticks = _ticks(pipe, 4, "fv", seed=1) + _ticks(pipe, 2, "audio", seed=2)
    a, b, got, ref = _drive_async_vs_sync(pipe, params, ticks)
    for (gs, gt), (rs, rt) in zip(got, ref):
        np.testing.assert_array_equal(gs, rs)
        np.testing.assert_array_equal(gt, rt)
    _assert_states_identical(a, b)


def test_run_batch_async_window_matches_sequential(backend):
    """A run_batch_async window dispatch == the same ticks stepped one
    by one (the scan body IS the fused tick — the coalescing window
    inherits the correctness story)."""
    pipe, params = backend
    ticks = _ticks(pipe, 5, "fv", seed=3)
    a = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    b = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    for sid in range(MAX_STREAMS):
        a.open_stream(sid)
        b.open_stream(sid)
    slab = np.stack([s for s, _ in ticks])
    mask = np.stack([m for _, m in ticks])
    h = a.run_batch_async(slab, mask)
    ref = [b.step_batch(s, m) for s, m in ticks]
    scores_seq, tops = h.result()
    for t, (rs, rt) in enumerate(ref):
        np.testing.assert_array_equal(scores_seq[t], rs)
        np.testing.assert_array_equal(tops[t], rt)
    _assert_states_identical(a, b)


@pytest.mark.parametrize("wake_threshold", [0.0, 0.3])
def test_async_bit_identical_cascaded(norm_stats, wake_threshold):
    """Async == sync with the stage-1 wake gate in the tick, both
    always-open (threshold 0) and at a real threshold with hangover —
    the gate's frozen-state holds ride the deferred handles too."""
    pipe = KWSPipeline(
        KWSPipelineConfig(
            classifier="qat",
            cascade=CascadeConfig(
                wake_threshold=wake_threshold, hangover_frames=1
            ),
        ),
        norm_stats=norm_stats,
    )
    params = pipe.init_params(jax.random.PRNGKey(5))
    ticks = _ticks(pipe, 6, "fv", seed=5)
    a, b, got, ref = _drive_async_vs_sync(pipe, params, ticks)
    for (gs, gt), (rs, rt) in zip(got, ref):
        np.testing.assert_array_equal(gs, rs)
        np.testing.assert_array_equal(gt, rt)
    _assert_states_identical(a, b)
    np.testing.assert_array_equal(a.wake_rate, b.wake_rate)


@pytest.mark.skipif(
    N_DEV < 2,
    reason="needs a multi-device platform (conftest forces 8 emulated "
    "CPU devices unless XLA_FLAGS overrides it)",
)
def test_async_bit_identical_sharded(backend):
    """Async dispatch against the mesh-sharded server == the sync
    single-device sequence, handles fetched late — deferred fetches
    must materialize correctly from sharded score buffers. (2 slots
    per shard, matching tests/test_serve_sharded.py: a 1-slot shard
    compiles a batch-1 per-shard program whose float reduction order
    differs bitwise — a platform quirk, not an async property.)"""
    pipe, params = backend
    ms = 2 * MESH_DEV
    ticks = _ticks(pipe, 4, "fv", seed=7, n_streams=ms)
    a, b, got, ref = _drive_async_vs_sync(
        pipe, params, ticks, devices=MESH_DEV, max_streams=ms
    )
    for (gs, gt), (rs, rt) in zip(got, ref):
        np.testing.assert_array_equal(gs, rs)
        np.testing.assert_array_equal(gt, rt)
    _assert_states_identical(a, b)


# --------------------------------------------------------------------------
# handle-after-donation safety
# --------------------------------------------------------------------------

def test_handle_survives_later_ticks_and_slot_resets(qat_server):
    """A handle fetched two ticks late — and again after open_stream
    slot resets rewrote state in place — reads exactly what an
    immediate fetch would have."""
    pipe, srv = qat_server
    _reset(srv)
    ticks = _ticks(pipe, 5, "fv", seed=11)
    ref_srv = StreamingKWSServer(
        pipe, srv.params, max_streams=MAX_STREAMS
    )
    for sid in range(MAX_STREAMS):
        ref_srv.open_stream(sid)
    ref0 = ref_srv.step_batch(*ticks[0])

    h0 = srv.step_batch_async(*ticks[0])
    srv.step_batch_async(*ticks[1])  # donates the state h0's raw
    srv.step_batch_async(*ticks[2])  # outputs could alias — twice
    got0 = h0.result()
    np.testing.assert_array_equal(got0[0], ref0[0])
    np.testing.assert_array_equal(got0[1], ref0[1])
    # a handle still unfetched while slots reset in place
    h3 = srv.step_batch_async(*ticks[3])
    srv.close_stream(0)
    srv.open_stream(100)  # _reset rewrites slot 0's state buffers
    srv.step_batch_async(*ticks[4])
    got3a = h3.result()
    got3b = h3.result()  # idempotent: cached host copy
    assert got3a is got3b
    assert h3.ready() and h3.done_at is not None
    _assert_views_of_one_owned_array(*got3a)


def test_step_batch_is_async_fetched_immediately(qat_server):
    """The sync path IS the async path + immediate result(): same
    arrays, views of one owned host copy."""
    pipe, srv = qat_server
    _reset(srv)
    slab, mask = _ticks(pipe, 1, "fv", seed=12)[0]
    scores, top = srv.step_batch(slab, mask)
    _assert_views_of_one_owned_array(scores, top)
    assert scores.shape == (MAX_STREAMS, pipe.config.gru.num_classes)
    assert top.shape == (MAX_STREAMS,)


# --------------------------------------------------------------------------
# the packed result: tick_reference's own bits, never aliased
# --------------------------------------------------------------------------

def _reference_outputs(pipe, srv, ticks, raw):
    """`tick_reference`'s own (scores, top), no packing, over `ticks`
    from `srv`'s current state (which is read, never donated)."""
    fn = jax.jit(functools.partial(tick_reference, pipe, raw))
    st = srv.state
    state4 = (st.gru, st.carry, st.scores, st.det)
    out = []
    for slab, mask in ticks:
        state4, scores, top = fn(srv.params, state4, slab, mask,
                                 srv.frontend_state, srv.smoothing)
        out.append((np.asarray(scores), np.asarray(top)))
    return out


@pytest.mark.parametrize(
    "kind", ["integer", "cascade", "audio", "sharded", "window"]
)
def test_packed_result_is_tick_reference_bit_for_bit(norm_stats, kind):
    """What a handle unpacks on the host is `tick_reference`'s
    (scores, top), bit for bit — for the integer, cascaded, raw-audio
    and mesh-sharded servers and a `run_batch_async` window — with
    every handle fetched only after the last dispatch and a slot reset
    that rewrote state in place."""
    if kind == "sharded" and N_DEV < 2:
        pytest.skip("needs a multi-device platform")
    pipe = KWSPipeline(
        KWSPipelineConfig(
            classifier="integer",
            cascade=(CascadeConfig(wake_threshold=0.3, hangover_frames=1)
                     if kind == "cascade" else None),
        ),
        norm_stats=norm_stats,
    )
    params = pipe.init_params(jax.random.PRNGKey(9))
    n = 2 * MESH_DEV if kind == "sharded" else MAX_STREAMS
    raw = kind == "audio"
    ticks = _ticks(pipe, 4, "audio" if raw else "fv", seed=17, n_streams=n)
    srv = StreamingKWSServer(pipe, params, max_streams=n,
                             devices=MESH_DEV if kind == "sharded" else None)
    twin = StreamingKWSServer(pipe, params, max_streams=n)
    for sid in range(n):
        srv.open_stream(sid)
        twin.open_stream(sid)
    want = _reference_outputs(pipe, twin, ticks, raw)
    if kind == "window":
        handles = [srv.run_batch_async(np.stack([s for s, _ in ticks]),
                                       np.stack([m for _, m in ticks]))]
    else:
        handles = [srv.step_batch_async(s, m) for s, m in ticks]
    srv.close_stream(0)
    srv.open_stream(100)  # _reset rewrites slot 0's state buffers
    got = [h.result() for h in handles]
    if kind == "window":
        (scores_seq, tops), = got
        assert scores_seq.shape == (len(ticks), n, 12)
        got = list(zip(scores_seq, tops))
    for (gs, gt), (ws, wt) in zip(got, want, strict=True):
        assert gs.dtype == ws.dtype and gt.dtype == wt.dtype
        np.testing.assert_array_equal(gs, ws)
        np.testing.assert_array_equal(gt, wt)


def _aliased_outputs(hlo_text):
    """Output indices the compiled program's ``input_output_alias``
    names (each reuses a donated input's buffer)."""
    head = hlo_text.splitlines()[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, entry_computation", head)
    return {int(i) for i in re.findall(r"\{(\d+)\}:", m.group(1))} if m else set()


@pytest.mark.parametrize("classifier", ["integer", "delta-int"])
@pytest.mark.parametrize("max_streams", [1, 13], ids=["one", "odd"])
def test_packed_output_is_never_aliased(norm_stats, classifier,
                                        max_streams):
    """The packed (K + 1, N) result is a fresh buffer: the compiled
    tick and scanned window alias donated state into state outputs
    only, never into the packed output — at a single stream and at an
    odd capacity equal to K + 1, where the packed shape is square."""
    pipe = KWSPipeline(KWSPipelineConfig(classifier=classifier),
                       norm_stats=norm_stats)
    srv = StreamingKWSServer(pipe, pipe.init_params(jax.random.PRNGKey(0)),
                             max_streams=max_streams)
    k = pipe.config.gru.num_classes
    n_state = len(jax.tree_util.tree_leaves(srv.state))
    dim = pipe.config.fex.num_channels
    for fn, lead in ((srv._tick_fv, ()), (srv._run_fv, (2,))):
        lowered = fn.lower(
            srv.params, srv.state,
            np.zeros(lead + (max_streams, dim), np.float32),
            np.ones(lead + (max_streams,), bool),
            srv.frontend_state, srv.smoothing,
        )
        assert lowered.out_info[1].shape == lead + (k + 1, max_streams)
        aliased = _aliased_outputs(lowered.compile().as_text())
        assert aliased and max(aliased) < n_state  # state outputs only
        assert n_state not in aliased


# --------------------------------------------------------------------------
# PipelinedIngress staging discipline
# --------------------------------------------------------------------------

def test_ingress_bit_identity_and_fifo_order(qat_server):
    """depth=2 ping-pong over distinct per-tick data: every retired
    handle bit-matches the sync reference, retirement order is dispatch
    order, and buffer reuse never corrupts an in-flight tick."""
    pipe, srv = qat_server
    _reset(srv)
    ref_srv = StreamingKWSServer(pipe, srv.params, max_streams=MAX_STREAMS)
    for sid in range(MAX_STREAMS):
        ref_srv.open_stream(sid)
    ticks = _ticks(pipe, 7, "fv", seed=13)
    ing = PipelinedIngress(srv, pipe.config.fex.num_channels, depth=2)
    for i, (s, m) in enumerate(ticks):
        slab, mask = ing.stage()
        assert not mask.any()  # stage() hands the mask back cleared
        slab[:] = s
        mask[:] = m
        ing.commit(meta=i)
        assert ing.in_flight <= 2
    handles = ing.drain()
    assert [h.meta for h in handles] == list(range(7))
    assert ing.in_flight == 0
    ref = [ref_srv.step_batch(s, m) for s, m in ticks]
    for h, (rs, rt) in zip(handles, ref):
        np.testing.assert_array_equal(h.scores, rs)
        np.testing.assert_array_equal(h.top, rt)
    _assert_states_identical(srv, ref_srv)


def test_ingress_windowed_bit_identity_with_partial_flush(qat_server):
    """window=3 over 8 ticks (2 full windows + a partial of 2): per-tick
    rows of every window handle bit-match the sync sequence; partial
    windows scan only the staged ticks (no padded no-ops)."""
    pipe, srv = qat_server
    _reset(srv)
    ref_srv = StreamingKWSServer(pipe, srv.params, max_streams=MAX_STREAMS)
    for sid in range(MAX_STREAMS):
        ref_srv.open_stream(sid)
    ticks = _ticks(pipe, 8, "fv", seed=14)
    ing = PipelinedIngress(
        srv, pipe.config.fex.num_channels, depth=2, window=3
    )
    returned = []
    for i, (s, m) in enumerate(ticks):
        slab, mask = ing.stage()
        slab[:] = s
        mask[:] = m
        returned.append(ing.commit(meta=i))
    # window=3: commits 2, 5 dispatch (0-indexed), the rest return None
    assert [r is not None for r in returned] == [
        False, False, True, False, False, True, False, False
    ]
    assert ing.pending_ticks == 2
    handles = ing.drain()
    assert ing.pending_ticks == 0
    metas = [m for h in handles for m in h.meta]
    assert metas == list(range(8))
    ref = [ref_srv.step_batch(s, m) for s, m in ticks]
    t = 0
    for h in handles:
        scores_seq, tops = h.result()
        assert scores_seq.shape[0] == len(h.meta)
        for k in range(scores_seq.shape[0]):
            np.testing.assert_array_equal(scores_seq[k], ref[t][0])
            np.testing.assert_array_equal(tops[k], ref[t][1])
            t += 1
    assert t == 8
    _assert_states_identical(srv, ref_srv)


def test_ingress_protocol_errors(qat_server):
    pipe, srv = qat_server
    _reset(srv)
    dim = pipe.config.fex.num_channels
    with pytest.raises(ValueError, match="depth"):
        PipelinedIngress(srv, dim, depth=0)
    with pytest.raises(ValueError, match="window"):
        PipelinedIngress(srv, dim, window=0)
    with pytest.raises(ValueError, match="trailing dim"):
        PipelinedIngress(srv, dim + 1)  # neither hop nor frame width
    ing = PipelinedIngress(srv, dim)
    with pytest.raises(RuntimeError, match="commit"):
        ing.commit()  # commit without stage
    ing.stage()
    with pytest.raises(RuntimeError, match="stage"):
        ing.stage()  # stage twice without commit
    with pytest.raises(RuntimeError, match="flush"):
        ing.flush()  # flush with a staged-but-uncommitted tick
    ing.commit()
    assert ing.drain()  # leaves the ingress reusable
    assert ing.in_flight == 0


def test_ingress_reallocates_after_resize(qat_server):
    """A live `resize()` invalidates the preallocated slabs: staging
    with old-capacity work still in flight raises (those buffers may
    still be read by the device), while drain() + stage() silently
    reallocates at the new capacity — and both the pre- and
    post-resize ticks bit-match a synchronous twin resized at the same
    point."""
    pipe, base = qat_server
    srv = StreamingKWSServer(pipe, base.params, max_streams=MAX_STREAMS)
    twin = StreamingKWSServer(pipe, base.params, max_streams=MAX_STREAMS)
    for sid in range(MAX_STREAMS):
        srv.open_stream(sid)
        twin.open_stream(sid)
    dim = pipe.config.fex.num_channels
    ing = PipelinedIngress(srv, dim, depth=2)
    ref = []
    for s, m in _ticks(pipe, 3, "fv", seed=21):
        slab, mask = ing.stage()
        slab[:] = s
        mask[:] = m
        ing.commit()
        ref.append(twin.step_batch(s, m))
    assert ing.in_flight > 0
    grown = MAX_STREAMS * 2
    srv.resize(grown)
    with pytest.raises(RuntimeError, match="drain"):
        ing.stage()  # in-flight dispatches hold old-capacity slabs
    for h, (rs, rt) in zip(ing.drain(), ref):
        np.testing.assert_array_equal(h.scores, rs)
        np.testing.assert_array_equal(h.top, rt)
    twin.resize(grown)
    assert twin.active == srv.active  # single-device remap is identity
    for k, (s, m) in enumerate(
        _ticks(pipe, 3, "fv", seed=22, n_streams=grown)
    ):
        m[MAX_STREAMS:] = False  # grown slots are still unopened
        slab, mask = ing.stage()
        assert slab.shape == (grown, dim)  # reallocated, new capacity
        assert mask.shape == (grown,)
        slab[:] = s
        mask[:] = m
        ing.commit(meta=k)
        ref.append(twin.step_batch(s, m))
    for h, (rs, rt) in zip(ing.drain(), ref[3:]):
        np.testing.assert_array_equal(h.scores, rs)
        np.testing.assert_array_equal(h.top, rt)
    _assert_states_identical(srv, twin)


# --------------------------------------------------------------------------
# TickCoalescer
# --------------------------------------------------------------------------

class _FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _coalescer(srv, **kw):
    clock = _FakeClock()
    return TickCoalescer(srv, clock=clock, **kw), clock


def test_coalescer_flushes_when_every_open_stream_submitted(qat_server):
    pipe, srv = qat_server
    _reset(srv, n_open=3)
    co, _clock = _coalescer(srv)
    rng = np.random.default_rng(15)
    frames = {
        sid: rng.standard_normal(16).astype(np.float32) for sid in range(3)
    }
    co.add(0, frames[0])
    co.add(1, frames[1])
    assert co.pending_streams == 2
    co.add(2, frames[2])  # tick full -> flush
    assert co.pending_streams == 0
    (h,) = co.drain()
    assert isinstance(h.meta, CoalescedTick)
    assert h.meta.sids == {sid: srv.active[sid] for sid in range(3)}
    assert h.meta.flushed_at is not None
    # rows bit-match a sync reference serving the same frames
    ref_srv = StreamingKWSServer(pipe, srv.params, max_streams=MAX_STREAMS)
    for sid in range(3):
        ref_srv.open_stream(sid)
    ref = ref_srv.step(frames)
    for sid, slot in h.meta.sids.items():
        np.testing.assert_array_equal(h.scores[slot], ref[sid]["probs"])


def test_coalescer_deadline_flush_via_injected_clock(qat_server):
    pipe, srv = qat_server
    _reset(srv, n_open=2)
    co, clock = _coalescer(srv, window_ms=16.0)
    f = np.ones(16, np.float32)
    co.add(0, f)
    assert co.poll() == []  # deadline not reached: no flush
    assert co.pending_streams == 1
    clock.t += 0.0159
    assert co.poll() == []  # 15.9 ms: still inside the window
    clock.t += 0.0002
    co.poll()  # 16.1 ms: flushes
    assert co.pending_streams == 0
    handles = co.drain()
    assert len(handles) == 1
    assert handles[0].meta.flushed_at - handles[0].meta.staged_at >= 0.016


def test_coalescer_second_frame_flushes_previous_window(qat_server):
    pipe, srv = qat_server
    _reset(srv, n_open=2)
    co, _clock = _coalescer(srv)
    f1 = np.ones(16, np.float32)
    f2 = np.full(16, 2.0, np.float32)
    co.add(0, f1)
    co.add(0, f2)  # same stream again: f1's window flushes first
    assert co.pending_streams == 1  # f2 opened the next window
    co.flush()
    handles = co.drain()
    assert len(handles) == 2
    assert list(handles[0].meta.sids) == [0]
    assert list(handles[1].meta.sids) == [0]
    # two ticks for stream 0, in submission order
    ref_srv = StreamingKWSServer(pipe, srv.params, max_streams=MAX_STREAMS)
    ref_srv.open_stream(0)
    r1 = ref_srv.step({0: f1})
    r2 = ref_srv.step({0: f2})
    slot = handles[0].meta.sids[0]
    np.testing.assert_array_equal(handles[0].scores[slot], r1[0]["probs"])
    np.testing.assert_array_equal(handles[1].scores[slot], r2[0]["probs"])


def test_coalescer_validation(qat_server):
    pipe, srv = qat_server
    _reset(srv, n_open=2)
    with pytest.raises(ValueError, match="window_ms"):
        TickCoalescer(srv, window_ms=0)
    co, _clock = _coalescer(srv)
    with pytest.raises(ValueError, match="stream 99 not open"):
        co.add(99, np.ones(16, np.float32))
    with pytest.raises(ValueError, match="trailing dim"):
        co.add(0, np.ones(17, np.float32))
    co.add(0, np.ones(16, np.float32))
    with pytest.raises(ValueError, match="same kind"):
        co.add(1, np.ones(pipe.chunk_samples, np.float32))
    assert co.pending_streams == 1  # the bad adds staged nothing
    co.drain()


# --------------------------------------------------------------------------
# lifecycle oracle: open/close interleaved with in-flight async ticks
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def async_oracle_servers(norm_stats):
    """(async 8-slot server, single-device 1-slot reference) on shared
    qat params — module-scoped so hypothesis examples reuse the
    compiled tick programs."""
    pipe = KWSPipeline(
        KWSPipelineConfig(classifier="qat"), norm_stats=norm_stats
    )
    params = pipe.init_params(jax.random.PRNGKey(7))
    srv = StreamingKWSServer(pipe, params, max_streams=MAX_STREAMS)
    reference = StreamingKWSServer(pipe, params, max_streams=1)
    return srv, reference


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    events=st.lists(
        st.tuples(
            st.booleans(),  # open a new stream before this tick?
            st.booleans(),  # close the oldest open stream first?
            st.integers(min_value=0, max_value=255),  # submit bitmask
        ),
        min_size=2,
        max_size=6,
    ),
)
def test_async_random_schedule_matches_lifecycle_oracle(
    async_oracle_servers, seed, events
):
    """Random open/close/submit schedules driven entirely through
    `step_batch_async` with handles held in flight across open/close
    events and fetched only at the end: each open stream's final scores
    bit-match a single-device synchronous replay of its own recorded
    frames — independent of every other stream's traffic and of when
    any handle was fetched."""
    srv, reference = async_oracle_servers
    for sid in list(srv.active):
        srv.close_stream(sid)
    rng = np.random.default_rng(seed)
    next_sid = 0
    frames_of = {}
    handles = []

    def do_open():
        nonlocal next_sid
        srv.open_stream(next_sid)
        frames_of[next_sid] = []
        next_sid += 1

    do_open()
    for want_open, want_close, submit_bits in events:
        if want_close and len(srv.active) > 1:
            victim = min(srv.active)
            srv.close_stream(victim)
            del frames_of[victim]
        if want_open and len(srv.active) < srv.max_streams:
            do_open()
        slab = np.zeros((srv.max_streams, 16), np.float32)
        mask = np.zeros((srv.max_streams,), bool)
        for i, sid in enumerate(sorted(srv.active)):
            if submit_bits >> (i % 8) & 1:
                f = rng.standard_normal(16).astype(np.float32)
                slab[srv.active[sid]] = f
                mask[srv.active[sid]] = True
                frames_of[sid].append(f)
        # dispatch WITHOUT fetching: handles stay in flight across the
        # open/close events of later iterations
        handles.append(srv.step_batch_async(slab.copy(), mask.copy()))
    for h in handles:
        h.result()  # late fetches must all still be valid
    for sid in sorted(srv.active):
        reference.open_stream(sid)
        expected = np.zeros_like(np.asarray(reference.state.scores[0]))
        for f in frames_of[sid]:
            out = reference.step({sid: f})
            expected = out[sid]["probs"]
        got = srv.scores[srv.active[sid]]
        np.testing.assert_array_equal(got, expected)
        reference.close_stream(sid)


# --------------------------------------------------------------------------
# TickHandle unit behavior
# --------------------------------------------------------------------------

def test_tick_handle_plain_arrays():
    """Non-jax stand-ins (plain numpy) work: ready() is immediately
    True and result() unpacks views of one owned host copy."""
    scores = np.arange(6.0, dtype=np.float32).reshape(2, 3)
    top = np.array([1, 2], np.int32)
    h = TickHandle(np.asarray(pack_result(scores, top)), meta="m")
    assert h.ready()
    s, t = h.result()
    np.testing.assert_array_equal(s, scores)
    np.testing.assert_array_equal(t, top)
    assert s.dtype == np.float32 and t.dtype == np.int32
    _assert_views_of_one_owned_array(s, t)
    assert h.meta == "m" and h.done_at is not None
