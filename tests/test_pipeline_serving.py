"""KWS pipeline end-to-end + the fused streaming serving stack.

Covers the serving hardening sweep: idle-stream state isolation (the
temporal-sparsity contract — a stream that skips a tick must be
bit-identical across it), slot-reuse hygiene on close/reopen, empty and
mixed-kind ticks, pre-batched slab ticks (`step_batch`), the lax.scan
offline replay driver (`run` / `run_batch`), three-way streaming-vs-
batch feature parity, and a TDC dispatch-mode parity sweep (deterministic
+ property-based via hypothesis when installed).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.fex import fit_norm_stats
from repro.core import quant
from repro.core.frontend import hardware_state
from repro.core.gru_delta import DeltaConfig
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.core.tdfex import TDFExConfig
from repro.kernels.tdc import tdc_counts
from repro.serving.serve_loop import ServerState, StreamingKWSServer

from _hypothesis_compat import given, settings, st


CLASSIFIERS = ("float", "qat", "integer", "delta", "delta-int")


def _pipeline_with_stats(audio, classifier="float"):
    # bootstrap pass (no normalizer) records FV_Raw to fit mu/sigma,
    # mirroring the chip's recording flow (Section III-F)
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, fv_raw = boot.features(audio)
    fv_log = quant.log_compress_lut(fv_raw, 12, 10)
    stats = fit_norm_stats(fv_log)
    # the ΔGRU pair at a real θ, so the ticks skip columns
    cfg = KWSPipelineConfig(classifier=classifier,
                            delta=DeltaConfig(0.15, 0.15))
    return KWSPipeline(cfg, norm_stats=stats)


def _audio(batch=2, samples=16000, seed=0, scale=0.05):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.standard_normal((batch, samples)).astype(np.float32) * scale
    )


def _server(max_streams=4, seed=0, classifier="float"):
    pipe = _pipeline_with_stats(_audio(seed=seed), classifier)
    params = pipe.init_params(jax.random.PRNGKey(seed))
    return pipe, StreamingKWSServer(pipe, params, max_streams=max_streams)


def _slot_state(srv, sid):
    """One stream's slice of every ServerState buffer, as host arrays."""
    slot = srv.active[sid]
    return jax.tree_util.tree_map(
        lambda t: np.asarray(t[slot]).copy(), srv.state
    )


def _hops(pipe, n, seed=0):
    rng = np.random.default_rng(seed)
    hop = pipe.chunk_samples
    return [
        rng.standard_normal(hop).astype(np.float32) * 0.05 for _ in range(n)
    ]


# --------------------------------------------------------------------------
# pipeline basics (pre-existing coverage)
# --------------------------------------------------------------------------

def test_pipeline_features_and_logits_shapes():
    audio = _audio(batch=4)
    pipe = _pipeline_with_stats(audio)
    fv, raw = pipe.features(audio)
    assert fv.shape == (4, 62, 16) and raw.shape == (4, 62, 16)
    params = pipe.init_params(jax.random.PRNGKey(0))
    logits = pipe.logits(params, fv)
    assert logits.shape == (4, 12)


def test_deprecated_shims_warn():
    """The pre-registry shims must emit DeprecationWarning pointing at
    the CHANGES.md migration table (they were silent before)."""
    audio = _audio(batch=1, samples=2048)
    pipe = _pipeline_with_stats(audio)
    with pytest.warns(DeprecationWarning, match="CHANGES.md"):
        pipe.features_software(audio)
    from repro.core.pipeline import record_features_hardware
    from repro.core.tdfex import TDFExConfig

    tdcfg = TDFExConfig()
    c = tdcfg.fex.num_channels
    with pytest.warns(DeprecationWarning, match="CHANGES.md"):
        record_features_hardware(
            np.asarray(audio), tdcfg, None,
            jnp.full((c,), tdcfg.beta_nominal), jnp.ones((c,)),
        )


def test_streaming_matches_batch_inference():
    audio = _audio(seed=1)
    pipe = _pipeline_with_stats(audio)
    params = pipe.init_params(jax.random.PRNGKey(1))
    fv, _ = pipe.features(audio)
    batch_logits = pipe.logits(params, fv)
    states = pipe.streaming_init(2)
    for t in range(fv.shape[1]):
        states, logits = pipe.streaming_step(params, states, fv[:, t])
    np.testing.assert_allclose(
        np.asarray(batch_logits), np.asarray(logits), atol=1e-5
    )


def test_streaming_server_lifecycle():
    _, srv = _server(seed=2)
    srv.open_stream(101)
    srv.open_stream(202)
    out = srv.step({101: np.ones(16, np.float32),
                    202: np.zeros(16, np.float32)})
    assert set(out) == {101, 202}
    assert abs(out[101]["probs"].sum() - (1 - srv.smoothing)) < 1e-5
    srv.close_stream(101)
    out = srv.step({202: np.ones(16, np.float32)})
    assert set(out) == {202}
    # slot reuse
    srv.open_stream(303)
    assert len(srv.active) == 2


def test_server_capacity():
    _, srv = _server(max_streams=2, seed=3)
    srv.open_stream(1)
    srv.open_stream(2)
    with pytest.raises(RuntimeError):
        srv.open_stream(3)


# --------------------------------------------------------------------------
# idle-stream isolation (regression: the pre-fused server advanced GRU
# state for streams that did not submit a frame)
# --------------------------------------------------------------------------

def test_idle_stream_state_bit_identical_across_other_ticks():
    """A stream that skips ticks must have bit-identical GRU state,
    frontend carry, scores, and posteriors while other streams tick."""
    pipe, srv = _server(seed=4)
    srv.open_stream(1)
    srv.open_stream(2)
    hops = _hops(pipe, 4, seed=4)
    srv.step({1: hops[0], 2: hops[0]})
    idle_before = _slot_state(srv, 2)
    for h in hops[1:]:  # stream 2 never submits
        srv.step({1: h})
    idle_after = _slot_state(srv, 2)
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, idle_before, idle_after
    )
    # ...and its reported posteriors pick up exactly where they left off:
    # identical to a server that ran only stream 2's traffic.
    out = srv.step({2: hops[1]})
    pipe2, srv2 = _server(seed=4)
    srv2.params = srv.params
    srv2.open_stream(2)
    srv2.step({2: hops[0]})
    out2 = srv2.step({2: hops[1]})
    np.testing.assert_array_equal(out[2]["probs"], out2[2]["probs"])


def test_idle_stream_isolated_under_fv_ticks():
    """Same isolation when ticks carry FV_Norm frames (no frontend)."""
    _, srv = _server(seed=5)
    srv.open_stream(1)
    srv.open_stream(2)
    fv = np.ones(16, np.float32)
    srv.step({1: fv, 2: fv})
    idle_before = _slot_state(srv, 2)
    srv.step({1: fv})
    srv.step({1: 2 * fv})
    idle_after = _slot_state(srv, 2)
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, idle_before, idle_after
    )


def test_empty_tick_is_noop():
    """`step({})` must not touch any state and not dispatch anything."""
    pipe, srv = _server(seed=6)
    srv.open_stream(7)
    srv.step({7: _hops(pipe, 1, seed=6)[0]})
    before = jax.tree_util.tree_map(
        lambda t: np.asarray(t).copy(), srv.state
    )
    assert srv.step({}) == {}
    after = jax.tree_util.tree_map(lambda t: np.asarray(t), srv.state)
    jax.tree_util.tree_map(np.testing.assert_array_equal, before, after)


# --------------------------------------------------------------------------
# stream lifecycle: slot-reuse hygiene
# --------------------------------------------------------------------------

def test_close_reopen_zeroes_reused_slot_only():
    """open -> tick -> close -> reopen must hand out a fully zeroed slot
    (GRU, carry, scores) while a concurrent stream's state is untouched."""
    pipe, srv = _server(max_streams=2, seed=7)
    srv.open_stream(1)
    srv.open_stream(2)
    hops = _hops(pipe, 2, seed=7)
    srv.step({1: hops[0], 2: hops[0]})
    survivor_before = _slot_state(srv, 2)
    old_slot = srv.active[1]
    srv.close_stream(1)
    srv.open_stream(3)  # only free slot -> must reuse stream 1's
    assert srv.active[3] == old_slot
    reused = _slot_state(srv, 3)
    jax.tree_util.tree_map(
        lambda t: np.testing.assert_array_equal(t, np.zeros_like(t)),
        reused,
    )
    survivor_after = _slot_state(srv, 2)
    jax.tree_util.tree_map(
        np.testing.assert_array_equal, survivor_before, survivor_after
    )
    # the reopened stream starts from scratch: same first-tick output as
    # a fresh server
    out = srv.step({3: hops[1]})
    _, fresh = _server(max_streams=2, seed=7)
    fresh.params = srv.params
    fresh.open_stream(3)
    out_fresh = fresh.step({3: hops[1]})
    np.testing.assert_array_equal(out[3]["probs"], out_fresh[3]["probs"])


def test_reopen_same_stream_id_rejected():
    _, srv = _server(seed=8)
    srv.open_stream(1)
    with pytest.raises(ValueError, match="already open"):
        srv.open_stream(1)


def test_mixed_kind_tick_rejected():
    pipe, srv = _server(seed=9)
    srv.open_stream(1)
    srv.open_stream(2)
    with pytest.raises(ValueError, match="same kind"):
        srv.step({1: np.ones(16, np.float32),
                  2: np.zeros(pipe.chunk_samples, np.float32)})


# --------------------------------------------------------------------------
# pre-batched ticks + scan replay driver
# --------------------------------------------------------------------------

@pytest.mark.parametrize("classifier", CLASSIFIERS)
def test_step_batch_matches_step(classifier):
    """The slab ingress path and the dict path are the same tick."""
    pipe, srv_a = _server(seed=10, classifier=classifier)
    _, srv_b = _server(seed=10, classifier=classifier)
    srv_b.params = srv_a.params
    for s in (srv_a, srv_b):
        s.open_stream(0)
        s.open_stream(1)
    hop = pipe.chunk_samples
    rng = np.random.default_rng(10)
    for _ in range(3):
        chunks = {i: rng.standard_normal(hop).astype(np.float32) * 0.05
                  for i in range(2)}
        out = srv_a.step(chunks)
        slab = np.zeros((srv_b.max_streams, hop), np.float32)
        mask = np.zeros((srv_b.max_streams,), bool)
        for sid, chunk in chunks.items():
            slab[srv_b.active[sid]] = chunk
            mask[srv_b.active[sid]] = True
        scores, tops = srv_b.step_batch(slab, mask)
    for sid in (0, 1):
        slot = srv_b.active[sid]
        np.testing.assert_array_equal(out[sid]["probs"], scores[slot])
        assert out[sid]["top"] == int(tops[slot])


@pytest.mark.parametrize("classifier", CLASSIFIERS)
def test_run_replay_matches_step_sequence(classifier):
    """`run` (lax.scan over the fused tick) == the same audio fed
    hop-by-hop through `step`, including ragged stream lengths."""
    pipe, srv_live = _server(seed=11, classifier=classifier)
    _, srv_scan = _server(seed=11, classifier=classifier)
    srv_scan.params = srv_live.params
    hop = pipe.chunk_samples
    rng = np.random.default_rng(11)
    buf1 = rng.standard_normal(hop * 4).astype(np.float32) * 0.05
    buf2 = rng.standard_normal(hop * 2).astype(np.float32) * 0.05
    for s in (srv_live, srv_scan):
        s.open_stream(1)
        s.open_stream(2)
    live = {1: [], 2: []}
    for t in range(4):
        frames = {1: buf1[t * hop:(t + 1) * hop]}
        if t < 2:  # stream 2 ends after 2 ticks (ragged)
            frames[2] = buf2[t * hop:(t + 1) * hop]
        out = srv_live.step(frames)
        for sid, r in out.items():
            live[sid].append(r["probs"])
    replay = srv_scan.run({1: buf1, 2: buf2})
    np.testing.assert_array_equal(np.stack(live[1]), replay[1]["probs"])
    np.testing.assert_array_equal(np.stack(live[2]), replay[2]["probs"])
    assert replay[1]["top"] == int(np.stack(live[1])[-1].argmax())
    # the servers end in identical states (scan leaves stream 2 masked
    # after its buffer ends, exactly like the live skips)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        srv_live.state, srv_scan.state,
    )


@pytest.mark.parametrize("classifier", CLASSIFIERS)
def test_run_batch_fv_matches_live_fv_ticks(classifier):
    _, srv_live = _server(seed=12, classifier=classifier)
    _, srv_scan = _server(seed=12, classifier=classifier)
    srv_scan.params = srv_live.params
    n, c = srv_live.max_streams, 16
    rng = np.random.default_rng(12)
    slab = rng.standard_normal((3, n, c)).astype(np.float32)
    mask = np.ones((3, n), bool)
    for s in (srv_live, srv_scan):
        for sid in range(n):
            s.open_stream(sid)
    live_scores = []
    for t in range(3):
        scores, _ = srv_live.step_batch(slab[t], mask[t])
        live_scores.append(scores)
    scores_seq, tops = srv_scan.run_batch(slab, mask)
    np.testing.assert_array_equal(np.stack(live_scores), scores_seq)
    assert tops.shape == (3, n)


# --------------------------------------------------------------------------
# streaming-vs-batch feature parity (three-way, through the pipeline's
# streaming_features_step — the path the fused tick inlines)
# --------------------------------------------------------------------------

@pytest.mark.parametrize(
    "frontend", ["software", "hardware", "hardware-pallas"]
)
def test_streaming_features_parity_all_frontends(frontend):
    """Hop-by-hop `streaming_features_step` must match the whole-
    utterance `features` path: exactly (up to the documented 1-sample
    chunk-edge oversampler replication and TDC count granularity — <= 1
    raw-code LSB) for every registered frontend, and through the full
    normalizer for the software backend."""
    audio = _audio(batch=2, samples=4096, seed=13)
    cfg = KWSPipelineConfig(frontend=frontend, use_norm=False)
    state = (
        hardware_state(cfg.tdfex_config) if frontend != "software" else None
    )
    pipe = KWSPipeline(cfg, state=state)
    _, raw_batch = pipe.features(audio)
    carry = pipe.streaming_features_init(audio.shape[0])
    hop = pipe.chunk_samples
    frames = []
    for t in range(audio.shape[1] // hop):
        carry, codes = pipe.frontend.streaming_step(
            audio[:, t * hop:(t + 1) * hop], cfg, pipe.state, carry
        )
        frames.append(np.asarray(codes))
    raw_stream = np.stack(frames, axis=1)
    d = np.abs(raw_stream - np.asarray(raw_batch))
    assert d.max() <= 1.0, f"{frontend}: {d.max()} LSB"
    assert (d == 0).mean() > 0.5, "parity should hold for most codes"


def test_streaming_fv_norm_parity_software():
    """FV_Norm parity through log LUT + normalizer + Q6.8 (the frames
    the GRU actually consumes): 1 raw-LSB code flips stay below one
    normalized quantization step."""
    audio = _audio(batch=2, samples=8192, seed=14)
    pipe = _pipeline_with_stats(audio)
    fv_batch, _ = pipe.features(audio)
    carry = pipe.streaming_features_init(audio.shape[0])
    hop = pipe.chunk_samples
    outs = []
    for t in range(audio.shape[1] // hop):
        carry, fv = pipe.streaming_features_step(
            carry, audio[:, t * hop:(t + 1) * hop]
        )
        outs.append(np.asarray(fv))
    stream = np.stack(outs, axis=1)
    np.testing.assert_allclose(stream, np.asarray(fv_batch), atol=0.5)


# --------------------------------------------------------------------------
# TDC dispatch parity: reference vs interpret
# --------------------------------------------------------------------------

_TDC_CFG = TDFExConfig()
_SPF = _TDC_CFG.decimation // _TDC_CFG.tdc_oversample


def _tdc_parity(b, frames, c, seed):
    rng = np.random.default_rng(seed)
    u = jnp.asarray(
        np.abs(rng.standard_normal((b, _SPF * frames, c))).astype(np.float32)
        * 0.2
    )
    ref = np.asarray(tdc_counts(u, _TDC_CFG, dispatch="reference"))
    itp = np.asarray(tdc_counts(u, _TDC_CFG, dispatch="interpret"))
    assert ref.shape == itp.shape == (b, frames, c)
    # The two formulations are algebraically identical; float32 rounding
    # can land a phase exactly on a floor boundary, flipping single
    # counts by 1 (both stay within 1 LSB of the float64 oracle, see
    # test_kernels). Anything beyond that is a real dispatch bug.
    d = np.abs(ref - itp)
    assert d.max() <= 1.0, f"dispatch divergence: {d.max()} counts"
    assert (d == 0).mean() >= 0.95, "boundary flips must be rare"


@pytest.mark.parametrize(
    "b,frames,c", [(1, 1, 1), (2, 3, 16), (5, 2, 7), (9, 1, 3)]
)
def test_tdc_dispatch_parity_sweep(b, frames, c):
    _tdc_parity(b, frames, c, seed=b * 100 + frames * 10 + c)


@settings(max_examples=20, deadline=None)
@given(
    b=st.integers(min_value=1, max_value=6),
    frames=st.integers(min_value=1, max_value=4),
    c=st.integers(min_value=1, max_value=16),
    seed=st.integers(min_value=0, max_value=2**31 - 1),
)
def test_tdc_dispatch_parity_property(b, frames, c, seed):
    """Property sweep across random (batch, frames, channels) shapes:
    reference and interpret dispatch agree for every shape (skipped when
    the hypothesis test extra is absent)."""
    _tdc_parity(b, frames, c, seed)


def test_tdc_dispatch_inside_jit_matches_outside():
    """`tdc_counts` must dispatch identically under an outer jit (the
    fused tick / features path) — and without a nested jit boundary."""
    rng = np.random.default_rng(15)
    u = jnp.asarray(
        np.abs(rng.standard_normal((2, _SPF * 2, 4))).astype(np.float32)
        * 0.2
    )
    outside = np.asarray(tdc_counts(u, _TDC_CFG, dispatch="interpret"))
    inside = np.asarray(
        jax.jit(lambda x: tdc_counts(x, _TDC_CFG, dispatch="interpret"))(u)
    )
    np.testing.assert_array_equal(outside, inside)


# --------------------------------------------------------------------------
# ServerState pytree mechanics
# --------------------------------------------------------------------------

def test_step_twice_keeps_first_scores_single_device():
    """Donation-hazard regression (single-device path; the sharded twin
    lives in tests/test_serve_sharded.py): two ticks back-to-back
    without fetching `scores` in between must leave the first tick's
    returned arrays intact. The state's buffers are DONATED to the
    next tick, so the host boundary hands out views of one owned host
    copy of the tick's packed result — never a zero-copy view of
    device memory."""
    _, srv = _server(seed=17)
    srv.open_stream(0)
    srv.open_stream(1)
    rng = np.random.default_rng(17)
    mask = np.zeros((srv.max_streams,), bool)
    mask[:2] = True
    fv1 = rng.standard_normal((srv.max_streams, 16)).astype(np.float32)
    fv2 = rng.standard_normal((srv.max_streams, 16)).astype(np.float32)
    scores1, top1 = srv.step_batch(fv1, mask)
    assert scores1.base is top1.base and scores1.base.flags["OWNDATA"]
    snap_s, snap_t = scores1.copy(), top1.copy()
    view = srv.scores  # the property must also be an owned copy
    assert view.flags["OWNDATA"]
    srv.step_batch(fv2, mask)  # donates the state scores1 could alias
    srv.step_batch(fv1, mask)
    np.testing.assert_array_equal(scores1, snap_s)
    np.testing.assert_array_equal(top1, snap_t)
    np.testing.assert_array_equal(view, snap_s)
    # same guard on the scanned replay driver
    slab = rng.standard_normal((2, srv.max_streams, 16)).astype(np.float32)
    seq, tops = srv.run_batch(slab, np.stack([mask, mask]))
    assert seq.base is tops.base and seq.base.flags["OWNDATA"]
    snap_seq = seq.copy()
    srv.run_batch(slab, np.stack([mask, mask]))
    np.testing.assert_array_equal(seq, snap_seq)


def test_server_state_is_donation_safe_pytree():
    """Every ServerState leaf must be a distinct buffer (the fused tick
    donates the whole pytree) and must round-trip tree flatten."""
    _, srv = _server(seed=16)
    leaves = jax.tree_util.tree_leaves(srv.state)
    buf_ids = [id(leaf) for leaf in leaves]
    assert len(set(buf_ids)) == len(buf_ids)
    flat, treedef = jax.tree_util.tree_flatten(srv.state)
    rebuilt = jax.tree_util.tree_unflatten(treedef, flat)
    assert isinstance(rebuilt, ServerState)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        srv.state, rebuilt,
    )


# --------------------------------------------------------------------------
# lifecycle error hygiene: canonical ValueError, no partial mutation
# --------------------------------------------------------------------------

def test_close_stream_never_opened_raises_value_error():
    """Closing an id that was never opened must raise the canonical
    ValueError, not leak a raw KeyError from the slot bookkeeping."""
    _, srv = _server(seed=20)
    with pytest.raises(ValueError, match="stream 99 not open"):
        srv.close_stream(99)
    # the router's free list must be untouched by the rejected close
    srv.open_stream(1)
    assert srv.active == {1: 0}


def test_close_stream_double_close_raises_value_error():
    _, srv = _server(seed=20)
    srv.open_stream(7)
    srv.close_stream(7)
    with pytest.raises(ValueError, match="stream 7 not open"):
        srv.close_stream(7)
    # the slot freed by the first close is still reusable
    srv.open_stream(8)
    assert 8 in srv.active


def _state_snapshot(srv):
    return [np.asarray(leaf).copy()
            for leaf in jax.tree_util.tree_leaves(srv.state)]


def test_step_unopened_stream_rejected_before_any_mutation():
    """A tick naming an unopened stream must raise the canonical
    ValueError and leave the server BIT-unchanged — the pre-validation
    code KeyError'd out of the slab build mid-tick."""
    pipe, srv = _server(seed=21)
    srv.open_stream(1)
    srv.open_stream(2)
    fv = np.ones(16, np.float32)
    srv.step({1: fv, 2: fv})  # advance to a non-trivial state
    before = _state_snapshot(srv)
    active_before = dict(srv.active)
    with pytest.raises(ValueError, match=r"stream\(s\) \[99\] not open"):
        srv.step({1: fv, 99: fv})
    after = _state_snapshot(srv)
    assert len(before) == len(after)
    for a, b in zip(before, after):
        np.testing.assert_array_equal(a, b)
    assert srv.active == active_before
    # multiple unknown ids are all reported, sorted
    with pytest.raises(
        ValueError, match=r"stream\(s\) \[41, 99\] not open"
    ):
        srv.step({99: fv, 41: fv})
    # the offline replay driver shares the validation
    with pytest.raises(ValueError, match=r"stream\(s\) \[50\] not open"):
        srv.run({50: np.zeros(srv.pipeline.chunk_samples * 2, np.float32)})


def test_ambiguous_serving_geometry_rejected_at_construction():
    """A config where a raw audio hop and an FV_Norm frame have the
    same width would make `_is_raw` silently route every tick down the
    raw-audio path; the server must refuse to build. (The paper's
    geometry — 256-sample hops vs 16 channels — never collides; this
    uses fs_audio=1000 Hz so one 16 ms hop is exactly 16 samples.)"""
    from repro.core.fex import FExConfig

    cfg = KWSPipelineConfig(
        use_norm=False, fex=FExConfig(fs_audio=1000.0)
    )
    pipe = KWSPipeline(cfg)
    assert pipe.chunk_samples == pipe.config.fex.num_channels == 16
    params = pipe.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="ambiguous serving geometry"):
        StreamingKWSServer(pipe, params, max_streams=4)
