"""Cascaded always-on wake serving: always-open bit-identity + gating
semantics + wake-rate telemetry (repro.serving.cascade).

The contract under test: the stage-1 detector produces nonnegative
scores, so an always-open gate (`CascadeConfig.always_on()`, i.e.
wake_threshold=0) makes the cascaded server BIT-identical
(assert_array_equal, never allclose) to the non-cascaded server for
EVERY registered classifier backend — fused tick (raw audio + FV
slabs, partial masks), slab ingress, and the lax.scan replay. (The
sharded multi-device twin of these identities lives in
tests/test_serve_sharded.py.) At wake_threshold > 0 the gate must
hold a gated stream's classifier state frozen (optionally decaying
its posterior), honor the hysteresis/hangover state machine, and keep
`srv.wake_rate` exact: reset with the slot, frozen while idle,
identical between live ticks and the scanned replay.

Like the integer/delta identity suites, these tests are fast and run
in the `-m "not slow"` CI selection (and as an explicit CI step).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.classifier import get_classifier
from repro.core.fex import fit_norm_stats
from repro.core.gru_delta import DeltaConfig
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.serving.cascade import (
    CascadeConfig,
    detector_scores,
    fit_linear_detector,
    gate_step,
    init_state,
    wake_rate,
)
from repro.serving.serve_loop import StreamingKWSServer

CLASSIFIERS = ("float", "qat", "integer", "delta", "delta-int")

# on the Q6.8 grid; energy score 0.0 (all channels below the corpus
# mean, the normalized shape of silence) vs 2.0 (speech-like)
SILENCE_FV = np.full((16,), -1.0, np.float32)
LOUD_FV = np.full((16,), 2.0, np.float32)


# --------------------------------------------------------------------------
# config + detector mechanics
# --------------------------------------------------------------------------

def test_cascade_config_validation():
    with pytest.raises(ValueError, match="detector"):
        CascadeConfig(detector="fft")
    with pytest.raises(ValueError, match="wake_threshold"):
        CascadeConfig(wake_threshold=-0.1)
    with pytest.raises(ValueError, match="release"):
        CascadeConfig(wake_threshold=0.1, release_threshold=0.2)
    with pytest.raises(ValueError, match="release"):
        CascadeConfig(wake_threshold=0.1, release_threshold=-0.05)
    with pytest.raises(ValueError, match="hangover"):
        CascadeConfig(hangover_frames=-1)
    with pytest.raises(ValueError, match="score_decay"):
        CascadeConfig(score_decay=1.5)
    with pytest.raises(ValueError, match="linear_w"):
        CascadeConfig(detector="linear", wake_threshold=0.5)


def test_always_on_is_always_open():
    assert CascadeConfig.always_on().always_open
    assert CascadeConfig().always_open  # default threshold is 0
    assert not CascadeConfig(wake_threshold=0.1).always_open
    # release defaults to wake (no hysteresis band)
    assert CascadeConfig(wake_threshold=0.3).release == 0.3
    assert (
        CascadeConfig(wake_threshold=0.3, release_threshold=0.1).release
        == 0.1
    )


def test_config_hashable_with_linear_weights():
    """linear_w normalizes to a float tuple so the config stays
    hashable (it is closed over statically by the fused tick's jit)."""
    cc = CascadeConfig(
        detector="linear",
        wake_threshold=0.5,
        linear_w=np.ones(16, np.float32),
    )
    assert isinstance(cc.linear_w, tuple)
    assert hash(cc) == hash(dataclasses.replace(cc))


def test_pipeline_binds_cascade_config():
    cc = CascadeConfig(wake_threshold=0.25)
    cfg = KWSPipelineConfig(classifier="qat", cascade=cc)
    assert cfg.cascade is cc
    assert KWSPipelineConfig().cascade is None
    # the cascade composes around the backend, it does not replace it
    assert (
        KWSPipeline(cfg).classifier is get_classifier("qat")
    )


def test_energy_detector_scores():
    fv = jnp.stack([jnp.asarray(SILENCE_FV), jnp.asarray(LOUD_FV)])
    sc = np.asarray(detector_scores(fv, CascadeConfig()))
    np.testing.assert_array_equal(sc, np.asarray([0.0, 2.0], np.float32))
    # mixed frame: mean of the positive channels only
    mixed = jnp.asarray([3.0] * 4 + [-5.0] * 12, jnp.float32)
    assert float(detector_scores(mixed, CascadeConfig())) == pytest.approx(
        12.0 / 16.0
    )


def test_detector_scores_nonnegative():
    """The structural guarantee `always_open` rests on: both detectors
    score >= 0 for any input."""
    fv = jax.random.normal(jax.random.PRNGKey(0), (64, 16)) * 10.0
    assert (np.asarray(detector_scores(fv, CascadeConfig())) >= 0).all()
    lc = CascadeConfig(
        detector="linear", linear_w=tuple(np.linspace(-2, 2, 16))
    )
    sc = np.asarray(detector_scores(fv, lc))
    assert (sc >= 0).all() and (sc <= 1).all()


def test_gate_step_hysteresis_and_hangover():
    """Score trajectory 0.6, 0.3, 0.1, 0.1, 0.1 at wake=0.5,
    release=0.2, hangover=1: the latch holds through 0.3 (inside the
    hysteresis band), drops at 0.1, and the hangover keeps the gate
    open one extra tick."""
    cc = CascadeConfig(
        wake_threshold=0.5, release_threshold=0.2, hangover_frames=1
    )
    st = init_state(1)
    gates, awakes = [], []
    for s in (0.6, 0.3, 0.1, 0.1, 0.1):
        st, gate = gate_step(st, jnp.asarray([s], jnp.float32), cc)
        gates.append(bool(gate[0]))
        awakes.append(bool(st["awake"][0]))
    assert awakes == [True, True, False, False, False]
    assert gates == [True, True, True, False, False]
    assert int(st["woken"][0]) == 3 and int(st["ticks"][0]) == 5
    assert float(wake_rate(st)[0]) == pytest.approx(0.6)


def test_wake_rate_unity_without_traffic():
    np.testing.assert_array_equal(
        np.asarray(wake_rate(init_state(3))), np.ones(3, np.float32)
    )


def test_fit_linear_detector_separates():
    rng = np.random.default_rng(0)
    speech = rng.normal(0.8, 0.4, (300, 16)).astype(np.float32)
    silence = rng.normal(-0.8, 0.4, (300, 16)).astype(np.float32)
    w, b = fit_linear_detector(speech, silence, steps=100)
    cc = CascadeConfig(detector="linear", linear_w=w, linear_b=b)
    s_speech = np.asarray(detector_scores(jnp.asarray(speech), cc))
    s_sil = np.asarray(detector_scores(jnp.asarray(silence), cc))
    assert s_speech.mean() > 0.9 and s_sil.mean() < 0.1


# --------------------------------------------------------------------------
# always-open bit-identity: the whole serving stack, every backend
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def norm_stats():
    rng = np.random.default_rng(0)
    audio = jnp.asarray(
        rng.standard_normal((4, 16000)).astype(np.float32) * 0.05
    )
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(audio)
    return fit_norm_stats(quant.log_compress_lut(raw, 12, 10))


@pytest.fixture(scope="module")
def shared_params():
    return KWSPipeline(KWSPipelineConfig()).init_params(
        jax.random.PRNGKey(7)
    )


def _server(norm_stats, params, classifier, cascade=None, theta=0.0,
            max_streams=4):
    pipe = KWSPipeline(
        KWSPipelineConfig(
            classifier=classifier,
            delta=DeltaConfig(theta_x=theta, theta_h=theta),
            cascade=cascade,
        ),
        norm_stats=norm_stats,
    )
    return StreamingKWSServer(pipe, params, max_streams=max_streams)


def _assert_gru_identical(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y)
        ),
        list(a.state.gru),
        list(b.state.gru),
    )


@pytest.mark.parametrize("classifier", CLASSIFIERS)
def test_always_open_bit_identical(norm_stats, shared_params, classifier):
    """`CascadeConfig.always_on()` degenerates the wake mask to the
    submitted mask: every backend's cascaded server matches the plain
    one bit for bit across live ticks (raw audio, rotating partial
    masks), FV slab ingress, and the scan replay."""
    plain = _server(norm_stats, shared_params, classifier)
    casc = _server(
        norm_stats, shared_params, classifier,
        cascade=CascadeConfig.always_on(),
    )
    for s in (plain, casc):
        for sid in range(3):
            s.open_stream(sid)
    hop = plain.pipeline.chunk_samples
    rng = np.random.default_rng(8)
    for t in range(3):  # live raw-audio ticks, rotating partial masks
        slab = rng.standard_normal((4, hop)).astype(np.float32) * 0.05
        mask = np.zeros(4, bool)
        mask[:3] = True
        mask[t % 3] = False
        s_a, t_a = plain.step_batch(slab, mask)
        s_b, t_b = casc.step_batch(slab, mask)
        np.testing.assert_array_equal(s_a, s_b)
        np.testing.assert_array_equal(t_a, t_b)
    # FV_Norm tick on the Q6.8 grid (the documented input contract)
    fv = np.asarray(
        quant.fake_quant(
            jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32)),
            quant.ACT_Q6_8,
        )
    )
    s_a, _ = plain.step_batch(fv, np.ones(4, bool))
    s_b, _ = casc.step_batch(fv, np.ones(4, bool))
    np.testing.assert_array_equal(s_a, s_b)
    # scan replay
    slab = rng.standard_normal((5, 4, hop)).astype(np.float32) * 0.05
    mask = rng.random((5, 4)) < 0.7
    seq_a, tops_a = plain.run_batch(slab, mask)
    seq_b, tops_b = casc.run_batch(slab, mask)
    np.testing.assert_array_equal(seq_a, seq_b)
    np.testing.assert_array_equal(tops_a, tops_b)
    # hidden state + scores identical; every submitted tick woke
    _assert_gru_identical(plain, casc)
    np.testing.assert_array_equal(plain.scores, casc.scores)
    np.testing.assert_array_equal(
        casc.wake_rate, np.ones(4, np.float32)
    )
    # and the plain server reports unity wake rate by definition
    np.testing.assert_array_equal(
        plain.wake_rate, np.ones(4, np.float32)
    )


def test_always_open_linear_detector_bit_identical(
    norm_stats, shared_params
):
    """The guarantee is detector-independent: a trained linear scorer
    at wake_threshold=0 (sigmoid >= 0) is also always-open."""
    rng = np.random.default_rng(13)
    cc = CascadeConfig.always_on(
        detector="linear",
        linear_w=tuple(rng.standard_normal(16)),
        linear_b=-0.3,
    )
    plain = _server(norm_stats, shared_params, "qat")
    casc = _server(norm_stats, shared_params, "qat", cascade=cc)
    for s in (plain, casc):
        s.open_stream(0)
    hop = plain.pipeline.chunk_samples
    for _ in range(4):
        f = rng.standard_normal(hop).astype(np.float32) * 0.05
        a = plain.step({0: f})
        b = casc.step({0: f})
        np.testing.assert_array_equal(a[0]["probs"], b[0]["probs"])
    np.testing.assert_array_equal(casc.wake_rate[casc.active[0]], 1.0)


# --------------------------------------------------------------------------
# gating semantics: frozen hold, decay, hangover, telemetry
# --------------------------------------------------------------------------

def test_silence_stream_never_wakes(norm_stats, shared_params):
    """A pure-silence stream's classifier state stays at its fresh
    zeros (the gate held it asleep from the first tick) and its wake
    rate reads 0."""
    srv = _server(
        norm_stats, shared_params, "qat",
        cascade=CascadeConfig(wake_threshold=0.1),
    )
    srv.open_stream(0)
    slot = srv.active[0]
    for _ in range(5):
        srv.step({0: SILENCE_FV})
    np.testing.assert_array_equal(
        srv.scores[slot], np.zeros(12, np.float32)
    )
    for h in srv.state.gru:
        np.testing.assert_array_equal(
            np.asarray(h)[slot], np.zeros_like(np.asarray(h)[slot])
        )
    assert srv.wake_rate[slot] == 0.0


def test_gate_wakes_holds_and_hangs_over(norm_stats, shared_params):
    """Loud frame wakes the classifier; the hangover keeps it running
    through trailing silence; past the hangover the hidden state holds
    frozen. woken/ticks counters are exact."""
    srv = _server(
        norm_stats, shared_params, "qat",
        cascade=CascadeConfig(wake_threshold=0.1, hangover_frames=2),
    )
    srv.open_stream(0)
    slot = srv.active[0]
    srv.step({0: LOUD_FV})
    assert srv.wake_rate[slot] == 1.0
    assert np.any(srv.scores[slot] != 0)
    for _ in range(4):
        srv.step({0: SILENCE_FV})
    # woken = 1 (loud) + 2 (hangover) of 5 submitted ticks
    det = srv.state.det
    assert int(np.asarray(det["woken"])[slot]) == 3
    assert int(np.asarray(det["ticks"])[slot]) == 5
    assert srv.wake_rate[slot] == pytest.approx(3 / 5)
    # fully gated now: further silence leaves the classifier state
    # bit-identical (frozen hold; default score_decay=1.0)
    h_before = [np.asarray(h)[slot].copy() for h in srv.state.gru]
    s_before = srv.scores[slot].copy()
    srv.step({0: SILENCE_FV})
    for h, hb in zip(srv.state.gru, h_before):
        np.testing.assert_array_equal(np.asarray(h)[slot], hb)
    np.testing.assert_array_equal(srv.scores[slot], s_before)


def test_score_decay_on_gated_ticks(norm_stats, shared_params):
    """score_decay < 1 forgets a stale detection while the classifier
    sleeps: each gated tick multiplies the held posterior exactly."""
    srv = _server(
        norm_stats, shared_params, "qat",
        cascade=CascadeConfig(wake_threshold=0.1, score_decay=0.5),
    )
    srv.open_stream(0)
    slot = srv.active[0]
    srv.step({0: LOUD_FV})
    s0 = srv.scores[slot].copy()
    srv.step({0: SILENCE_FV})  # gated: no hangover configured
    np.testing.assert_array_equal(srv.scores[slot], s0 * np.float32(0.5))
    srv.step({0: SILENCE_FV})
    np.testing.assert_array_equal(srv.scores[slot], s0 * np.float32(0.25))


def test_wake_telemetry_idle_freeze_and_slot_reset(
    norm_stats, shared_params
):
    """`srv.wake_rate` has the `srv.sparsity` telemetry contract:
    frozen while the stream idles (other streams' traffic is
    invisible), reset with the slot on open_stream."""
    srv = _server(
        norm_stats, shared_params, "qat",
        cascade=CascadeConfig(wake_threshold=0.1),
    )
    srv.open_stream(0)
    srv.open_stream(1)
    slot1 = srv.active[1]
    srv.step({0: LOUD_FV, 1: LOUD_FV})
    srv.step({0: SILENCE_FV, 1: SILENCE_FV})
    wr_before = srv.wake_rate[slot1]
    assert wr_before == pytest.approx(0.5)
    for fv in (LOUD_FV, SILENCE_FV, LOUD_FV):  # stream 1 idles
        srv.step({0: fv})
    assert srv.wake_rate[slot1] == wr_before
    # close + reopen: the reused slot's gate state starts fresh
    srv.close_stream(1)
    srv.open_stream(99)
    assert srv.active[99] == slot1
    det = srv.state.det
    for leaf in det.values():
        assert np.asarray(leaf)[slot1] == 0
    assert srv.wake_rate[slot1] == 1.0


@pytest.mark.parametrize("classifier", ("qat", "integer", "delta",
                                        "delta-int"))
def test_scan_replay_matches_live_ticks(norm_stats, shared_params,
                                        classifier):
    """The gate is exact under `lax.scan`: replaying a slab through
    run_batch leaves scores, GRU state AND detector counters
    bit-identical to the same traffic through live step_batch ticks,
    for each classifier backend (the ΔGRU pair at a real θ)."""
    cc = CascadeConfig(wake_threshold=0.1, hangover_frames=1)
    theta = 0.25 if classifier.startswith("delta") else 0.0
    live = _server(norm_stats, shared_params, classifier, cascade=cc,
                   theta=theta)
    scan = _server(norm_stats, shared_params, classifier, cascade=cc,
                   theta=theta)
    for s in (live, scan):
        for sid in range(3):
            s.open_stream(sid)
    rng = np.random.default_rng(21)
    slab = np.zeros((6, 4, 16), np.float32)
    for t in range(6):
        for n in range(4):
            slab[t, n] = LOUD_FV if rng.random() < 0.4 else SILENCE_FV
    mask = rng.random((6, 4)) < 0.7
    for t in range(6):
        live.step_batch(slab[t], mask[t])
    scan.run_batch(slab, mask)
    np.testing.assert_array_equal(live.scores, scan.scores)
    _assert_gru_identical(live, scan)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        live.state.det,
        scan.state.det,
    )
    np.testing.assert_array_equal(live.wake_rate, scan.wake_rate)


def test_cascade_composes_with_delta(norm_stats, shared_params):
    """Cascade x ΔGRU: gated ticks freeze the delta MAC counters (the
    classifier never ran), so `srv.sparsity` measures sparsity WITHIN
    the woken ticks — the factor that multiplies with `srv.wake_rate`
    in the energy model."""
    srv = _server(
        norm_stats, shared_params, "delta", theta=0.25,
        cascade=CascadeConfig(wake_threshold=0.1),
    )
    srv.open_stream(0)
    slot = srv.active[0]
    srv.step({0: LOUD_FV})
    totals_after_wake = [
        int(np.asarray(st["total"])[slot]) for st in srv.state.gru
    ]
    assert all(t > 0 for t in totals_after_wake)
    sparsity_after_wake = srv.sparsity[slot]
    for _ in range(3):
        srv.step({0: SILENCE_FV})
    totals_after_gate = [
        int(np.asarray(st["total"])[slot]) for st in srv.state.gru
    ]
    assert totals_after_gate == totals_after_wake
    assert srv.sparsity[slot] == sparsity_after_wake
    assert srv.wake_rate[slot] == pytest.approx(1 / 4)
