"""Temporal-sparsity ΔGRU backend: θ=0 bit-identity + telemetry suite.

The contract under test (repro.core.gru_delta): at θ=0 the delta engine
skips only exactly-unchanged components, its partial sums telescope to
the dense matmuls on the nose, and the "delta" backend is BIT-identical
(assert_array_equal, never allclose) to "qat" — and "delta-int" to
"integer" — for the full forward, the streaming step, the fused serving
tick, slab ingress, and the lax.scan replay. (The sharded multi-device
twin of these identities lives in tests/test_serve_sharded.py.) At
θ > 0 the skipped/total MAC counters must be monotone, bounded by the
offered work, exact in their totals, masked for idle streams, and reset
with the slot — the invariants `srv.sparsity` telemetry rests on.

Like the integer-identity suite, these tests are fast and run in the
`-m "not slow"` CI selection (and as an explicit first-class CI step).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import quant
from repro.core.classifier import available_classifiers, get_classifier
from repro.core.fex import fit_norm_stats
from repro.core.gru import (
    GRUConfig,
    gru_classifier_forward,
    gru_classifier_step,
    init_gru_classifier,
    init_states,
)
from repro.core.gru_delta import (
    DeltaConfig,
    delta_classifier_forward,
    delta_classifier_step,
    delta_eligible_macs_per_frame,
    delta_init_states,
    dense_fc_macs_per_frame,
    effective_mac_fraction,
    int_delta_classifier_forward,
    is_delta_states,
)
from repro.core.gru_int import (
    QuantizedClassifier,
    dequantize_acts,
    int_gru_classifier_forward,
    quantize_acts,
)
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.serving.quantize import quantize_classifier
from repro.serving.serve_loop import StreamingKWSServer

CFG = GRUConfig(quantized=True)
T0 = DeltaConfig().code_thresholds(CFG.num_layers)


def _params(seed=0):
    return init_gru_classifier(jax.random.PRNGKey(seed), CFG)


def _grid_fv(shape, seed=0, scale=4.0):
    x = jax.random.normal(jax.random.PRNGKey(seed), shape) * scale
    return quant.fake_quant(x, quant.ACT_Q6_8)


# --------------------------------------------------------------------------
# registry + config mechanics
# --------------------------------------------------------------------------

def test_delta_backends_registered():
    assert "delta" in available_classifiers()
    assert "delta-int" in available_classifiers()
    assert get_classifier("delta").name == "delta"
    assert get_classifier("delta-int").name == "delta-int"


def test_delta_config_validation():
    with pytest.raises(ValueError, match=">= 0"):
        DeltaConfig(theta_x=-0.1)
    with pytest.raises(ValueError, match=">= 0"):
        DeltaConfig(per_layer=((0.1, 0.1), (-0.2, 0.0)))
    with pytest.raises(ValueError, match="entries"):
        DeltaConfig(per_layer=((0.1, 0.1),)).code_thresholds(2)
    # thresholds snap to the Q6.8 grid, per layer
    dc = DeltaConfig(per_layer=((0.25, 0.5), (0.0, 1.0)))
    assert dc.code_thresholds(2) == ((64, 128), (0, 256))
    assert DeltaConfig(theta_x=0.25).code_thresholds(2) == ((64, 0), (64, 0))


def test_pipeline_binds_delta_config():
    """KWSPipelineConfig(delta=...) reaches the backend instance; the
    registry singleton itself stays at θ=0."""
    dc = DeltaConfig(theta_x=0.25, theta_h=0.125)
    pipe = KWSPipeline(KWSPipelineConfig(classifier="delta", delta=dc))
    assert pipe.classifier.delta == dc
    assert get_classifier("delta").delta == DeltaConfig()
    # delta=None (the default) keeps the θ=0 singleton
    pipe0 = KWSPipeline(KWSPipelineConfig(classifier="delta"))
    assert pipe0.classifier is get_classifier("delta")
    # dense backends ignore the field entirely
    pq = KWSPipeline(KWSPipelineConfig(classifier="qat", delta=dc))
    assert pq.classifier is get_classifier("qat")


def test_prepare_params_shapes():
    params = _params()
    pd = KWSPipeline(KWSPipelineConfig(classifier="delta"))
    assert pd.prepare_params(params) is params  # float domain: untouched
    pdi = KWSPipeline(KWSPipelineConfig(classifier="delta-int"))
    q = pdi.prepare_params(params)
    assert isinstance(q, QuantizedClassifier)
    assert pdi.prepare_params(q) is q  # idempotent
    with pytest.raises(TypeError, match="prepare_params"):
        get_classifier("delta-int").forward(params, _grid_fv((1, 2, 16)), CFG)


# --------------------------------------------------------------------------
# θ=0 bit-identity: forward + streaming step
# --------------------------------------------------------------------------

def test_forward_theta0_bit_identical_to_qat():
    params = _params(0)
    fv = _grid_fv((3, 25, 16), seed=1)
    ref = gru_classifier_forward(params, fv, CFG)
    out = delta_classifier_forward(params, fv, CFG, T0)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_forward_theta0_bit_identical_to_integer():
    params = _params(1)
    q = quantize_classifier(params, CFG)
    fv = _grid_fv((3, 25, 16), seed=2)
    ref = int_gru_classifier_forward(q, quantize_acts(fv), CFG)
    out = int_delta_classifier_forward(q, quantize_acts(fv), CFG, T0)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_streaming_step_theta0_tracks_qat_states():
    params = _params(2)
    fv = _grid_fv((4, 15, 16), seed=3)
    sq = init_states(CFG, 4)
    sd = delta_init_states(CFG, 4)
    for t in range(fv.shape[1]):
        sq, lq = gru_classifier_step(params, sq, fv[:, t], CFG)
        sd, ld = delta_classifier_step(params, sd, fv[:, t], CFG, T0)
        np.testing.assert_array_equal(np.asarray(lq), np.asarray(ld))
        for hq, std in zip(sq, sd):
            np.testing.assert_array_equal(
                np.asarray(hq), np.asarray(std["h"])
            )


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    scale=st.floats(min_value=0.25, max_value=16.0),
    t=st.integers(min_value=1, max_value=8),
)
def test_forward_theta0_identity_property(seed, scale, t):
    """Identity must hold for any on-grid input (magnitude and length
    swept), in both arithmetic domains."""
    params = _params(seed % 5)
    q = quantize_classifier(params, CFG)
    fv = quant.fake_quant(
        jax.random.normal(jax.random.PRNGKey(seed), (2, t, 16)) * scale,
        quant.ACT_Q6_8,
    )
    np.testing.assert_array_equal(
        np.asarray(gru_classifier_forward(params, fv, CFG)),
        np.asarray(delta_classifier_forward(params, fv, CFG, T0)),
    )
    np.testing.assert_array_equal(
        np.asarray(int_gru_classifier_forward(q, quantize_acts(fv), CFG)),
        np.asarray(
            int_delta_classifier_forward(q, quantize_acts(fv), CFG, T0)
        ),
    )


def test_pipeline_logits_and_predict_parity():
    audio = jnp.asarray(
        np.random.default_rng(4).standard_normal((3, 8192)).astype(
            np.float32
        ) * 0.05
    )
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(audio)
    stats = fit_norm_stats(quant.log_compress_lut(raw, 12, 10))
    pq = KWSPipeline(KWSPipelineConfig(classifier="qat"), norm_stats=stats)
    pd = KWSPipeline(KWSPipelineConfig(classifier="delta"), norm_stats=stats)
    params = pq.init_params(jax.random.PRNGKey(4))
    fv, _ = pq.features(audio)
    np.testing.assert_array_equal(
        np.asarray(pq.logits(params, fv)), np.asarray(pd.logits(params, fv))
    )
    np.testing.assert_array_equal(
        np.asarray(pq.predict(params, audio)),
        np.asarray(pd.predict(params, audio)),
    )


# --------------------------------------------------------------------------
# θ=0 bit-identity: the whole serving stack (single device; the sharded
# twin lives in tests/test_serve_sharded.py)
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


def _server(norm_stats, params, classifier, theta=0.0, max_streams=4):
    pipe = KWSPipeline(
        KWSPipelineConfig(
            classifier=classifier,
            delta=DeltaConfig(theta_x=theta, theta_h=theta),
        ),
        norm_stats=norm_stats,
    )
    return StreamingKWSServer(pipe, params, max_streams=max_streams)


@pytest.mark.parametrize(
    "delta_key,base_key", [("delta", "qat"), ("delta-int", "integer")]
)
def test_server_theta0_bit_identical(
    norm_stats, shared_params, delta_key, base_key
):
    """Fused tick (raw audio + FV slabs, partial masks) and the scan
    replay: the θ=0 delta server matches its dense base bit for bit."""
    sb = _server(norm_stats, shared_params, base_key)
    sd = _server(norm_stats, shared_params, delta_key)
    for s in (sb, sd):
        for sid in range(3):
            s.open_stream(sid)
    hop = sb.pipeline.chunk_samples
    rng = np.random.default_rng(8)
    for t in range(3):  # live raw-audio ticks, rotating partial masks
        slab = rng.standard_normal((4, hop)).astype(np.float32) * 0.05
        mask = np.zeros(4, bool)
        mask[:3] = True
        mask[t % 3] = False
        s_a, t_a = sb.step_batch(slab, mask)
        s_b, t_b = sd.step_batch(slab, mask)
        np.testing.assert_array_equal(s_a, s_b)
        np.testing.assert_array_equal(t_a, t_b)
    # FV_Norm ticks must sit on the Q6.8 grid (the documented input
    # contract — cross-backend identity only holds for grid frames,
    # exactly as in the integer/QAT suite)
    fv = np.asarray(
        quant.fake_quant(
            jnp.asarray(rng.standard_normal((4, 16)).astype(np.float32)),
            quant.ACT_Q6_8,
        )
    )
    s_a, _ = sb.step_batch(fv, np.ones(4, bool))
    s_b, _ = sd.step_batch(fv, np.ones(4, bool))
    np.testing.assert_array_equal(s_a, s_b)
    # scan replay
    slab = rng.standard_normal((5, 4, hop)).astype(np.float32) * 0.05
    mask = rng.random((5, 4)) < 0.7
    seq_a, tops_a = sb.run_batch(slab, mask)
    seq_b, tops_b = sd.run_batch(slab, mask)
    np.testing.assert_array_equal(seq_a, seq_b)
    np.testing.assert_array_equal(tops_a, tops_b)
    # the delta server's hidden state tracks the dense server's exactly
    for hb, std in zip(sb.state.gru, sd.state.gru):
        np.testing.assert_array_equal(
            np.asarray(hb), np.asarray(std["h"])
        )


def test_theta_gt0_cross_domain_equality(norm_stats, shared_params):
    """At θ>0 the float- and code-domain ΔGRU engines fire identically
    and produce bit-identical posteriors (same grid arithmetic)."""
    sd = _server(norm_stats, shared_params, "delta", theta=0.25)
    si = _server(norm_stats, shared_params, "delta-int", theta=0.25)
    for s in (sd, si):
        s.open_stream(0)
    hop = sd.pipeline.chunk_samples
    rng = np.random.default_rng(9)
    for _ in range(5):
        f = rng.standard_normal(hop).astype(np.float32) * 0.05
        od = sd.step({0: f})
        oi = si.step({0: f})
        np.testing.assert_array_equal(od[0]["probs"], oi[0]["probs"])
    np.testing.assert_array_equal(sd.sparsity, si.sparsity)
    assert sd.sparsity[sd.active[0]] < 1.0


# --------------------------------------------------------------------------
# θ>0: MAC counters + sparsity telemetry invariants
# --------------------------------------------------------------------------

def _counters(srv):
    sk = np.stack([np.asarray(st["skipped"]) for st in srv.state.gru])
    to = np.stack([np.asarray(st["total"]) for st in srv.state.gru])
    return sk, to


def test_counters_monotone_and_bounded(norm_stats, shared_params):
    srv = _server(norm_stats, shared_params, "delta", theta=0.25)
    srv.open_stream(0)
    srv.open_stream(1)
    hop = srv.pipeline.chunk_samples
    rng = np.random.default_rng(10)
    prev_sk = prev_to = None
    # counters tick in weight-column units: a layer offers I+H columns
    # per frame (each worth 3H MACs — effective_mac_fraction converts)
    per_step = [
        i + CFG.hidden_dim for i in (CFG.input_dim, CFG.hidden_dim)
    ]
    for t in range(6):
        srv.step({
            sid: rng.standard_normal(hop).astype(np.float32) * 0.05
            for sid in (0, 1)
        })
        sk, to = _counters(srv)
        assert (sk >= 0).all() and (sk <= to).all()
        if prev_sk is not None:  # monotone, never decreasing
            assert (sk >= prev_sk).all() and (to >= prev_to).all()
        # totals are exact: (t+1) steps of the full offered work per
        # open slot, zero elsewhere
        for layer, per in enumerate(per_step):
            for sid in (0, 1):
                assert to[layer, srv.active[sid]] == (t + 1) * per
        prev_sk, prev_to = sk, to
    frac = srv.sparsity
    assert frac.shape == (4,) and ((frac >= 0) & (frac <= 1)).all()
    assert is_delta_states(list(srv.state.gru))


def test_repeated_frame_is_skipped(norm_stats, shared_params):
    """Submitting the same FV frame twice: the second tick's input
    deltas are all zero, so the input-side counters must record a full
    skip (the DeltaKWS steady-state win)."""
    srv = _server(norm_stats, shared_params, "delta", theta=0.0)
    srv.open_stream(0)
    fv = np.asarray(_grid_fv((16,), seed=11, scale=2.0))
    srv.step({0: fv})
    sk1, _ = _counters(srv)
    srv.step({0: fv})
    sk2, to2 = _counters(srv)
    slot = srv.active[0]
    # layer 0 skipped at least the whole input matmul on tick 2 (all
    # input_dim weight columns)
    assert sk2[0, slot] - sk1[0, slot] >= CFG.input_dim
    assert srv.sparsity[slot] < 1.0


def test_counters_idle_isolation_and_reset(norm_stats, shared_params):
    """Idle streams' counters are untouched by other streams' ticks;
    open_stream hands out zeroed counters (sparsity telemetry resets
    with the slot)."""
    srv = _server(norm_stats, shared_params, "delta", theta=0.25)
    srv.open_stream(0)
    srv.open_stream(1)
    hop = srv.pipeline.chunk_samples
    rng = np.random.default_rng(12)
    srv.step({
        sid: rng.standard_normal(hop).astype(np.float32) * 0.05
        for sid in (0, 1)
    })
    slot1 = srv.active[1]
    sk_before, to_before = _counters(srv)
    for _ in range(3):  # stream 1 idles
        srv.step({0: rng.standard_normal(hop).astype(np.float32) * 0.05})
    sk_after, to_after = _counters(srv)
    np.testing.assert_array_equal(sk_before[:, slot1], sk_after[:, slot1])
    np.testing.assert_array_equal(to_before[:, slot1], to_after[:, slot1])
    # close + reopen: the reused slot's telemetry starts fresh
    frac_open = srv.sparsity[slot1]
    srv.close_stream(1)
    srv.open_stream(99)
    assert srv.active[99] == slot1
    sk, to = _counters(srv)
    assert (sk[:, slot1] == 0).all() and (to[:, slot1] == 0).all()
    assert srv.sparsity[slot1] == 1.0
    del frac_open


def test_dense_backends_report_unity_sparsity(norm_stats, shared_params):
    srv = _server(norm_stats, shared_params, "qat")
    np.testing.assert_array_equal(
        srv.sparsity, np.ones(srv.max_streams, np.float32)
    )


def test_effective_mac_fraction_accounting():
    """The fraction folds the always-dense FC back in: a stream that
    skipped every eligible MAC still pays the FC head."""
    states = delta_init_states(CFG, 2)
    per = delta_eligible_macs_per_frame(CFG)
    fc = dense_fc_macs_per_frame(CFG)
    h = CFG.hidden_dim
    per_layer_cols = [CFG.input_dim + h, h + h]  # counter units: columns
    assert sum(3 * h * c for c in per_layer_cols) == per
    # stream 0: one frame, every eligible column skipped; stream 1: no
    # traffic at all
    for st_l, cols in zip(states, per_layer_cols):
        st_l["total"] = jnp.asarray([cols, 0], jnp.int32)
        st_l["skipped"] = jnp.asarray([cols, 0], jnp.int32)
    frac = np.asarray(effective_mac_fraction(states, CFG))
    np.testing.assert_allclose(frac[0], fc / (per + fc), rtol=1e-6)
    assert frac[1] == 1.0


# --------------------------------------------------------------------------
# property test: random lifecycle schedules, delta(θ=0) vs qat
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def schedule_servers(norm_stats, shared_params):
    """(delta θ=0, qat) servers on shared params — module-scoped so
    hypothesis examples reuse the compiled tick programs (the PR 4
    lifecycle-oracle harness, pointed at the ΔGRU backend)."""
    sd = _server(norm_stats, shared_params, "delta", max_streams=8)
    sq = _server(norm_stats, shared_params, "qat", max_streams=8)
    return sd, sq


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
def test_random_schedule_delta_matches_qat(schedule_servers, seed, events):
    """Random open/close/submit schedules: the θ=0 delta server's
    per-stream posteriors bit-match the qat server's at every tick —
    lifecycle hygiene (slot reuse, idle masking) included."""
    sd, sq = schedule_servers
    for srv in (sd, sq):
        for sid in list(srv.active):
            srv.close_stream(sid)
    rng = np.random.default_rng(seed)
    next_sid = 0

    def do_open():
        nonlocal next_sid
        sd.open_stream(next_sid)
        sq.open_stream(next_sid)
        next_sid += 1

    do_open()
    for want_open, want_close, submit_bits in events:
        if want_close and len(sd.active) > 1:
            victim = min(sd.active)
            sd.close_stream(victim)
            sq.close_stream(victim)
        if want_open and len(sd.active) < sd.max_streams:
            do_open()
        frames = {}
        for i, sid in enumerate(sorted(sd.active)):
            if submit_bits >> (i % 8) & 1:
                # on the Q6.8 grid — the FV_Norm input contract
                frames[sid] = np.asarray(
                    quant.fake_quant(
                        jnp.asarray(
                            rng.standard_normal(16).astype(np.float32)
                        ),
                        quant.ACT_Q6_8,
                    )
                )
        out_d = sd.step(dict(frames))
        out_q = sq.step(dict(frames))
        for sid in frames:
            np.testing.assert_array_equal(
                out_d[sid]["probs"], out_q[sid]["probs"]
            )
            assert out_d[sid]["top"] == out_q[sid]["top"]
    np.testing.assert_array_equal(sd.scores, sq.scores)
