"""Classifier backend registry + integer/QAT bit-identity suite.

The contract under test (promised in repro.core.quant's docstring): the
bit-exact integer engine (`repro.core.gru_int` — int8 weight codes,
Q6.8 activation codes, saturating-int24 matmuls, LUT sigmoid/tanh) is
BIT-identical to the QAT fake-quant forward of `repro.core.gru` on the
same parameters, for the full forward, the streaming step, and the
whole serving stack (fused tick, slab ingress, lax.scan replay). These
tests are deliberately exact (assert_array_equal, never allclose) and
fast — they run in the `-m "not slow"` CI selection so any parity
regression fails on every PR.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core import quant
from repro.core.classifier import (
    available_classifiers,
    get_classifier,
    resolve_classifier_key,
)
from repro.core.fex import fit_norm_stats
from repro.core.gru import (
    GRUConfig,
    gru_classifier_forward,
    gru_classifier_step,
    init_gru_classifier,
    init_states,
)
from repro.core.gru_int import (
    QuantizedClassifier,
    dequantize_acts,
    int_gru_classifier_forward,
    int_gru_classifier_step,
    int_init_states,
    quantize_acts,
)
from repro.core.pipeline import KWSPipeline, KWSPipelineConfig
from repro.serving.quantize import quantize_classifier
from repro.serving.serve_loop import StreamingKWSServer

CFG = GRUConfig(quantized=True)


def _params(seed=0):
    return init_gru_classifier(jax.random.PRNGKey(seed), CFG)


def _grid_fv(shape, seed=0, scale=4.0):
    """Random FV_Norm input snapped to the Q6.8 grid, as the pipeline's
    post-processing guarantees for real traffic."""
    x = jax.random.normal(jax.random.PRNGKey(seed), shape) * scale
    return quant.fake_quant(x, quant.ACT_Q6_8)


# --------------------------------------------------------------------------
# registry mechanics (mirrors the frontend registry contract)
# --------------------------------------------------------------------------

def test_registry_contents():
    assert available_classifiers() == (
        "delta", "delta-int", "float", "integer", "qat"
    )
    for name in available_classifiers():
        assert get_classifier(name).name == name


def test_unknown_classifier_rejected():
    with pytest.raises(KeyError, match="unknown classifier"):
        get_classifier("analog")
    with pytest.raises(KeyError, match="unknown classifier"):
        KWSPipeline(KWSPipelineConfig(classifier="analog"))


def test_default_resolution_follows_gru_quantized():
    assert resolve_classifier_key(None, GRUConfig(quantized=True)) == "qat"
    assert resolve_classifier_key(None, GRUConfig(quantized=False)) == "float"
    assert resolve_classifier_key("integer", CFG) == "integer"
    assert KWSPipeline(KWSPipelineConfig()).classifier.name == "qat"
    assert (
        KWSPipeline(
            KWSPipelineConfig(gru=GRUConfig(quantized=False))
        ).classifier.name
        == "float"
    )


def test_prepare_params_idempotent():
    pipe = KWSPipeline(KWSPipelineConfig(classifier="integer"))
    params = _params()
    q = pipe.prepare_params(params)
    assert isinstance(q, QuantizedClassifier)
    assert pipe.prepare_params(q) is q
    # float/qat backends pass float params through untouched
    pipe_qat = KWSPipeline(KWSPipelineConfig(classifier="qat"))
    assert pipe_qat.prepare_params(params) is params


def test_integer_backend_rejects_unprepared_params():
    backend = get_classifier("integer")
    with pytest.raises(TypeError, match="prepare_params"):
        backend.step(_params(), int_init_states(CFG, 1), jnp.zeros((1, 16)), CFG)


def test_quantize_classifier_checks_geometry():
    with pytest.raises(ValueError, match="layers"):
        quantize_classifier(
            _params(), GRUConfig(num_layers=3, quantized=True)
        )


# --------------------------------------------------------------------------
# bit-identity: integer engine vs QAT fake-quant
# --------------------------------------------------------------------------

def test_forward_bit_identical_to_qat():
    params = _params(0)
    q = quantize_classifier(params, CFG)
    fv = _grid_fv((3, 25, 16), seed=1)
    ref = gru_classifier_forward(params, fv, CFG)
    out = dequantize_acts(int_gru_classifier_forward(q, quantize_acts(fv), CFG))
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_streaming_step_bit_identical_to_qat():
    params = _params(2)
    q = quantize_classifier(params, CFG)
    fv = _grid_fv((4, 15, 16), seed=3)
    states_f = init_states(CFG, 4)
    states_i = int_init_states(CFG, 4)
    for t in range(fv.shape[1]):
        states_f, lf = gru_classifier_step(params, states_f, fv[:, t], CFG)
        states_i, li = int_gru_classifier_step(
            q, states_i, quantize_acts(fv[:, t]), CFG
        )
        np.testing.assert_array_equal(
            np.asarray(lf), np.asarray(dequantize_acts(li))
        )
        # the hidden-state codes themselves track the QAT values exactly
        for hf, hi in zip(states_f, states_i):
            np.testing.assert_array_equal(
                np.asarray(hf), np.asarray(dequantize_acts(hi))
            )


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**31 - 1),
    scale=st.floats(min_value=0.25, max_value=16.0),
    t=st.integers(min_value=1, max_value=8),
)
def test_forward_bit_identity_property(seed, scale, t):
    """Property sweep over input magnitude and sequence length: parity
    must hold for any on-grid input, not just one lucky draw (skipped
    when the hypothesis test extra is absent)."""
    params = _params(seed % 7)
    q = quantize_classifier(params, CFG)
    key = jax.random.PRNGKey(seed)
    fv = quant.fake_quant(
        jax.random.normal(key, (2, t, 16)) * scale, quant.ACT_Q6_8
    )
    ref = gru_classifier_forward(params, fv, CFG)
    out = dequantize_acts(
        int_gru_classifier_forward(q, quantize_acts(fv), CFG)
    )
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(out))


def test_lut_nonlinearities_match_fake_quant():
    """The Q6.8 sigmoid/tanh ROMs agree with float-op-then-fake-quant on
    every representable summed preactivation."""
    codes = jnp.arange(2 * quant.ACT_Q6_8.qmin, 2 * quant.ACT_Q6_8.qmax + 1)
    x = codes.astype(jnp.float32) * quant.ACT_Q6_8.scale
    np.testing.assert_array_equal(
        np.asarray(quant.lut_sigmoid_q68(codes)),
        np.asarray(quant.quantize_int(jax.nn.sigmoid(x), quant.ACT_Q6_8)),
    )
    np.testing.assert_array_equal(
        np.asarray(quant.lut_tanh_q68(codes)),
        np.asarray(quant.quantize_int(jnp.tanh(x), quant.ACT_Q6_8)),
    )


@pytest.mark.parametrize("layout", ["gates", "ragged"])
@pytest.mark.parametrize("name", ["sigmoid", "tanh"])
def test_lut_step_count_equals_clipped_rom(name, layout):
    """The device form (a count of the ROM's unit steps) returns the ROM
    entry at the clipped code, for every code within 64 of the domain
    and at the int32 extremes: laid out as (streams, 48) gates, which
    the count takes 128 to a row, and as a vector it cannot."""
    lut, table = {
        "sigmoid": (quant.lut_sigmoid_q68, quant.sigmoid_lut_q68()),
        "tanh": (quant.lut_tanh_q68, quant.tanh_lut_q68()),
    }[name]
    info = np.iinfo(np.int32)
    codes = np.arange(quant._LUT_MIN - 64, quant._LUT_MAX + 65)
    extremes = np.resize([info.min, info.min + 1, info.max - 1, info.max],
                         -codes.size % 384)  # 384: rows of 48 and of 128
    codes = np.concatenate([codes, extremes]).astype(np.int32)
    codes = codes.reshape(-1, 48) if layout == "gates" else codes[:-1]
    idx = np.clip(codes, quant._LUT_MIN, quant._LUT_MAX) - quant._LUT_MIN
    got = np.asarray(lut(jnp.asarray(codes)))
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, table[idx])


@pytest.mark.parametrize("bad,step,at", [
    ([0, 0, 2, 3], 2, 2),
    ([0, 1, 2, 1], -1, 3),
], ids=["step-of-2", "step-down"])
def test_rom_steps_refuses_non_unit_steps(bad, step, at):
    base, thresholds = quant._rom_steps(np.array([3, 3, 4, 5, 5], np.int32))
    assert base == 3
    np.testing.assert_array_equal(thresholds, quant._LUT_MIN + np.array([2, 3]))
    with pytest.raises(ValueError, match=f"steps by {step} at code "
                                         f"{quant._LUT_MIN + at}"):
        quant._rom_steps(np.array(bad, np.int32))


@settings(max_examples=30, deadline=None)
@given(
    v=st.integers(min_value=-(2**23), max_value=2**23 - 1),
    shift=st.integers(min_value=1, max_value=16),
)
def test_round_shift_even_matches_jnp_round(v, shift):
    got = int(quant.round_shift_even(jnp.int32(v), shift))
    want = int(np.round(v / 2.0**shift))  # numpy double: exact + half-even
    assert got == want


# --------------------------------------------------------------------------
# pipeline + serving integration
# --------------------------------------------------------------------------

def _audio(batch=2, samples=8192, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(
        rng.standard_normal((batch, samples)).astype(np.float32) * 0.05
    )


def _stats(audio):
    boot = KWSPipeline(KWSPipelineConfig(use_norm=False))
    _, raw = boot.features(audio)
    return fit_norm_stats(quant.log_compress_lut(raw, 12, 10))


def test_pipeline_logits_and_predict_parity():
    audio = _audio(batch=3, seed=20)
    stats = _stats(audio)
    pq = KWSPipeline(KWSPipelineConfig(classifier="qat"), norm_stats=stats)
    pi = KWSPipeline(
        KWSPipelineConfig(classifier="integer"), norm_stats=stats
    )
    params = pq.init_params(jax.random.PRNGKey(20))
    fv, _ = pq.features(audio)
    np.testing.assert_array_equal(
        np.asarray(pq.logits(params, fv)), np.asarray(pi.logits(params, fv))
    )
    np.testing.assert_array_equal(
        np.asarray(pq.logits_all_frames(params, fv)),
        np.asarray(pi.logits_all_frames(params, fv)),
    )
    np.testing.assert_array_equal(
        np.asarray(pq.predict(params, audio)),
        np.asarray(pi.predict(params, audio)),
    )


def test_pipeline_streaming_step_parity_and_state_dtype():
    audio = _audio(seed=21)
    stats = _stats(audio)
    pq = KWSPipeline(KWSPipelineConfig(classifier="qat"), norm_stats=stats)
    pi = KWSPipeline(
        KWSPipelineConfig(classifier="integer"), norm_stats=stats
    )
    params = pq.init_params(jax.random.PRNGKey(21))
    fv, _ = pq.features(audio)
    sq = pq.streaming_init(2)
    si = pi.streaming_init(2)
    assert si[0].dtype == jnp.int32 and sq[0].dtype == jnp.float32
    for t in range(6):
        sq, lq = pq.streaming_step(params, sq, fv[:, t])
        si, li = pi.streaming_step(params, si, fv[:, t])
        np.testing.assert_array_equal(np.asarray(lq), np.asarray(li))


def _server(classifier, params=None, max_streams=4, seed=22):
    audio = _audio(seed=seed)
    stats = _stats(audio)
    pipe = KWSPipeline(
        KWSPipelineConfig(classifier=classifier), norm_stats=stats
    )
    if params is None:
        params = pipe.init_params(jax.random.PRNGKey(seed))
    return pipe, StreamingKWSServer(pipe, params, max_streams=max_streams)


def test_server_fused_tick_parity_qat_vs_integer():
    """The whole fused serving tick (frontend + GRU + softmax +
    smoothing) produces bit-identical posteriors on both backends, for
    raw-audio and FV ticks."""
    params_src = KWSPipeline(KWSPipelineConfig()).init_params(
        jax.random.PRNGKey(22)
    )
    pipe, sq = _server("qat", params_src)
    _, si = _server("integer", params_src)
    assert isinstance(si.params, QuantizedClassifier)
    for s in (sq, si):
        s.open_stream(1)
        s.open_stream(2)
    hop = pipe.chunk_samples
    rng = np.random.default_rng(22)
    for _ in range(4):
        frames = {
            sid: rng.standard_normal(hop).astype(np.float32) * 0.05
            for sid in (1, 2)
        }
        oq = sq.step(dict(frames))
        oi = si.step(dict(frames))
        for sid in frames:
            np.testing.assert_array_equal(
                oq[sid]["probs"], oi[sid]["probs"]
            )
            assert oq[sid]["top"] == oi[sid]["top"]
    fv = np.ones(16, np.float32)
    oq = sq.step({1: fv})
    oi = si.step({1: fv})
    np.testing.assert_array_equal(oq[1]["probs"], oi[1]["probs"])


def test_server_integer_idle_stream_isolation():
    """The temporal-sparsity contract holds for int32 GRU state leaves:
    an idle stream's codes are bit-identical across others' ticks."""
    pipe, srv = _server("integer", seed=23)
    srv.open_stream(1)
    srv.open_stream(2)
    hop = pipe.chunk_samples
    rng = np.random.default_rng(23)
    hops = [rng.standard_normal(hop).astype(np.float32) * 0.05
            for _ in range(3)]
    srv.step({1: hops[0], 2: hops[0]})
    slot = srv.active[2]
    before = jax.tree_util.tree_map(
        lambda t: np.asarray(t[slot]).copy(), srv.state
    )
    for h in hops[1:]:
        srv.step({1: h})
    after = jax.tree_util.tree_map(
        lambda t: np.asarray(t[slot]).copy(), srv.state
    )
    jax.tree_util.tree_map(np.testing.assert_array_equal, before, after)


def test_server_integer_scan_replay_matches_live():
    """run (lax.scan over the fused tick) == live step ticks with the
    integer engine inside the scanned program."""
    params_src = KWSPipeline(KWSPipelineConfig()).init_params(
        jax.random.PRNGKey(24)
    )
    pipe, live = _server("integer", params_src, seed=24)
    _, scan = _server("integer", params_src, seed=24)
    hop = pipe.chunk_samples
    rng = np.random.default_rng(24)
    buf = rng.standard_normal(hop * 4).astype(np.float32) * 0.05
    for s in (live, scan):
        s.open_stream(9)
    outs = []
    for t in range(4):
        o = live.step({9: buf[t * hop:(t + 1) * hop]})
        outs.append(o[9]["probs"])
    rep = scan.run({9: buf})
    np.testing.assert_array_equal(np.stack(outs), rep[9]["probs"])
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        live.state, scan.state,
    )


def test_float_backend_is_unquantized():
    """classifier="float" must bypass fake-quant entirely (outputs off
    the Q6.8 grid), regardless of gru.quantized on the config."""
    params = _params(25)
    fv = _grid_fv((2, 10, 16), seed=25)
    backend = get_classifier("float")
    out = np.asarray(backend.forward(params, fv, CFG))
    codes = out * 256.0
    assert np.abs(codes - np.round(codes)).max() > 1e-3
