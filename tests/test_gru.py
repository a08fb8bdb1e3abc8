"""GRU classifier: convention, quantized bounds, streaming equivalence."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import quant
from repro.core.gru_delta import (
    DeltaConfig,
    delta_classifier_step,
    delta_init_states,
)
from repro.core.gru import (
    GRUConfig,
    classifier_macs,
    classifier_param_bytes,
    gru_cell,
    gru_classifier_forward,
    gru_classifier_step,
    init_gru_classifier,
    init_states,
)


def _manual_gru_step(layer, h, x):
    """PyTorch-convention reference in numpy."""
    w_i, w_h = np.asarray(layer["w_i"]), np.asarray(layer["w_h"])
    b_i, b_h = np.asarray(layer["b_i"]), np.asarray(layer["b_h"])
    gi = x @ w_i + b_i
    gh = h @ w_h + b_h
    H = h.shape[-1]

    def sig(v):
        return 1 / (1 + np.exp(-v))

    r = sig(gi[:, :H] + gh[:, :H])
    z = sig(gi[:, H : 2 * H] + gh[:, H : 2 * H])
    n = np.tanh(gi[:, 2 * H :] + r * gh[:, 2 * H :])
    return (1 - z) * n + z * h


def test_cell_matches_pytorch_convention():
    cfg = GRUConfig(quantized=False)
    params = init_gru_classifier(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((4, 16)).astype(np.float32)
    h = rng.standard_normal((4, 48)).astype(np.float32)
    ours = gru_cell(params["gru"][0], jnp.asarray(h), jnp.asarray(x), cfg)
    ref = _manual_gru_step(params["gru"][0], h, x)
    np.testing.assert_allclose(np.asarray(ours), ref, atol=2e-6)


def test_quantized_activations_within_format():
    cfg = GRUConfig(quantized=True)
    params = init_gru_classifier(jax.random.PRNGKey(1), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (8, 20, 16)) * 10
    out = gru_classifier_forward(params, x, cfg)
    assert float(jnp.abs(out).max()) <= quant.ACT_Q6_8.max_value
    # outputs land exactly on the Q6.8 grid
    codes = np.asarray(out) * 256.0
    np.testing.assert_allclose(codes, np.round(codes), atol=1e-3)


def test_streaming_equals_full_forward():
    cfg = GRUConfig(quantized=True)
    params = init_gru_classifier(jax.random.PRNGKey(3), cfg)
    fv = jax.random.normal(jax.random.PRNGKey(4), (3, 12, 16))
    full = gru_classifier_forward(params, fv, cfg)
    states = init_states(cfg, 3)
    outs = []
    for t in range(12):
        states, logits = gru_classifier_step(params, states, fv[:, t], cfg)
        outs.append(logits)
    stream = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(full), np.asarray(stream), atol=1e-6
    )


@pytest.mark.parametrize("engine", ["qat", "delta"])
def test_classifier_dots_ask_for_highest_precision(engine):
    """A TPU runs a default-precision float32 dot as one bfloat16 pass,
    which drops 6 of a Q6.8 code's 14 bits; every dot of the float-domain
    classifiers must ask for HIGHEST so the chip computes what the CPU
    does."""
    cfg = GRUConfig(quantized=True)
    params = init_gru_classifier(jax.random.PRNGKey(5), cfg)
    fv = jnp.zeros((2, cfg.input_dim), jnp.float32)
    if engine == "qat":
        step = lambda st, x: gru_classifier_step(params, st, x, cfg)  # noqa: E731
        states = init_states(cfg, 2)
    else:
        thetas = DeltaConfig(0.15, 0.15).code_thresholds(cfg.num_layers)
        step = lambda st, x: delta_classifier_step(  # noqa: E731
            params, st, x, cfg, thetas)
        states = delta_init_states(cfg, 2)
    text = jax.jit(step).lower(states, fv).as_text()
    dots = [ln for ln in text.splitlines() if "dot_general" in ln]
    assert len(dots) == 2 * cfg.num_layers + 1  # x·W_i, h·W_h per layer, FC
    assert all("precision = [HIGHEST, HIGHEST]" in ln for ln in dots), dots


def test_paper_size_checks():
    cfg = GRUConfig()
    assert classifier_macs(cfg) == 24204  # = 24 KB at 8-bit (WMEM)
    assert classifier_param_bytes(cfg) == 24204
