"""Plain reference of the served KWS tick, written from the paper's model.

It shares no code with the system under test. Given a configuration
file, the benchmark's own weights and norm statistics, and the audio a
set of streams received, it recomputes what the server must have
produced for those streams:

  frontend    2x linear oversampling (the last sample of a hop repeated),
              16 constant-0-dB-peak band-pass biquads in transposed
              direct form II at the Mel-spaced centres, |y| averaged over
              the 16 ms frame, 12-bit quantizer, 12->10-bit log table,
              (x - mu) / sigma rounded to Q6.8 codes;
  gate        (cascade only) mean(relu(FV_Norm)) against wake and release
              thresholds, with hangover, advancing the classifier only
              on woken ticks;
  classifier  2 x GRU(48) and the FC head on integer codes: int8 weights,
              a 24-bit saturating accumulator, biases at frac 15, every
              rescale rounded half to even, sigmoid and tanh as Q6.8
              tables built in float64;
  head        softmax of the logits and exponential smoothing of the
              posteriors, top-1 of the smoothed posterior.

The frontend, which makes the FV_Norm frames a feature-upload fleet
sends and replays a raw-audio fleet's streams, runs in jax on the
default device, in float32 unless the control asks for one precision
down. The classifier runs on the host in numpy, exact on integers.

A configuration may name another module in ``bench/`` as its reference
(key ``reference``); it exposes `frontend` and `classifier` with these
signatures.
"""

from __future__ import annotations

import math
from typing import Dict, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

ACT_MIN, ACT_MAX = -(2 ** 13), 2 ** 13 - 1  # Q6.8 register, 14 bits
ACC_MIN, ACC_MAX = -(2 ** 23), 2 ** 23 - 1  # 24-bit accumulator
W_FRAC, ACT_FRAC = 7, 8


def filterbank(cfg: dict) -> np.ndarray:
    """(5, C) float32 rows b0, b1, b2, a1, a2 of the Mel band-pass bank."""
    fs = cfg["fs_audio"] * cfg["oversample"]
    mel = lambda f: 2595.0 * np.log10(1.0 + f / 700.0)  # noqa: E731
    mels = np.linspace(mel(cfg["f_lo"]), mel(cfg["f_hi"]),
                       cfg["num_channels"])
    f0 = 700.0 * (10.0 ** (mels / 2595.0) - 1.0)
    w0 = 2.0 * math.pi * f0 / fs
    alpha = np.sin(w0) / (2.0 * cfg["q"])
    a0 = 1.0 + alpha
    rows = [alpha / a0, np.zeros_like(w0), -alpha / a0,
            -2.0 * np.cos(w0) / a0, (1.0 - alpha) / a0]
    return np.stack(rows).astype(np.float32)


def log_table(cfg: dict) -> np.ndarray:
    """The 12-bit -> 10-bit logarithmic compression ROM, as float."""
    v = np.arange(2 ** cfg["quant_bits"], dtype=np.float64)
    out = (2.0 ** cfg["log_bits"] - 1.0) * np.log2(1.0 + v) / cfg["quant_bits"]
    return np.round(out)


def act_table(fn) -> np.ndarray:
    """Q6.8 ROM of ``fn`` over the sum of two Q6.8 codes."""
    codes = np.arange(2 * ACT_MIN, 2 * ACT_MAX + 1, dtype=np.float64)
    return np.clip(np.round(fn(codes / 256.0) * 256.0), ACT_MIN, ACT_MAX)


SIGMOID = act_table(lambda x: 1.0 / (1.0 + np.exp(-x)))
TANH = act_table(np.tanh)


def _rom(table, codes):
    return table[np.clip(codes, 2 * ACT_MIN, 2 * ACT_MAX) - 2 * ACT_MIN]


def _rshift(x, s):
    """round(x / 2**s), half to even; exact for |x| < 2**53."""
    return np.round(x / float(2 ** s))


# ---------------------------------------------------------------- frontend

@jax.jit
def _frontend_block(carry, hops, valid, coeffs, table, mu, sigma,
                    full_scale, levels):
    """hops (T, S, hop) -> (carry, FV_Norm codes (T, S, C) int32); a tick
    whose ``valid`` is False leaves the carry as it was. The filter runs
    in the dtype of ``hops``, ``coeffs`` and ``carry``; the quantizer,
    the log table and the normalizer in float32."""
    b0, b1, b2, a1, a2 = (coeffs[i] for i in range(5))

    def tick(carry, xs):
        hop, ok = xs
        nxt = jnp.concatenate([hop[:, 1:], hop[:, -1:]], axis=1)
        mid = (hop + nxt) * 0.5
        x = jnp.stack([hop, mid], axis=-1).reshape(hop.shape[0], -1)

        def sample(c, x_t):
            s1, s2, acc = c
            xc = x_t[:, None]
            y = b0 * xc + s1
            return (b1 * xc - a1 * y + s2, b2 * xc - a2 * y,
                    acc + jnp.abs(y)), None

        acc0 = jnp.zeros_like(carry[0])
        (s1, s2, acc), _ = jax.lax.scan(
            sample, (carry[0], carry[1], acc0), x.T)
        frame = (acc / x.shape[1]).astype(jnp.float32)
        raw = jnp.round(jnp.clip(frame, 0.0, full_scale) / full_scale * levels)
        logv = table[raw.astype(jnp.int32)]
        norm = (logv - mu) / sigma
        codes = jnp.clip(jnp.round(norm * 256.0), ACT_MIN, ACT_MAX)
        keep = lambda new, old: jnp.where(ok, new, old)  # noqa: E731
        return (keep(s1, carry[0]), keep(s2, carry[1])), codes.astype(
            jnp.int32)

    return jax.lax.scan(tick, carry, (hops, valid))


def frontend(cfg, norm, hops_fn, n_ticks, n_streams, block=128,
             dtype="float32"):
    """(FV_Norm codes (T, S, C), final filter carry), as numpy.

    ``hops_fn(t0, t1)`` returns the (t1 - t0, S, hop) float32 audio of
    ticks t0..t1-1; the frontend runs over it in blocks of ``block``
    ticks so that the audio of a long run never sits on the device whole.
    The carry is each biquad's two transposed-direct-form-II registers
    after the last tick, ``{"s1": (S, C), "s2": (S, C)}`` in float32.
    ``dtype`` is the filter's precision: float32, or bfloat16 for the
    check's control.
    """
    c = cfg["num_channels"]
    coeffs = jnp.asarray(filterbank(cfg), dtype)
    table = jnp.asarray(log_table(cfg), jnp.float32)
    mu = jnp.asarray(norm["mu"], jnp.float32)
    sigma = jnp.asarray(norm["sigma"], jnp.float32)
    carry = (jnp.zeros((n_streams, c), dtype),) * 2
    out = []
    for t0 in range(0, n_ticks, block):
        t1 = min(n_ticks, t0 + block)
        hops = hops_fn(t0, t1)
        if t1 - t0 < block:  # one program for every block
            hops = np.concatenate(
                [hops, np.zeros((block - (t1 - t0),) + hops.shape[1:],
                                np.float32)])
        valid = np.arange(block) < t1 - t0
        carry, codes = _frontend_block(
            carry, jnp.asarray(hops, dtype), jnp.asarray(valid), coeffs,
            table, mu, sigma, np.float32(cfg["quant_full_scale"]),
            np.float32(2 ** cfg["quant_bits"] - 1))
        out.append(np.asarray(codes))
    final = {"s1": np.asarray(carry[0], np.float32),
             "s2": np.asarray(carry[1], np.float32)}
    return np.concatenate(out)[:n_ticks], final


# ---------------------------------------------------------------- classifier

class Served(NamedTuple):
    scores: np.ndarray  # (T, S, K) smoothed posteriors
    top: np.ndarray  # (T, S)
    lead: np.ndarray  # (T, S) top-1 minus top-2 of the smoothed posterior
    h: tuple  # final per-layer hidden codes, each (S, H)
    det: Dict[str, np.ndarray]  # final gate state (cascade only)


def _accum(x, w, b):
    acc = np.clip(x @ w, ACC_MIN, ACC_MAX) + b
    return np.clip(_rshift(acc, W_FRAC), ACT_MIN, ACT_MAX)


def _gru(layer, h, x):
    gi = _accum(x, layer["w_i"], layer["b_i"])
    gh = _accum(h, layer["w_h"], layer["b_h"])
    hd = h.shape[1]
    r = _rom(SIGMOID, (gi[:, :hd] + gh[:, :hd]).astype(np.int64))
    z = _rom(SIGMOID, (gi[:, hd:2 * hd] + gh[:, hd:2 * hd]).astype(np.int64))
    rn = np.clip(_rshift(r * gh[:, 2 * hd:], ACT_FRAC), ACT_MIN, ACT_MAX)
    n = _rom(TANH, (gi[:, 2 * hd:] + rn).astype(np.int64))
    return np.clip(_rshift((256.0 - z) * n + z * h, ACT_FRAC),
                   ACT_MIN, ACT_MAX)


def classifier(cfg, weights, fv):
    """Serve FV_Norm codes (T, S, C) through the gate, the GRU and the head.

    ``weights`` are integer codes as float64 arrays (exact): per layer
    w_i, w_h, b_i, b_h, then fc_w, fc_b.
    """
    n_ticks, s, _ = fv.shape
    hd, k = cfg["hidden_dim"], cfg["num_classes"]
    h = [np.zeros((s, hd)) for _ in range(cfg["num_layers"])]
    smooth = cfg["smoothing"]
    scores = np.zeros((s, k))
    out_scores = np.zeros((n_ticks, s, k), np.float64)
    casc = cfg.get("cascade")
    det = {"awake": np.zeros(s, bool), "hang": np.zeros(s, np.int64),
           "woken": np.zeros(s, np.int64), "ticks": np.zeros(s, np.int64)}
    if casc:
        # the server compares float32 scores with float32 thresholds
        wake_t = float(np.float32(casc["wake_threshold"]))
        rel_t = float(np.float32(casc["release_threshold"]))
    for t in range(n_ticks):
        x = fv[t].astype(np.float64)
        wake = np.ones(s, bool)
        if casc:
            energy = np.maximum(x, 0.0).sum(axis=1) / 256.0 / x.shape[1]
            awake = (energy >= wake_t) | (det["awake"] & ~(energy < rel_t))
            wake = awake | (det["hang"] > 0)
            det["hang"] = np.where(awake, casc["hangover_frames"],
                                   np.maximum(det["hang"] - 1, 0))
            det["awake"] = awake
            det["woken"] = det["woken"] + wake
            det["ticks"] = det["ticks"] + 1
        inp = x
        new_h = []
        for layer, h_l in zip(weights["gru"], h):
            h_n = _gru(layer, h_l, inp)
            new_h.append(h_n)
            inp = h_n
        logits = _accum(inp, weights["fc_w"], weights["fc_b"]) / 256.0
        p = np.exp(logits - logits.max(axis=1, keepdims=True))
        p /= p.sum(axis=1, keepdims=True)
        w = wake[:, None]
        h = [np.where(w, hn, ho) for hn, ho in zip(new_h, h)]
        scores = np.where(w, smooth * scores + (1.0 - smooth) * p, scores)
        out_scores[t] = scores
    srt = np.sort(out_scores, axis=2)
    return Served(
        scores=out_scores,
        top=out_scores.argmax(axis=2),
        lead=srt[..., -1] - srt[..., -2],
        h=tuple(h),
        det=det if casc else {},
    )
