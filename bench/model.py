"""A configuration's weights and norm statistics, made from the seed.

Both are the benchmark's, not the program's: the server is handed the
float weights (exactly on the int8 / frac-15 grids, so its own
quantization is exact) and the norm statistics; the reference reads the
same codes directly.
"""

from __future__ import annotations

import json
import pathlib

import numpy as np
from scipy import signal

from bench import reference, traffic

DIR = pathlib.Path(__file__).resolve().parent / "configs"


def load(name: str) -> dict:
    with open(DIR / f"{name}.json") as f:
        return json.load(f)


def weights(cfg: dict, seed: int):
    """Seeded integer codes, uniform over +-1/sqrt(H) as PyTorch's GRU
    init draws floats: (codes for the reference, floats for the server)."""
    rng = np.random.default_rng([seed, 2])
    h, k = cfg["hidden_dim"], cfg["num_classes"]
    w_lim = int(round(128 / np.sqrt(h)))
    b_lim = int(round(32768 / np.sqrt(h)))
    codes = {"gru": []}
    for layer in range(cfg["num_layers"]):
        fan_in = cfg["num_channels"] if layer == 0 else h
        codes["gru"].append({
            "w_i": rng.integers(-w_lim, w_lim + 1, (fan_in, 3 * h)),
            "w_h": rng.integers(-w_lim, w_lim + 1, (h, 3 * h)),
            "b_i": rng.integers(-b_lim, b_lim + 1, (3 * h,)),
            "b_h": rng.integers(-b_lim, b_lim + 1, (3 * h,)),
        })
    codes["fc_w"] = rng.integers(-w_lim, w_lim + 1, (h, k))
    codes["fc_b"] = rng.integers(-b_lim, b_lim + 1, (k,))
    w = lambda c: (c / 128.0).astype(np.float32)  # noqa: E731
    b = lambda c: (c / 32768.0).astype(np.float32)  # noqa: E731
    floats = {
        "gru": [{"w_i": w(l_["w_i"]), "w_h": w(l_["w_h"]),
                 "b_i": b(l_["b_i"]), "b_h": b(l_["b_h"])}
                for l_ in codes["gru"]],
        "fc": {"w": w(codes["fc_w"]), "b": b(codes["fc_b"])},
    }
    as_f64 = {
        "gru": [{n: v.astype(np.float64) for n, v in l_.items()}
                for l_ in codes["gru"]],
        "fc_w": codes["fc_w"].astype(np.float64),
        "fc_b": codes["fc_b"].astype(np.float64),
    }
    return as_f64, floats


def fv_log(cfg: dict, audio: np.ndarray) -> np.ndarray:
    """(B, n) audio -> (B, frames, C) 10-bit log codes, in float64."""
    nxt = np.concatenate([audio[:, 1:], audio[:, -1:]], axis=1)
    x = np.stack([audio, 0.5 * (audio + nxt)], axis=-1).reshape(
        audio.shape[0], -1).astype(np.float64)
    coeffs = reference.filterbank(cfg).astype(np.float64)
    frame = 2 * cfg["hop_samples"]
    n_frames = x.shape[1] // frame
    out = []
    for ch in range(cfg["num_channels"]):
        b0, b1, b2, a1, a2 = coeffs[:, ch]
        y = np.abs(signal.lfilter([b0, b1, b2], [1.0, a1, a2], x, axis=1))
        out.append(y[:, : n_frames * frame].reshape(-1, n_frames, frame)
                   .mean(axis=2))
    frames = np.stack(out, axis=-1)
    levels = 2 ** cfg["quant_bits"] - 1
    raw = np.round(np.clip(frames, 0.0, cfg["quant_full_scale"])
                   / cfg["quant_full_scale"] * levels)
    return reference.log_table(cfg)[raw.astype(np.int64)]


def norm_stats(cfg: dict, seed: int) -> dict:
    """mu / sigma of FV_Log over seeded speech clips (the training-set
    statistics the chip's normalizer is loaded with)."""
    fit = cfg["norm_fit"]
    rng = np.random.default_rng([seed, 3])
    clips = traffic.speech(rng, fit["clips"],
                           fit["hops"] * cfg["hop_samples"], fit["speech"])
    logv = fv_log(cfg, clips).reshape(-1, cfg["num_channels"])
    return {"mu": logv.mean(axis=0).astype(np.float32),
            "sigma": (logv.std(axis=0) + 1e-3).astype(np.float32)}
