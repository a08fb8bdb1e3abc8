"""Operations and bytes of the software frontend in the served tick, per
hop, counted from the code the raw-audio tick runs
(`repro.core.frontend` "software": 2x oversampling, the in-scan biquad
bank with its rectified frame mean, the 12-bit quantizer, the log ROM
read as a count of its steps, the normalizer and the Q6.8 rounding).

Like `bench.ops`, these are what the algorithm needs at the tick's
shapes, not what the compiler emitted.
"""

from __future__ import annotations

import numpy as np

from bench import reference

F32 = 4  # bytes of every frontend value: samples, registers, frames

# float operations per internal sample and channel in the filter scan
BIQUAD = 9  # y = b0 x + s1; s1 = b1 x - a1 y + s2; s2 = b2 x - a2 y
RECTIFY_MEAN = 2  # |y| and its running sum
# per channel and hop
FRAME_MEAN = 1  # the sum over the frame divided by its length
QUANT = 5  # clip (2), / full scale, * levels, round
ROM_INPUT = 3  # clip (2) and round of the FV_Raw code
ROM_STEP = 3  # compare, select the step's size, add
NORM = 7  # (x - mu) / sigma (2), then * 256, round, clip (2), * 2^-8


def log_rom_steps(cfg: dict) -> int:
    """Steps of the 12 -> 10-bit log table: codes where it climbs."""
    return int(np.count_nonzero(np.diff(reference.log_table(cfg))))


def frontend_ops(cfg: dict) -> int:
    """Operations of one stream's hop through the frontend."""
    hop = cfg["hop_samples"]
    samples = hop * cfg["oversample"]
    oversample = 2 * hop if cfg["oversample"] == 2 else 0  # 0.5 (a + next)
    per_channel = (samples * (BIQUAD + RECTIFY_MEAN) + FRAME_MEAN + QUANT
                   + ROM_INPUT + ROM_STEP * log_rom_steps(cfg) + NORM)
    return oversample + cfg["num_channels"] * per_channel


def frontend_bytes(cfg: dict, rows: int) -> int:
    """Bytes the frontend must move for ``rows`` streams in one tick: the
    raw hop in, both registers of every biquad read and written, the
    FV_Norm frame out."""
    c = cfg["num_channels"]
    return rows * F32 * (cfg["hop_samples"] + 2 * 2 * c + c)


def frontend_ms(ctx):
    """Device ms per tick of every op under the tick's ``kws_frontend``
    scope, busiest device, from a traced run's ``ctx.spans``; None where
    the trace holds none (a feature-upload tick has no frontend)."""
    spans = getattr(ctx, "spans", None)
    if spans is None:
        return None
    ms = [v for k, v in spans["scope_ms"].items()
          if v is not None and (k == "kws_frontend"
                                or k.startswith("kws_frontend/"))]
    return sum(ms) if ms else None
