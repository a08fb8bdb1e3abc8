"""Operations and bytes the served tick needs, counted from its shapes.

These are what the algorithm needs, not what the compiler emitted, so
they do not move when the implementation does.
"""

from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; an unknown kind is an error."""
    with open(PEAKS) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS.name}")
    return table[device_kind]


def gemm_shapes(cfg: dict, rows: int):
    """(name, M, K, N) of every int8 GEMM of one tick over ``rows`` streams."""
    h = cfg["hidden_dim"]
    out = []
    for layer in range(cfg["num_layers"]):
        k_in = cfg["num_channels"] if layer == 0 else h
        out.append((f"gru{layer}.w_i", rows, k_in, 3 * h))
        out.append((f"gru{layer}.w_h", rows, h, 3 * h))
    out.append(("fc", rows, h, cfg["num_classes"]))
    return out


def gemm_ops(m: int, k: int, n: int) -> int:
    return 2 * m * k * n


def gemm_bytes(m: int, k: int, n: int) -> int:
    """14-bit activation codes in 2 bytes, int8 weights, int32 results."""
    return 2 * m * k + k * n + 4 * m * n


def gemm_roofline_s(m, k, n, peak_ops, bw):
    """(least seconds, "compute" or "memory") for one GEMM."""
    t_ops = gemm_ops(m, k, n) / peak_ops
    t_mem = gemm_bytes(m, k, n) / bw
    return max(t_ops, t_mem), ("compute" if t_ops >= t_mem else "memory")


def hop_ops(cfg: dict) -> int:
    """Model operations per hop: 2x the GRU and head MACs. The cells feed
    FV_Norm frames, so the frontend's filter runs on the edge device,
    not on the chip, and is not counted."""
    return 2 * sum(k * n for _, _, k, n in gemm_shapes(cfg, 1))
