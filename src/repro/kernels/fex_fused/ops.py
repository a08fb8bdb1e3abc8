"""Public wrapper for the fused FEx kernel.

Tier selection (pallas on TPU, interpreter off-TPU — no standalone jnp
reference, the interpreter IS the non-Mosaic evaluation of the same
body) comes from `repro.kernels.dispatch`, so the same call site works
in CI (CPU, interpret validates the kernel body) and in production
(TPU, compiled Mosaic kernel).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.filters import BiquadCoeffs
from repro.kernels.dispatch import resolve_dispatch
from repro.kernels.fex_fused.kernel import fex_fused_pallas

_fex_fused_call = jax.jit(
    fex_fused_pallas,
    static_argnames=("frame_len", "block_batch", "interpret"),
)


def fex_fused(
    x: jnp.ndarray,
    coeffs: BiquadCoeffs,
    frame_len: int,
    block_batch: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused biquad + FWR + frame average: (B, T) -> (B, F, C).

    Pads the batch up to the block size and trims T to a whole number of
    frames, so any (B, T) is accepted.
    """
    path = resolve_dispatch(
        interpret=interpret, off_tpu="interpret", has_reference=False
    )
    interpret = path != "pallas"
    if block_batch is None:
        block_batch = 8 if interpret else 256
    b, t = x.shape
    t_use = (t // frame_len) * frame_len
    x = x[:, :t_use]
    pad = (-b) % block_batch
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, t_use), x.dtype)], axis=0)
    # Coefficients stay f32 regardless of the IO dtype: the 100 Hz
    # channel's a1 ~ -1.9961 rounds to -1.9922 in bf16, pushing the pole
    # to the unit circle and blowing the filter up (the analog
    # equivalent: the FLL bias precision that sets each channel's f0).
    out = _fex_fused_call(
        x, coeffs.stacked(dtype=jnp.float32),
        frame_len=frame_len, block_batch=block_batch, interpret=interpret,
    )
    return out[:b]
