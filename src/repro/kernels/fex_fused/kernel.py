"""Fused FEx Pallas kernel: biquad filterbank + FWR + frame accumulation.

The IC computes features *in-stream*: the per-channel band-passed waveform
never exists as a stored signal — only the rectified, decimated energy
leaves the analog front-end. This kernel is the TPU transcription of that
insight: the (B, T, C) filtered intermediate never touches HBM.

Memory-roofline napkin math (per 1 s clip, 32 kHz, C=16, f32):
  unfused:  write+read BPF output  2 * T*C*4 = 4.1 MB
            + read audio T*4      = 0.13 MB, write frames F*C*4 = 4 KB
  fused:    read audio 0.13 MB + write frames 4 KB      (~32x less traffic)

Layout: grid = (B/BB, T/FRAME); the frame axis is sequential ("arbitrary")
so the IIR state carried in VMEM scratch persists across frames; the batch
axis is parallel. Within a block the kernel scans FRAME time steps with a
fori_loop over (BB, C) vectors — batch in sublanes, channels in lanes
(C=16 zero-padded to the 128-lane register; BB defaults to 8 sublanes of
f32; on real TPUs BB=256 amortizes the scalar loop overhead and still uses
< 1 MB of VMEM).

State is transposed-direct-form-II per channel:
    y  = b0*x + s1
    s1 = b1*x - a1*y + s2
    s2 = b2*x - a2*y
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _fex_fused_kernel(
    x_ref,  # (BB, FRAME) audio block at the internal rate
    coef_ref,  # (5, C): b0, b1, b2, a1, a2
    out_ref,  # (BB, 1, C) frame output
    s1_ref,  # scratch (BB, C) IIR state
    s2_ref,  # scratch (BB, C)
    acc_ref,  # scratch (BB, C) rectified accumulator
    *,
    frame_len: int,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _reset():
        # New batch tile (frame index restarts): clear filter state.
        s1_ref[...] = jnp.zeros_like(s1_ref)
        s2_ref[...] = jnp.zeros_like(s2_ref)

    acc_ref[...] = jnp.zeros_like(acc_ref)

    b0 = coef_ref[0, :][None, :].astype(jnp.float32)  # (1, C)
    b1 = coef_ref[1, :][None, :].astype(jnp.float32)
    b2 = coef_ref[2, :][None, :].astype(jnp.float32)
    a1 = coef_ref[3, :][None, :].astype(jnp.float32)
    a2 = coef_ref[4, :][None, :].astype(jnp.float32)

    def step(i, carry):
        s1, s2, acc = carry
        x_t = x_ref[:, i][:, None].astype(jnp.float32)  # (BB, 1)
        y = b0 * x_t + s1
        s1 = b1 * x_t - a1 * y + s2
        s2 = b2 * x_t - a2 * y
        acc = acc + jnp.abs(y)
        return (s1, s2, acc)

    s1, s2, acc = jax.lax.fori_loop(
        0, frame_len, step, (s1_ref[...], s2_ref[...], acc_ref[...])
    )
    s1_ref[...] = s1
    s2_ref[...] = s2
    out_ref[:, 0, :] = (acc * (1.0 / frame_len)).astype(out_ref.dtype)


def fex_fused_pallas(
    x: jnp.ndarray,  # (B, T) audio at the internal (32 kHz) rate
    coeffs: jnp.ndarray,  # (5, C) stacked biquad coefficients
    *,
    frame_len: int,
    block_batch: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns rectified-average frames (B, T // frame_len, C)."""
    b, t = x.shape
    c = coeffs.shape[1]
    if t % frame_len:
        raise ValueError(f"T={t} not a multiple of frame_len={frame_len}")
    if b % block_batch:
        raise ValueError(f"B={b} not a multiple of block_batch={block_batch}")
    n_frames = t // frame_len

    kernel = functools.partial(_fex_fused_kernel, frame_len=frame_len)
    return pl.pallas_call(
        kernel,
        grid=(b // block_batch, n_frames),
        in_specs=[
            pl.BlockSpec((block_batch, frame_len), lambda ib, it: (ib, it)),
            pl.BlockSpec((5, c), lambda ib, it: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_batch, 1, c), lambda ib, it: (ib, it, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_frames, c), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_batch, c), jnp.float32),
            pltpu.VMEM((block_batch, c), jnp.float32),
            pltpu.VMEM((block_batch, c), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(x, coeffs)
