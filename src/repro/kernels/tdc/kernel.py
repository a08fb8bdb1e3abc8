"""SRO DeltaSigma-TDC Pallas kernel (Sections III-B/D).

Simulates, per channel: SRO frequency f = f0_eff + k_eff * u; phase
integration; 15-phase floor quantization; XOR first-difference; 1st-order
CIC decimation. Uses the exact fractional-carry formulation

    r <- r + n_phases * f * dt ;  incr = floor(r) ;  r <- r - incr

whose per-frame sum telescopes to the quantized phase increment — the
same math as counter sampling + XOR diff + boxcar, but without an
unbounded phase accumulator (float32-safe for arbitrarily long streams,
like the real free-running counter which wraps modulo 2^width).

The 2x zero-order-hold from the 32 kHz audio-internal rate to the TDC
rate is fused (os ticks per input sample), so the 64 kHz stream is never
materialized: HBM traffic is one read of (B, T, C) and one write of
(B, F, C) — the same in-stream property as the silicon.

Grid = (B/BB, n_frames) with frames sequential (carry r); per-frame
fori_loop over samples, os ticks unrolled inside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _tdc_kernel(
    u_ref,  # (BB, S, C) rectified input, one frame of samples
    f0_ref,  # (1, C) effective free-running frequency (incl. mismatch)
    k_ref,  # (1, C) effective gain (incl. mismatch)
    out_ref,  # (BB, 1, C) counts per frame
    r_ref,  # scratch (BB, C): fractional phase carry in [0, 1)
    *,
    samples_per_frame: int,
    os: int,
    dt: float,
    n_phases: int,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _reset():
        r_ref[...] = jnp.zeros_like(r_ref)

    f0 = f0_ref[0, :][None, :]
    kg = k_ref[0, :][None, :]
    scale = n_phases * dt

    def sample_step(i, carry):
        r, acc = carry
        u = u_ref[:, i, :]  # (BB, C)
        delta = scale * jnp.maximum(f0 + kg * u, 0.0)
        for _ in range(os):  # os static (ZOH ticks per sample)
            r = r + delta
            incr = jnp.floor(r)
            r = r - incr
            acc = acc + incr
        return (r, acc)

    r0 = r_ref[...]
    acc0 = jnp.zeros_like(r0)
    r, acc = jax.lax.fori_loop(
        0, samples_per_frame, sample_step, (r0, acc0)
    )
    r_ref[...] = r
    out_ref[:, 0, :] = acc


def tdc_pallas(
    u: jnp.ndarray,  # (B, T, C) rectified, at the 32 kHz internal rate
    f0_eff: jnp.ndarray,  # (C,)
    k_eff: jnp.ndarray,  # (C,)
    *,
    samples_per_frame: int,
    os: int,
    f_tdc: float,
    n_phases: int = 15,
    block_batch: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns per-frame counts (B, T // samples_per_frame, C)."""
    b, t, c = u.shape
    if t % samples_per_frame:
        raise ValueError(f"T={t} not multiple of frame {samples_per_frame}")
    if b % block_batch:
        raise ValueError(f"B={b} not multiple of block {block_batch}")
    n_frames = t // samples_per_frame
    kernel = functools.partial(
        _tdc_kernel,
        samples_per_frame=samples_per_frame,
        os=os,
        dt=1.0 / f_tdc,
        n_phases=n_phases,
    )
    return pl.pallas_call(
        kernel,
        grid=(b // block_batch, n_frames),
        in_specs=[
            pl.BlockSpec(
                (block_batch, samples_per_frame, c),
                lambda ib, it: (ib, it, 0),
            ),
            pl.BlockSpec((1, c), lambda ib, it: (0, 0)),
            pl.BlockSpec((1, c), lambda ib, it: (0, 0)),
        ],
        out_specs=pl.BlockSpec(
            (block_batch, 1, c), lambda ib, it: (ib, it, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((b, n_frames, c), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_batch, c), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(u, f0_eff[None, :], k_eff[None, :])
