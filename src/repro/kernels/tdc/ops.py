"""Config-aware public entry point for the TDC kernel.

`tdc_counts` picks one of three equivalent implementations per call:

  * ``pallas``    — the compiled Mosaic kernel (explicit only: Mosaic
                    refuses it on v5e, see `resolve_tdc_dispatch`);
  * ``interpret`` — the same kernel body run by the Pallas interpreter
                    (validates kernel logic on CPU CI);
  * ``reference`` — the pure-jnp cumsum/floor formulation of
                    `repro.core.tdfex.sro_tdc` (fastest off-TPU, and the
                    fallback for shapes the kernel does not tile well).

Tier selection, the legacy ``interpret=`` flag and the `force_dispatch`
override are the shared `repro.kernels.dispatch` machinery; this
kernel's local policy is the reference on TPU and the off-TPU auto
split — small batches run the interpreter (cheap, keeps CI validating
the kernel logic), large batches the vectorized jnp reference (the
interpreter is per-element slow).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.tdfex import TDFExConfig, TDFExState, sro_tdc
from repro.kernels.dispatch import resolve_dispatch
from repro.kernels.tdc.kernel import tdc_pallas

_tdc_call = jax.jit(
    tdc_pallas,
    static_argnames=(
        "samples_per_frame", "os", "f_tdc", "n_phases",
        "block_batch", "interpret",
    ),
)


def resolve_tdc_dispatch(
    batch: int,
    dispatch: str = "auto",
    interpret: Optional[bool] = None,
) -> str:
    """Resolve 'auto' to a concrete path for this backend + batch shape.

    On TPU 'auto' picks the reference: Mosaic refuses the kernel on v5e
    (its (block, samples, channels) tiles pad 16 channels to 128 lanes
    and overflow VMEM; tests/test_tpu_compile.py), so "pallas" stays an
    explicit choice.
    """
    return resolve_dispatch(
        dispatch, interpret,
        off_tpu="interpret" if batch <= 8 else "reference",
        on_tpu="reference",
    )


def tdc_counts(
    u: jnp.ndarray,  # (B, T, C) rectified @ fs_internal
    cfg: TDFExConfig,
    chip: Optional[TDFExState] = None,
    block_batch: Optional[int] = None,
    interpret: Optional[bool] = None,
    dispatch: str = "auto",
) -> jnp.ndarray:
    """Config-level entry point: (B, T, C) -> (B, F, C) counts."""
    b = u.shape[0]
    path = resolve_tdc_dispatch(b, dispatch, interpret)
    if path == "reference":
        return sro_tdc(u, cfg, chip)
    run_interpret = path == "interpret"
    if block_batch is None:
        block_batch = 8 if run_interpret else 128
    c = u.shape[-1]
    gain = jnp.ones((c,), jnp.float32)
    if chip is not None:
        gain = 1.0 + chip.gain_mismatch
    f0_eff = cfg.f_free_hz * gain
    k_eff = cfg.k_sro_hz * gain
    samples_per_frame = cfg.decimation // cfg.tdc_oversample
    # trim to whole frames (the reference path does the same inside its
    # CIC decimation)
    t_use = (u.shape[1] // samples_per_frame) * samples_per_frame
    u = u[:, :t_use]
    pad = (-b) % block_batch
    if pad:
        u = jnp.concatenate(
            [u, jnp.zeros((pad,) + u.shape[1:], u.dtype)], axis=0
        )
    out = _tdc_call(
        u, f0_eff, k_eff,
        samples_per_frame=samples_per_frame,
        os=cfg.tdc_oversample, f_tdc=cfg.f_tdc,
        n_phases=cfg.n_phases, block_batch=block_batch,
        interpret=run_interpret,
    )
    return out[:b]
