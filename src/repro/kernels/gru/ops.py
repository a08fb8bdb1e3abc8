"""Public wrapper: batch-major API, shared dispatch tiers, batch padding.

Tier selection (pallas on TPU, interpreter off-TPU — this kernel has
no standalone jnp reference, the interpreter IS its non-Mosaic
evaluation) comes from `repro.kernels.dispatch`.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_dispatch
from repro.kernels.gru.kernel import gru_sequence_pallas

_gru_seq_call = jax.jit(
    gru_sequence_pallas, static_argnames=("block_batch", "interpret")
)


def gru_sequence(
    xs: jnp.ndarray,  # (B, T, I) batch-major
    w: jnp.ndarray,
    u: jnp.ndarray,
    b_i: jnp.ndarray,
    b_h: jnp.ndarray,
    h0: Optional[jnp.ndarray] = None,
    block_batch: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """(B, T, I) -> (B, T, H) with weights resident in VMEM."""
    path = resolve_dispatch(
        interpret=interpret, off_tpu="interpret", has_reference=False
    )
    interpret = path != "pallas"
    if block_batch is None:
        block_batch = 8 if interpret else 128
    b = xs.shape[0]
    h = u.shape[0]
    if h0 is None:
        h0 = jnp.zeros((b, h), xs.dtype)
    pad = (-b) % block_batch
    if pad:
        xs = jnp.concatenate(
            [xs, jnp.zeros((pad,) + xs.shape[1:], xs.dtype)], axis=0
        )
        h0 = jnp.concatenate([h0, jnp.zeros((pad, h), h0.dtype)], axis=0)
    out = _gru_seq_call(
        jnp.moveaxis(xs, 1, 0), w, u, b_i, b_h, h0,
        block_batch=block_batch, interpret=interpret,
    )
    return jnp.moveaxis(out, 0, 1)[:b]
