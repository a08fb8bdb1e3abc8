"""Integer GEMM kernel modeling the IC's HPE datapath (Section III-E).

HPE arithmetic: 14-bit activation x 8-bit weight multiplies into a 24-bit
saturating accumulator. On TPU the analogue is the MXU's native int8 path
with int32 accumulation. The MXU takes int8 operands only, so each
activation code is split into two int8 halves, x = 128 * hi + lo with
hi = x >> 7 (signed) and lo = x & 127, and the kernel accumulates
128 * (hi @ w) + lo @ w in int32. The split is exact for |x| < 2^14,
which covers Q6.8 codes and their ΔGRU deltas. The last K step
saturates to the 24-bit range, so results are bit-identical to the
hardware (for the network sizes involved, K <= 512, the exact int32 sum
cannot overflow before the final saturation: |x| < 2^14, |w| <= 2^7 ->
|x.w| < K * 2^21 < 2^31).

Grid = (M/BM, N/BN, K/BK), K sequential innermost; partial products
accumulate in an int32 VMEM scratch tile; the last K step saturates to
[-2^23, 2^23 - 1] and writes out.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

INT24_MAX = 2**23 - 1
INT24_MIN = -(2**23)
_LO_BITS = 7  # x = (hi << 7) + lo, lo in [0, 127]


def _intgemm_kernel(hi_ref, lo_ref, w_ref, out_ref, acc_ref, *, n_k: int):
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    w = w_ref[...]
    hi = jnp.dot(hi_ref[...], w, preferred_element_type=jnp.int32)
    lo = jnp.dot(lo_ref[...], w, preferred_element_type=jnp.int32)
    acc_ref[...] += hi * (1 << _LO_BITS) + lo

    @pl.when(k == n_k - 1)
    def _write():
        out_ref[...] = jnp.clip(acc_ref[...], INT24_MIN, INT24_MAX)


def intgemm_pallas(
    x: jnp.ndarray,  # (M, K) int activation codes, |x| < 2^14
    w: jnp.ndarray,  # (K, N) int8 weight codes
    *,
    block_m: int = 128,
    block_n: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    """Saturating 24-bit integer matmul -> (M, N) int32 codes."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    if m % block_m or n % block_n or k % block_k:
        raise ValueError(
            f"shapes ({m},{k})x({k},{n}) not multiples of blocks "
            f"({block_m},{block_k},{block_n})"
        )
    n_k = k // block_k
    x = x.astype(jnp.int32)
    hi = (x >> _LO_BITS).astype(jnp.int8)
    lo = (x & ((1 << _LO_BITS) - 1)).astype(jnp.int8)
    x_spec = pl.BlockSpec((block_m, block_k), lambda i, j, kk: (i, kk))
    return pl.pallas_call(
        functools.partial(_intgemm_kernel, n_k=n_k),
        grid=(m // block_m, n // block_n, n_k),
        in_specs=[
            x_spec,
            x_spec,
            pl.BlockSpec((block_k, block_n), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="kws_intgemm",
    )(hi, lo, w.astype(jnp.int8))
