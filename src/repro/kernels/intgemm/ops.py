"""Config-aware public entry point for the integer GEMM kernel.

`intgemm` picks one of three equivalent implementations per call:

  * ``pallas``    — the compiled Mosaic kernel (TPU), the MXU analogue
                    of the IC's 8-HPE int8 datapath;
  * ``interpret`` — the same kernel body run by the Pallas interpreter
                    (validates kernel logic on CPU CI);
  * ``reference`` — the exact jnp int32 matmul + final 24-bit saturation
                    (`intgemm_ref`; fastest off-TPU, bit-identical to
                    the kernel for all in-range inputs).

Tier selection, the legacy ``interpret=`` flag and the `force_dispatch`
override are the shared `repro.kernels.dispatch` machinery.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import resolve_dispatch
from repro.kernels.intgemm.kernel import intgemm_pallas
from repro.kernels.intgemm.ref import intgemm_ref

_intgemm_call = jax.jit(
    intgemm_pallas,
    static_argnames=("block_m", "block_n", "block_k", "interpret"),
)


def resolve_intgemm_dispatch(
    dispatch: str = "auto",
    interpret: Optional[bool] = None,
) -> str:
    """Resolve 'auto' to a concrete path for this backend.

    Off-TPU the interpreter is per-element slow and the jnp reference
    is bit-identical by contract (tests/test_kernels.py), so serving
    hot paths (the integer classifier tick) auto-select the reference.
    """
    return resolve_dispatch(dispatch, interpret, off_tpu="reference")


def intgemm(
    x: jnp.ndarray,  # (M, K) int activation codes, |x| < 2^14
    w: jnp.ndarray,  # (K, N) int8 weight codes
    block_m: Optional[int] = None,
    block_n: int = 128,
    block_k: int = 128,
    interpret: Optional[bool] = None,
    dispatch: str = "auto",
) -> jnp.ndarray:
    """Saturating-24-bit int matmul, any (M, K, N) via zero padding.

    ``block_m`` defaults to M rounded up to the int8 sublane tile (32),
    capped at 512 rows: one grid step per 512 streams at serving width.
    """
    path = resolve_intgemm_dispatch(dispatch, interpret)
    if path == "reference":
        return intgemm_ref(x, w)
    run_interpret = path == "interpret"
    m, k = x.shape
    n = w.shape[1]
    if block_m is None:
        block_m = min(512, -(-m // 32) * 32)
    pm, pk, pn = (-m) % block_m, (-k) % block_k, (-n) % block_n
    xp = jnp.pad(x.astype(jnp.int32), ((0, pm), (0, pk)))
    wp = jnp.pad(w.astype(jnp.int8), ((0, pk), (0, pn)))
    out = _intgemm_call(
        xp, wp,
        block_m=block_m, block_n=block_n, block_k=block_k,
        interpret=run_interpret,
    )
    return out[:m, :n]
