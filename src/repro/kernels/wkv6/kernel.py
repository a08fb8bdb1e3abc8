"""WKV6 recurrence Pallas kernel — the state-resident inner loop of the
RWKV6 backbone, and the direct scaled-up analogue of the paper's GRU
accelerator (state lives next to the compute; one frame in, one frame
out, nothing else moves).

Motivation measured in EXPERIMENTS.md §Perf cell C: the chunked XLA
formulation materializes (t, j, H, P) decay-ratio tensors in HBM —
~5 × 550 GB per training step at chunk 128. This kernel runs the exact
sequential recurrence

    y_t = r_t . (S + u ⊙ k_t v_t^T)
    S  <- diag(w_t) S + k_t v_t^T

with S (BB, P, P) pinned in VMEM scratch across the whole sequence: HBM
traffic is exactly one read of r/k/v/w and one write of y — zero
intermediate tensors. Grid = (B/BB, H, T) with T sequential (carry S).

Intended TPU layout: P=64 lanes x BB sublanes; the (BB, P, P) state is
BB*16 KB of VMEM (BB=8 -> 128 KB/core).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _wkv6_kernel(
    r_ref,  # (BB, 1, 1, P)
    k_ref,
    v_ref,
    w_ref,  # log-decay (<= 0)
    u_ref,  # (1, P) bonus for this head
    y_ref,  # (BB, 1, 1, P) output
    s_ref,  # scratch (BB, P, P): the resident state
):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _reset():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[:, 0, 0, :]  # (BB, P)
    k = k_ref[:, 0, 0, :]
    v = v_ref[:, 0, 0, :]
    w = jnp.exp(w_ref[:, 0, 0, :])  # decay in (0, 1]
    u = u_ref[0, :][None, :]  # (1, P)

    s = s_ref[...]  # (BB, P, P) keyed [key_dim, value_dim]
    kv = k[:, :, None] * v[:, None, :]  # (BB, P, P)
    y = jnp.sum(
        r[:, :, None] * (s + u[:, :, None] * kv), axis=1
    )  # (BB, P)
    s_ref[...] = s * w[:, :, None] + kv
    y_ref[:, 0, 0, :] = y.astype(y_ref.dtype)


def wkv6_pallas(
    r: jnp.ndarray,  # (B, T, H, P)
    k: jnp.ndarray,
    v: jnp.ndarray,
    logw: jnp.ndarray,  # (B, T, H, P), <= 0
    u: jnp.ndarray,  # (H, P)
    *,
    block_batch: int = 4,
    interpret: bool = False,
) -> jnp.ndarray:
    b, t, h, p = r.shape
    if b % block_batch:
        raise ValueError(f"B={b} not a multiple of {block_batch}")
    spec = pl.BlockSpec(
        (block_batch, 1, 1, p), lambda ib, ih, it: (ib, it, ih, 0)
    )
    return pl.pallas_call(
        _wkv6_kernel,
        grid=(b // block_batch, h, t),
        in_specs=[
            spec, spec, spec, spec,
            pl.BlockSpec((1, p), lambda ib, ih, it: (ih, 0)),
        ],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((b, t, h, p), r.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_batch, p, p), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(r, k, v, logw, u)
