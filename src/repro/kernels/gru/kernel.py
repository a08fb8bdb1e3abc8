"""Weights-resident GRU sequence kernel.

TPU transcription of the IC's accelerator (Section III-E): the whole
24 KB weight memory lives next to the MACs (WMEM SRAM) and never moves
during inference. Here the layer weights are pinned in VMEM across every
time step (constant-index BlockSpecs load them once), and the hidden
state h lives in VMEM scratch — nothing round-trips HBM except one frame
of input in and one frame of logits/hidden out per step.

Grid = (B/BB, T) with T sequential. Per step:
    gi = x_t @ W + b_i          (BB, 3H)
    gh = h   @ U + b_h          (BB, 3H)
    r = sigmoid(gi_r + gh_r); z = sigmoid(gi_z + gh_z)
    n = tanh(gi_n + r * gh_n)
    h' = (1 - z) * n + z * h    (PyTorch GRU convention, like the paper)

Matmul shapes (BB x I x 3H) = (128, 16..48, 144): one MXU pass each.
VMEM: W + U + b = (I+H)*3H*4 B < 56 KB — trivially resident, same
work-fits-in-SRAM property the IC exploits.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _gru_seq_kernel(
    x_ref,  # (1, BB, I) this step's input
    w_ref,  # (I, 3H)
    u_ref,  # (H, 3H)
    bi_ref,  # (1, 3H)
    bh_ref,  # (1, 3H)
    h0_ref,  # (BB, H) initial state for this batch tile
    out_ref,  # (1, BB, H)
    h_ref,  # scratch (BB, H)
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _reset():
        h_ref[...] = h0_ref[...].astype(h_ref.dtype)

    h = h_ref[...]  # f32 scratch — state accumulates in f32
    x = x_ref[0, :, :].astype(jnp.float32)
    hdim = h.shape[-1]

    gi = (
        jnp.dot(x, w_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
        + bi_ref[0, :][None, :].astype(jnp.float32)
    )
    gh = (
        jnp.dot(h, u_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)
        + bh_ref[0, :][None, :].astype(jnp.float32)
    )
    i_r, i_z, i_n = gi[:, :hdim], gi[:, hdim : 2 * hdim], gi[:, 2 * hdim :]
    h_r, h_z, h_n = gh[:, :hdim], gh[:, hdim : 2 * hdim], gh[:, 2 * hdim :]
    r = jax.nn.sigmoid(i_r + h_r)
    z = jax.nn.sigmoid(i_z + h_z)
    n = jnp.tanh(i_n + r * h_n)
    h_new = (1.0 - z) * n + z * h

    h_ref[...] = h_new.astype(h_ref.dtype)
    out_ref[0, :, :] = h_new.astype(out_ref.dtype)


def gru_sequence_pallas(
    xs: jnp.ndarray,  # (T, B, I) time-major
    w: jnp.ndarray,  # (I, 3H)
    u: jnp.ndarray,  # (H, 3H)
    b_i: jnp.ndarray,  # (3H,)
    b_h: jnp.ndarray,  # (3H,)
    h0: jnp.ndarray,  # (B, H)
    *,
    block_batch: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns all hidden states, time-major (T, B, H)."""
    t, b, i = xs.shape
    h = u.shape[0]
    if b % block_batch:
        raise ValueError(f"B={b} not a multiple of block_batch={block_batch}")
    return pl.pallas_call(
        _gru_seq_kernel,
        grid=(b // block_batch, t),
        in_specs=[
            pl.BlockSpec((1, block_batch, i), lambda ib, it: (it, ib, 0)),
            pl.BlockSpec((i, 3 * h), lambda ib, it: (0, 0)),
            pl.BlockSpec((h, 3 * h), lambda ib, it: (0, 0)),
            pl.BlockSpec((1, 3 * h), lambda ib, it: (0, 0)),
            pl.BlockSpec((1, 3 * h), lambda ib, it: (0, 0)),
            pl.BlockSpec((block_batch, h), lambda ib, it: (ib, 0)),
        ],
        out_specs=pl.BlockSpec(
            (1, block_batch, h), lambda ib, it: (it, ib, 0)
        ),
        out_shape=jax.ShapeDtypeStruct((t, b, h), xs.dtype),
        scratch_shapes=[pltpu.VMEM((block_batch, h), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xs, w, u, b_i[None, :], b_h[None, :], h0)
