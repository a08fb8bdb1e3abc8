"""Pallas TPU kernels for the compute hot spots (validated in
interpret mode on CPU; BlockSpec layouts target TPU VMEM/MXU).

  fex_fused  — biquad filterbank + FWR + frame accumulation, fused
  gru        — weights-resident GRU sequence (the IC's WMEM insight)
  intgemm    — int16 x int8 -> saturating-int24 matmul (HPE datapath)
  tdc        — SRO DeltaSigma TDC + XOR diff + CIC decimation
  wkv6       — state-resident RWKV6 recurrence (the §Perf cell-C lever)

Dispatch-tier selection (pallas on TPU / interpret / reference, the
legacy ``interpret=`` flag, the `force_dispatch` override) is shared
across all kernels: `repro.kernels.dispatch`.
"""

from repro.kernels.dispatch import (
    DISPATCH_TIERS,
    dispatch_override,
    force_dispatch,
    resolve_dispatch,
)
from repro.kernels.fex_fused import fex_fused, fex_fused_ref
from repro.kernels.gru import gru_sequence, gru_sequence_ref
from repro.kernels.intgemm import intgemm, intgemm_ref
from repro.kernels.tdc import tdc_counts, tdc_counts_ref
from repro.kernels.wkv6 import wkv6, wkv6_ref

__all__ = [
    "DISPATCH_TIERS", "dispatch_override", "force_dispatch",
    "resolve_dispatch",
    "fex_fused", "fex_fused_ref",
    "gru_sequence", "gru_sequence_ref",
    "intgemm", "intgemm_ref",
    "tdc_counts", "tdc_counts_ref",
    "wkv6", "wkv6_ref",
]
