from repro.kernels.tick_fused.kernel import (
    gather_delta_intgemm,
    gather_delta_matmul,
    make_sparse_step,
    tick_fused_pallas,
)
from repro.kernels.tick_fused.ops import (
    mosaic_unsupported,
    over_stream_shards,
    resolve_tick_dispatch,
    tick_fused,
)
from repro.kernels.tick_fused.ref import tick_reference

__all__ = [
    "gather_delta_intgemm",
    "gather_delta_matmul",
    "make_sparse_step",
    "mosaic_unsupported",
    "over_stream_shards",
    "resolve_tick_dispatch",
    "tick_fused",
    "tick_fused_pallas",
    "tick_reference",
]
