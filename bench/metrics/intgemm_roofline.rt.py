"""The int8 GEMMs' least time on the chip (the larger of operations over
the int8 peak and bytes over HBM bandwidth, from their shapes) over the
measured time of the intgemm kernel's events in the traced slice."""

from bench import ops


def read(ctx):
    n_events = sum(ctx.summary["kernel_n"].values())
    measured = sum(ctx.summary["kernel_s"].values())
    if not n_events or measured <= 0:
        return None
    rows = ctx.streams // ctx.chips
    per_tick = sum(
        ops.gemm_roofline_s(m, k, n, ctx.peaks["int8_ops_per_s"],
                            ctx.peaks["hbm_bytes_per_s"])[0]
        for _, m, k, n in ops.gemm_shapes(ctx.cfg, rows))
    return 100.0 * per_tick * ctx.slice_ticks * ctx.chips / measured
