"""Model operations of the hops completed in the window, per second, as
a share of the chips' peak in the configuration's classifier precision."""

from bench import ops


def read(ctx):
    if not ctx.hops_in_window:
        return None
    rate = ops.hop_ops(ctx.cfg) * ctx.hops_in_window / ctx.window_s
    return 100.0 * rate / (ctx.chips * ctx.peaks[ctx.cfg["peak"]])
