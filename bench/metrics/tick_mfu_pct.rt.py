"""Model operations of one tick (every stream's hop) over the mean
dispatch-to-scores time of a tick in the traced slice, as a share of the
chips' peak in the configuration's classifier precision."""

from bench import ops


def read(ctx):
    if not ctx.tick_s:
        return None
    t = sum(ctx.tick_s) / len(ctx.tick_s)
    rate = ops.hop_ops(ctx.cfg) * ctx.streams / t
    return 100.0 * rate / (ctx.chips * ctx.peaks[ctx.cfg["peak"]])
