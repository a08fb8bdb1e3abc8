"""Model and frontend operations of one tick (every stream's hop: the
filter bank, log ROM and normalizer besides the GRU and head) over the
mean dispatch-to-scores time of a tick in the traced slice, as a share of
the chips' peak in the configuration's classifier precision."""

from bench import frontend_ops, ops


def read(ctx):
    if not ctx.tick_s:
        return None
    t = sum(ctx.tick_s) / len(ctx.tick_s)
    per_hop = ops.hop_ops(ctx.cfg) + frontend_ops.frontend_ops(ctx.cfg)
    rate = per_hop * ctx.streams / t
    return 100.0 * rate / (ctx.chips * ctx.peaks[ctx.cfg["peak"]])
