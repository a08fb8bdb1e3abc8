"""The frontend's least time on the chip, its bytes (`bench.frontend_ops`)
over HBM bandwidth, over its measured device time per tick (what
``frontend_ms.audio`` reads). The chip's peaks list no float32 vector
rate, so the operations' side is not part of the share."""

from bench import frontend_ops


def read(ctx):
    ms = frontend_ops.frontend_ms(ctx)
    if not ms:
        return None
    rows = ctx.streams // ctx.chips
    least_s = (frontend_ops.frontend_bytes(ctx.cfg, rows)
               / ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / (ms * 1e-3)
