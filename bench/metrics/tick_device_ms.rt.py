"""Device busy milliseconds per tick: the union of op intervals in the
traced slice over its ticks, on the busiest device."""


def read(ctx):
    busy = ctx.summary["busy_s"]
    if not busy or not ctx.slice_ticks or max(busy.values()) <= 0:
        return None
    return 1e3 * max(busy.values()) / ctx.slice_ticks
