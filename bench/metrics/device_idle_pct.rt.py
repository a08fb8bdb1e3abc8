"""Share of the traced slice in which no op ran on the device, as a
mean over the devices used."""


def read(ctx):
    busy = ctx.summary["busy_s"]
    span = ctx.summary["slice_s"]
    if not busy or span <= 0:
        return None
    return 100.0 * sum(1.0 - b / span for b in busy.values()) / len(busy)
